"""Device time in collective operations (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all) over device busy time,
the mean over the chips."""
from chipbench import devtrace


def read(run):
    if run.trace is None or len(run.trace.ops) < 2:
        return None
    shares = []
    for d in run.trace.ops:
        busy = sum(e - s for s, e in run.trace.busy(d))
        if busy > 0:
            shares.append(devtrace.collective_s(run.trace, d) / busy)
    return sum(shares) / len(shares) if shares else None
