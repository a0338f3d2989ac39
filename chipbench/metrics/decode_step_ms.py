"""Device time of one decode step: the programs that ran while
``runner.decode`` was open, the mean per call over the traced window,
averaged over the chips, in milliseconds."""
from chipbench.devcalls import span_device_s


def read(run):
    if run.trace is None:
        return None
    t = span_device_s(run.trace, "runner.decode")
    return None if t is None else t["seconds"] / t["calls"] * 1e3
