"""Device time of prefill (the programs that ran while ``runner.prefill``
was open: the prefill, the insert of its cache into the slot, the argmax)
per 1000 prompt tokens prefilled in the traced window, averaged over the
chips."""
from chipbench.devcalls import span_device_s


def read(run):
    if run.trace is None:
        return None
    t = span_device_s(run.trace, "runner.prefill")
    tokens = sum(sum(s.prefills) for s in run.window_steps())
    if t is None or not tokens:
        return None
    return t["seconds"] * 1e3 / (tokens / 1e3)
