"""Share of prefill's roofline, in percent: the least time the window's
prefills need on these chips (2 x parameters x tokens plus causal
attention, and weights plus the new cache moved once, from the shapes)
over the device time of the prefills."""
import sys

from chipbench.devcalls import bound, least_s, prefill_work, span_device_s


def read(run):
    if run.trace is None:
        return None
    t = span_device_s(run.trace, "runner.prefill")
    work = prefill_work(run)
    if t is None or not work:
        return None
    print(f"[prefill_roofline] bound={bound(run, work)}", file=sys.stderr)
    return 100.0 * least_s(run, work) / t["seconds"]
