"""Share of the decode step's roofline, in percent: the least time the
window's decode steps need on these chips (their algorithmic operations
and bytes from the shapes, ``chipbench/shapes.py``, over the peaks) over
the device time of the decode steps."""
import sys

from chipbench.devcalls import bound, decode_work, least_s, span_device_s


def read(run):
    if run.trace is None:
        return None
    t = span_device_s(run.trace, "runner.decode")
    work = decode_work(run)
    if t is None or not work:
        return None
    if int(t["calls"]) != len(work):
        print(f"[decode_step_roofline] {int(t['calls'])} decode calls "
              f"traced, {len(work)} decode steps recorded", file=sys.stderr)
    print(f"[decode_step_roofline] bound={bound(run, work)}",
          file=sys.stderr)
    return 100.0 * least_s(run, work) / t["seconds"]
