"""90th percentile (nearest rank) of the time a request waited for a
slot, from its arrival to its admission as the engine stamped them
(``Request.arrival`` at ``submit``, ``t_admitted`` when the scheduler
admitted it), over every request that arrived in the window. One not
admitted by the close counts its wait so far. Unlike ``queue_ttft_p90_s``
it leaves out the request's own prefill and the prefills ahead of it.

The engine's stamps are read only where they are on the client's clock
(``time.perf_counter()``): an engine that stamps a request as arriving
before the client sent it keeps another clock, and the run holds nothing
to read."""
from chipbench import stats


def read(run):
    sent = [r for r in run.recs
            if r.handle is not None and r.sent_at is not None]
    if not sent or any(r.handle.arrival < r.sent_at for r in sent):
        return None
    waits = []
    for r in sent:
        arrival, admitted = r.handle.arrival, r.handle.t_admitted
        if not run.t_open <= arrival < run.t_close:
            continue
        if admitted is None or admitted > run.t_close:
            admitted = run.t_close
        waits.append(admitted - arrival)
    return stats.nearest_rank(waits, 0.9)
