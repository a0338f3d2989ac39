"""90th percentile (nearest rank) of the time to first token, from each
request's due time, over every request due in the window; one with no
first token by the close counts at its wait so far (host clock). The
queue in front of the slots and the prefills ahead of a request make most
of it. A multi-second stall of the host lengthens it for every request
that queues behind the stall, which is why it is read per layer and not
bounded end to end."""
from chipbench import stats


def read(run):
    return stats.nearest_rank(stats.ttfts(run.recs, run.t_open, run.t_close),
                              0.9)
