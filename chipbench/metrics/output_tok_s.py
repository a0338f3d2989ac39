"""Output tokens per second: every token that reached the client inside
the window, over the window (host clock)."""
from chipbench import stats


def read(run):
    return stats.tokens_in(run.recs, run.t_open, run.t_close) / run.seconds
