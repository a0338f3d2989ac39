"""Median gap between consecutive tokens of a request, pooled over every
gap with both tokens inside the window (host clock), in milliseconds."""
from chipbench import stats


def read(run):
    v = stats.nearest_rank(stats.itls(run.recs, run.t_open, run.t_close), 0.5)
    return None if v is None else v * 1e3
