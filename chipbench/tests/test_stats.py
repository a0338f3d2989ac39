from types import SimpleNamespace

from chipbench import stats


def rec(due, times):
    return SimpleNamespace(due_at=due, token_times=times)


def test_nearest_rank_is_the_ceil_qn_th_order_statistic():
    v = list(range(1, 21))                       # 1..20
    assert stats.nearest_rank(v, 0.5) == 10
    assert stats.nearest_rank(v, 0.9) == 18
    assert stats.nearest_rank(v, 0.95) == 19     # not the max at n = 20
    assert stats.nearest_rank([3.0], 0.99) == 3.0
    assert stats.nearest_rank([], 0.5) is None


def test_ttft_counts_from_due_and_censors_at_the_close():
    reqs = [rec(1.0, [1.5, 1.6]),                 # 0.5
            rec(2.0, []),                         # none yet: 10 - 2 = 8
            rec(3.0, [11.0]),                     # after the close: 7
            rec(0.5, [1.0]),                      # due before the window
            rec(10.0, [10.1])]                    # due at the close: out
    assert sorted(stats.ttfts(reqs, 1.0, 10.0)) == [0.5, 7.0, 8.0]


def test_itl_pools_gaps_with_both_tokens_in_the_window():
    reqs = [rec(0.0, [0.5, 1.5, 1.7, 2.0, 3.5]),  # 0.5 is before the open
            rec(0.0, [1.0, 1.1])]
    gaps = sorted(round(g, 6) for g in stats.itls(reqs, 1.0, 3.0))
    assert gaps == [0.1, 0.2, 0.3]               # 3.5 is after the close
    assert stats.tokens_in(reqs, 1.0, 3.0) == 5
