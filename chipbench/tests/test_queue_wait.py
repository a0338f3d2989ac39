"""``queue_wait_p90_s``: the engine's own arrival and admission stamps, on
hand-built records and on a tiny run of the real engine."""
import json
import os
import shutil
from types import SimpleNamespace

import pytest

from chipbench import bench
from conftest import ROOT, run_tiny


def rec(sent_at, arrival, admitted):
    return SimpleNamespace(sent_at=sent_at, handle=SimpleNamespace(
        arrival=arrival, t_admitted=admitted))


def read(recs, t_open=10.0, t_close=20.0):
    run = SimpleNamespace(recs=recs, t_open=t_open, t_close=t_close)
    return bench.reader(ROOT, "queue_wait_p90_s")(run)


def test_waits_in_the_window_and_censored_at_the_close():
    recs = [rec(9.0, 9.0, 12.0),                     # arrived before: out
            *[rec(t, t + 1e-4, t + 1e-4 + w) for t, w in
              zip([11.0, 12.0, 13.0, 14.0, 15.0, 16.0, 17.0],
                  [0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7])],
            rec(18.0, 18.0, None),                   # not admitted: 2.0
            rec(19.0, 19.0, 21.5),                   # admitted after: 1.0
            SimpleNamespace(sent_at=None, handle=None)]   # never sent
    # nine waits: 0.1-0.7, 1.0, 2.0; the ceil(0.9 x 9) = 9th is 2.0
    assert read(recs) == pytest.approx(2.0)
    assert read(recs[:6]) == pytest.approx(0.5)      # 0.1-0.5: the 5th


@pytest.mark.parametrize("recs", [
    [],                                              # nothing sent
    [rec(5.0, 5.0, 6.0)],                            # nothing in the window
    [rec(11.0, 11.0, 11.5), rec(12.0, 0.7, 0.9)],    # another clock
], ids=["none_sent", "none_in_window", "engine_clock_not_the_clients"])
def test_nothing_to_read(recs):
    assert read(recs) is None


def test_a_tiny_run_reads_the_engine_stamps(tiny_bench, tmp_path):
    """On the real engine at tiny widths the engine's stamps are on the
    client's clock, so the metric reads a wait inside the run."""
    root = str(tmp_path / "copy")
    shutil.copytree(tiny_bench, root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    m, = [m for m in spec["per_layer"] if m["name"] == "queue_wait_p90_s"]
    spec["end_to_end"].append(dict(m, bound=0.01))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    res = run_tiny(root, "danube3-4b.code", seconds=3.0)
    assert res["correct"], res["checked"]
    assert 0.0 <= res["metrics"]["queue_wait_p90_s"]["value"] < 3.0
