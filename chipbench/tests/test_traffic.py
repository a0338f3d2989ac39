import json
import os
from collections import Counter

import pytest

from chipbench import traffic
from conftest import run_tiny

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def mix(name):
    with open(os.path.join(ROOT, "chipbench", "traffic", name + ".json")) as f:
        return json.load(f)


def sizes(reqs):
    return Counter((len(r.prompt), r.max_new) for r in reqs)


@pytest.mark.parametrize("name", ["code", "reasoning"])
def test_same_seed_same_requests_other_seed_same_sizes(name):
    m = mix(name)
    a = traffic.generate(m, 30, 32000, 4096, 7)
    b = traffic.generate(m, 30, 32000, 4096, 7)
    c = traffic.generate(m, 30, 32000, 4096, 2**31 + 99)
    assert [(r.prompt, r.max_new, r.due) for r in a] == \
        [(r.prompt, r.max_new, r.due) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    assert sizes(a) == sizes(c)                  # the seed changes no size
    if name == "code":                           # and, in an open loop, no
        assert [(len(r.prompt), r.max_new, r.due) for r in a] == \
            [(len(r.prompt), r.max_new, r.due) for r in c]   # arrival


def test_code_mix_grid_and_schedule():
    m = mix("code")
    reqs = traffic.generate(m, 30, 32000, 4096, 3)
    assert len(reqs) == round(m["rate_rps"] * (m["preroll_s"] + 30))
    due = [r.due for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < m["preroll_s"] + 30
    for r in reqs:
        assert len(r.prompt) % 256 == 0 and 256 <= len(r.prompt) <= 3584
        assert 4 <= r.max_new <= 256
        assert len(r.prompt) in traffic.prompt_lengths(m, 4096)


def test_reasoning_warm_start():
    m = mix("reasoning")
    reqs = traffic.generate(m, 30, 151936, 4096, 5)
    c, k = m["clients"], m["per_client"]
    assert len(reqs) == c * k
    grid = set(traffic.prompt_lengths(m, 4096))
    first = [r for i, r in enumerate(reqs) if i % k == 0]
    warm = [r for r in first if len(r.prompt) > 512]
    assert len(warm) >= c // 2                   # most start mid-chain
    for r in first:
        assert len(r.prompt) % 512 == 0 or len(r.prompt) <= 512
    for i, r in enumerate(reqs):
        assert r.client == i // k
        assert len(r.prompt) in grid
        assert len(r.prompt) + r.max_new - 1 <= 4096
        if i % k:
            assert len(r.prompt) % 64 == 0 and 64 <= len(r.prompt) <= 512


def test_unknown_kind_is_refused():
    with pytest.raises(ValueError, match="unknown traffic kind"):
        traffic.generate({"kind": "nope"}, 1, 10, 10, 0)


def test_a_new_mix_config_and_metric_are_files_and_entries(tiny_bench,
                                                           tmp_path):
    """A later cell is new files and new entries: a copy of the tiny
    benchmark gains a mix, a configuration, a metric and a cell without a
    line of code changing, and a run of that cell reports the metric."""
    import shutil
    root = str(tmp_path / "copy")
    shutil.copytree(tiny_bench, root)
    bench = os.path.join(root, "chipbench")
    with open(os.path.join(bench, "traffic", "code-tiny.json")) as f:
        m = json.load(f)
    m.update(rate_rps=30.0)
    with open(os.path.join(bench, "traffic", "dummy.json"), "w") as f:
        json.dump(m, f)
    shutil.copy(os.path.join(bench, "configs", "danube3-4b-tiny.json"),
                os.path.join(bench, "configs", "dummy-model.json"))
    with open(os.path.join(bench, "metrics", "requests_sent.py"), "w") as f:
        f.write("def read(run):\n"
                "    return sum(r.sent_at is not None for r in run.recs)\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "dummy-model.dummy",
                              "config": "dummy-model", "traffic": "dummy",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "requests_sent", "unit": "count",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["dummy-model.dummy"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    res = run_tiny(root, "dummy-model.dummy", seconds=3.0)
    assert res["correct"], res["checked"]
    assert res["metrics"]["requests_sent"]["value"] == res["attempted"] > 0
    from chipbench.bench import load              # listed for another cell
    other = load(root, "danube3-4b.code")
    assert "requests_sent" not in [m["name"] for m in other.end_to_end]
