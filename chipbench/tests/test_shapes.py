import dataclasses
import json
import os

from chipbench import shapes
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def danube():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "danube3-4b.json")) as f:
        return shapes.Dims.of(json.load(f))


def test_danube_counts_by_hand():
    d = danube()
    # per layer: wq 3840x32x120, wk and wv 3840x8x120, wo 32x120x3840,
    # gate, up and down 3840x10240
    layer = 3840 * 3840 * 2 + 3840 * 960 * 2 + 3 * 3840 * 10240
    assert d.layer_matmul_params == layer
    weights = 2 * (24 * (layer + 2 * 3840) + 3840 + 3840 * 32000)
    assert d.weight_bytes == weights
    assert d.kv_bytes_per_position == 2 * 24 * 8 * 120 * 2    # 92,160 B

    # one decode step, two slots with 100 and 4000 keys (no window)
    dec = d.decode([100, 4000])
    flops = sum(2 * 24 * layer + 4 * 24 * 32 * 120 * keys + 2 * 3840 * 32000
                for keys in (100, 4000))
    assert dec["flops"] == flops
    assert dec["bytes"] == (weights + 2 * 3840 * 2
                            + (99 + 3999 + 2) * 92160 + 2 * 32000 * 2)

    # a 1000-token prefill: causal attention over 1000 * 1001 / 2 pairs
    pre = d.prefill(1000)
    assert pre["flops"] == (2 * 24 * layer * 1000
                            + 4 * 24 * 32 * 120 * 1000 * 1001 // 2
                            + 2 * 3840 * 32000)
    assert pre["bytes"] == (weights + 1000 * 3840 * 2 + 1000 * 92160
                            + 32000 * 2)


def test_window_caps_long_prefill_attention():
    d = dataclasses.replace(danube(), window=4096)
    n = 5000
    pairs = sum(min(q, 4096) for q in range(1, n + 1))
    attn = 4 * 24 * 32 * 120 * pairs
    assert d.prefill(n)["flops"] == (2 * 24 * d.layer_matmul_params * n
                                     + attn + 2 * 3840 * 32000)


def test_least_time_names_its_bound():
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    t = shapes.least_time({"flops": 197e12, "bytes": 819e9 / 2}, peaks, 1)
    assert t == {"seconds": 1.0, "bound": "compute"}
    t = shapes.least_time({"flops": 1.0, "bytes": 4 * 819e9}, peaks, 4)
    assert t == {"seconds": 1.0, "bound": "memory"}
