"""The trace reduction on a small trace: two devices, a 1-second window,
a prefill and two decode steps with the host spans around them."""
import pytest

from chipbench import devcalls, devtrace, shapes


def small_trace():
    spans = [("engine.step", 0.10, 0.40), ("runner.prefill", 0.11, 0.35),
             ("engine.step", 0.45, 0.60), ("runner.decode", 0.46, 0.58),
             ("engine.step", 0.70, 0.85), ("runner.decode", 0.71, 0.84)]
    mods, ops = {}, {}
    for d in (0, 1):
        mods[d] = [("jit_prefill(3)", 0.12, 0.30), ("jit_insert(4)", 0.30, 0.32),
                   ("jit_step(5)", 0.47, 0.55), ("jit_step(5)", 0.72, 0.80),
                   ("jit_early(6)", 0.00, 0.05)]
        ops[d] = [("fusion.1", 0.12, 0.20), ("fusion.2", 0.20, 0.30),
                  ("copy.3", 0.30, 0.32), ("fusion.4", 0.47, 0.50),
                  ("all-reduce.7", 0.50, 0.55), ("fusion.4", 0.72, 0.78),
                  ("all-reduce.7", 0.78, 0.80), ("fusion.9", 0.00, 0.05)]
    return devtrace.Trace(mods, ops, spans, 0.1, 1.1)


def test_busy_idle_and_collectives():
    tr = small_trace()
    assert tr.window_s == pytest.approx(1.0)
    # ops inside the window: 0.12-0.32, 0.47-0.55, 0.72-0.80
    assert tr.busy_s() == pytest.approx(0.20 + 0.08 + 0.08)
    assert devtrace.collective_s(tr, 0) == pytest.approx(0.07)
    gaps = devtrace.gaps(tr.busy(0), tr.t0, tr.t1)
    assert [round(e - s, 6) for s, e in gaps] == [0.02, 0.15, 0.17, 0.3]


def test_nested_operations_count_their_own_time():
    evs = [("%while.3 = (s32[]) while(...)", 0.0, 1.0),
           ("%fusion.1 = bf16[8] fusion(...)", 0.1, 0.3),
           ("%fusion.2 = bf16[8] fusion(...)", 0.5, 0.6),
           ("%copy.7 = bf16[8] copy(...)", 1.2, 1.5)]
    own = {devtrace.op_kind(ev[0]) + str(ev[1]): t
           for ev, t in devtrace.self_times(evs, 0.0, 1.4)}
    assert own == pytest.approx({"while0.0": 0.7, "fusion0.1": 0.2,
                                 "fusion0.5": 0.1, "copy1.2": 0.2})


def test_union_merges_overlaps():
    assert devtrace.union([(0, 2), (1, 3), (5, 6), (5.5, 5.7)], 0.5, 5.8) \
        == [(0.5, 3), (5, 5.8)]


def test_device_time_is_found_by_host_spans():
    tr = small_trace()
    dec = devcalls.span_device_s(tr, "runner.decode")
    assert dec == {"calls": 2.0, "seconds": pytest.approx(0.16)}
    pre = devcalls.span_device_s(tr, "runner.prefill")
    assert pre == {"calls": 1.0, "seconds": pytest.approx(0.20)}
    assert devcalls.span_device_s(tr, "nothing") is None


def test_breakdown_names_programs_and_host_spans():
    b = devtrace.breakdown(small_trace())
    ops = dict((k, v) for k, v in b["device_ops"])
    assert ops["jit_prefill/fusion"] == pytest.approx(0.18)
    assert ops["jit_step/all-reduce"] == pytest.approx(0.07)
    idle = dict((k, v) for k, v in b["idle_gaps"])
    # idle 0.10-0.12, 0.32-0.47, 0.55-0.72, 0.80-1.10 against the spans
    assert idle["engine.step>runner.prefill"] == pytest.approx(0.01 + 0.03)
    assert idle["engine.step"] == pytest.approx(0.01 + 0.05 + 0.01 + 0.02
                                                + 0.01 + 0.01)
    assert idle["engine.step>runner.decode"] == pytest.approx(0.01 + 0.03
                                                              + 0.01 + 0.04)
    assert idle["none"] == pytest.approx(0.05 + 0.10 + 0.25)


def test_round_trip_through_json():
    tr = small_trace()
    back = devtrace.Trace.from_json(tr.to_json())
    assert back.busy_s() == tr.busy_s() and back.spans == tr.spans


def test_metric_readers_on_the_trace():
    """The trace-based readers on a record whose steps match the trace."""
    import json
    import os
    from types import SimpleNamespace

    from chipbench import bench
    from conftest import ROOT
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
    with open(os.path.join(root, "chipbench", "configs",
                           "danube3-4b.json")) as f:
        dims = shapes.Dims.of(json.load(f))
    steps = [SimpleNamespace(t0=0.10, t1=0.40, contexts=[], prefills=[1024]),
             SimpleNamespace(t0=0.45, t1=0.60, contexts=[1025], prefills=[]),
             SimpleNamespace(t0=0.70, t1=0.85, contexts=[1026], prefills=[])]
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    run = SimpleNamespace(trace=small_trace(), steps=steps, chips=2,
                          dims=dims, peaks=peaks, slots=10, max_len=4096,
                          window_steps=lambda: steps)
    read = lambda name: bench.reader(ROOT, name)(run)           # noqa: E731
    assert read("decode_step_ms") == pytest.approx(80.0)
    assert read("prefill_ms_per_ktok") == pytest.approx(200.0 / 1.024)
    least = sum(shapes.least_time(dims.decode([c]), peaks, 2)["seconds"]
                for c in (1025, 1026))
    assert read("decode_step_roofline") == pytest.approx(100 * least / 0.16)
    flops = dims.prefill(1024)["flops"]
    assert read("mfu.prefill") == pytest.approx(100 * flops / (2 * 197e12))
    assert read("device_idle_share") == pytest.approx(1 - 0.36)
    assert read("collective_share") == pytest.approx(0.07 / 0.36)
    assert read("batch_occupancy") == pytest.approx(0.1)
    assert read("kv_live_share") == pytest.approx((1025 + 1026) / 2 / 40960)
