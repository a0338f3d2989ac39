"""repro.obs: exact request spans, windowed time-series, regime
classification, the bottleneck report, Perfetto export, the CLI, and the
benchmark ``--report`` wiring. The two load-bearing guarantees:

  * span decompositions sum to measured end-to-end latency *exactly* (ulp
    equality, on every finished request of every cluster shape);
  * attaching obs to a run leaves its metrics byte-identical (pure
    stream consumer, REP009)."""
import dataclasses
import json
import os
import sys
from fractions import Fraction

import pytest

from repro.obs import (PHASES, REGIMES, RegimeRules, WindowStats, attach,
                       attribute, bottleneck_report, build_windows, classify,
                       fold_spans, regime_fractions, render_text,
                       to_chrome_trace)
from repro.obs.__main__ import main as obs_main
from repro.scenario import (ModelRef, Scenario, Traffic, WorkerGroup,
                            get_scenario, requests)
from repro.trace import dump_events

COLOCATED = "ds8b-4xh200-colocated"
DISAGG = "ds8b-4xh200-disagg"
ELASTIC = "ds8b-autoscale-diurnal"


def _shrunk(name, n=14, **changes):
    sc = get_scenario(name)
    return dataclasses.replace(
        sc, traffic=dataclasses.replace(sc.traffic, n_requests=n, **changes))


def _cluster_run(sc):
    rt = sc.to_cluster()
    rt.events.enable_recording()
    rt.submit_trace(sc.trace())
    rt.run()
    return rt


def _finished(events):
    return {e.ref.rid: e.ref for e in events if e.kind == "finish"}


# one engine-fidelity scenario family for seeded regime traces: a closed
# reasoning burst against a configurable pool/cap (the capacity-trap shape)
def _trap(max_seqs, n=40, n_pages=None, cap_tokens=10 ** 9,
          max_steps=400_000):
    fleet = WorkerGroup(role="colocated", count=1, admission="naive",
                        max_seqs=max_seqs,
                        **({"n_pages": n_pages} if n_pages else {}))
    sc = Scenario(name=f"obs-trap-{max_seqs}", model=ModelRef("ds-distill-8b"),
                  fleet=(fleet,),
                  traffic=Traffic(process="closed", workload="reasoning",
                                  n_requests=n, osl_cap=8000, seed=1))
    eng = sc.to_engine()
    eng.events.enable_recording()
    capacity = eng.alloc.n_pages * eng.alloc.page_size
    for isl, osl in requests(sc):
        osl = min(osl, cap_tokens, max(capacity - isl - 2, 1))
        eng.submit(int(isl), int(osl), arrival=0.0)
    eng.run(max_steps=max_steps)
    return eng


# ------------------------------------------------------------ span exactness
@pytest.mark.parametrize("name,n", [(COLOCATED, 20), (DISAGG, 16),
                                    (ELASTIC, 30)])
def test_span_sum_equals_e2e_to_the_last_ulp(name, n):
    """The headline guarantee, on all three cluster shapes: per-phase
    durations telescope exactly — as rationals AND as correctly-rounded
    floats — to the measured end-to-end latency of every finished
    request."""
    rt = _cluster_run(_shrunk(name, n))
    events = rt.events.events
    by_rid = _finished(events)
    fold = fold_spans(events)
    assert len(fold.spans) == len(by_rid) > 0
    for s in fold.spans:
        r = by_rid[s.rid]
        assert s.exact_total == Fraction(r.t_finished) - Fraction(r.arrival)
        assert s.total_s == r.e2e()          # float ==, deliberately
        assert all(f >= 0 for f in s.phase_fracs.values())


def test_disagg_spans_carry_migration_and_kv_transfer():
    events = _cluster_run(_shrunk(DISAGG, 16)).events.events
    fold = fold_spans(events)
    migrated = [s for s in fold.spans if len(s.workers) > 1]
    assert migrated, "disagg run produced no migrated spans"
    for s in migrated:
        assert s.phase_fracs["kv_transfer"] > 0
        # prefill happened on a prefill-role worker, decode on the adopter
        assert s.workers[0] != s.workers[-1]


def test_span_segments_tile_the_request_lifetime():
    events = _cluster_run(_shrunk(COLOCATED, 12)).events.events
    for s in fold_spans(events).spans:
        assert s.segments, s.rid
        assert s.segments[0].t0 == s.arrival
        assert s.segments[-1].t1 == s.t_finished
        for a, b in zip(s.segments, s.segments[1:]):
            assert a.t1 == b.t0              # contiguous, no gaps/overlap
            assert a.t0 < a.t1
        assert {seg.phase for seg in s.segments} <= set(PHASES)


def test_truncated_trace_leaves_open_spans_not_garbage():
    eng = _trap(max_seqs=2048, n=40, n_pages=400, max_steps=4000)
    events = eng.events.events
    fold = fold_spans(events)
    assert fold.open_spans                   # run was cut mid-flight
    rep = bottleneck_report(events)
    assert rep["requests"]["n_unfinished"] == len(fold.open_spans)


# ----------------------------------------------------------------- windows
def test_windows_are_deterministic_across_same_seed_runs():
    a = build_windows(_cluster_run(_shrunk(COLOCATED, 14)).events.events)
    b = build_windows(_cluster_run(_shrunk(COLOCATED, 14)).events.events)
    assert a.workers == b.workers
    assert a.window_s == b.window_s
    for w in a.workers:
        assert a.by_worker[w] == b.by_worker[w]   # dataclass field equality


def test_window_token_counts_are_exact():
    """decode/prefill tokens come from per-step events, not snapshot
    subsampling: window sums must equal the stream's own totals."""
    events = _cluster_run(_shrunk(COLOCATED, 12)).events.events
    ws = build_windows(events)
    decode = sum(len(e.payload["rids"]) for e in events
                 if e.kind == "decode_step")
    prefill = sum(e.payload["chunk"] for e in events if e.kind == "prefill")
    assert sum(w.decode_tokens for w in ws.all_windows()) == decode
    assert sum(w.prefill_tokens for w in ws.all_windows()) == prefill


def test_windows_see_migration_traffic_on_the_destination():
    events = _cluster_run(_shrunk(DISAGG, 16)).events.events
    ws = build_windows(events)
    n_inject = sum(1 for e in events if e.kind == "inject")
    assert sum(w.migrations_in for w in ws.all_windows()) == n_inject
    assert sum(w.migrations_out for w in ws.all_windows()) == n_inject
    assert any(w.transfer_overlap_s > 0 for w in ws.all_windows())


def test_step_payload_feeds_windows_without_engine_access():
    """The PR-9 step-payload extension: absolute KV page counts and the
    live cap are in the stream, so windows get them post-hoc. The payload
    holds counts the engine has, and no modeled fraction."""
    events = _cluster_run(_shrunk(COLOCATED, 8)).events.events
    steps = [e for e in events if e.kind == "step"]
    assert steps
    for e in steps:
        assert set(e.payload) == {
            "running", "waiting", "kv_util", "kv_frag", "gen_tokens",
            "prefill_tokens", "preemptions", "kv_pages_used",
            "kv_pages_free", "max_seqs"}
    ws = build_windows(events)
    assert any(w.kv_pages_used_max > 0 for w in ws.all_windows())
    assert all(w.max_seqs > 0 for w in ws.all_windows() if w.n_samples)


# ----------------------------------------------------------------- regimes
def _w(**kw):
    base = dict(worker="w0", t0=0.0, t1=1.0)
    base.update(kw)
    return WindowStats(**base)


def test_classify_decision_table():
    r = RegimeRules()
    assert classify(_w(warming=True), r) == ("comms_bound", "cold_start")
    assert classify(_w(), r) == ("idle", "no_work")
    assert classify(_w(transfer_overlap_s=0.2), r) == \
        ("comms_bound", "starved_awaiting_kv_transfer")
    assert classify(_w(n_samples=4, running_max=8, decode_tokens=100,
                       preemptions=2), r) == \
        ("capacity_bound", "preemption_storm")
    assert classify(_w(n_samples=4, running_max=8, decode_tokens=100,
                       kv_util_max=0.95, waiting_mean=3.0), r) == \
        ("capacity_bound", "kv_throttled_admission")
    assert classify(_w(n_samples=4, running_max=2, decode_tokens=10,
                       transfer_overlap_s=0.6), r) == \
        ("comms_bound", "migration_dominated")
    assert classify(_w(n_samples=4, running_max=8, max_seqs=64,
                       waiting_mean=5.0, decode_tokens=100), r) == \
        ("queue_bound", "backlog_below_concurrency_cap")
    assert classify(_w(n_samples=4, running_max=64, max_seqs=64,
                       waiting_mean=5.0, decode_tokens=100), r) == \
        ("compute_bound", "busy_no_kv_pressure")


def test_seeded_capacity_bound_trace_classifies_capacity_bound():
    """High concurrency against a starved pool: preemption storms + KV
    saturation — the capacity trap — must read ``capacity_bound``."""
    eng = _trap(max_seqs=2048, n=40, n_pages=400, max_steps=15_000)
    ws = build_windows(eng.events.events)
    rep = attribute(ws)
    assert rep.dominant == "capacity_bound"
    assert rep.busy_fractions["capacity_bound"] > 0.5
    assert max(w.kv_util_max for w in ws.all_windows()) >= 0.99
    assert sum(w.preemptions for w in ws.all_windows()) > 0


def test_seeded_compute_bound_trace_classifies_compute_bound():
    """Same workload shape, ample KV, short outputs at a tight cap: the
    batch runs at its concurrency limit with no KV pressure."""
    eng = _trap(max_seqs=16, n=40, cap_tokens=400)
    ws = build_windows(eng.events.events)
    rep = attribute(ws)
    assert rep.dominant == "compute_bound"
    assert rep.worker_seconds["capacity_bound"] == 0.0
    assert max(w.kv_util_max for w in ws.all_windows()) < 0.5


def test_attribute_fractions_are_a_partition():
    events = _cluster_run(_shrunk(ELASTIC, 30)).events.events
    rep = attribute(build_windows(events))
    assert set(rep.worker_seconds) == set(REGIMES)
    assert abs(sum(rep.fractions.values()) - 1.0) < 1e-9
    total = sum(rep.worker_seconds.values())
    per_worker_total = sum(sum(v["seconds"].values())
                           for v in rep.per_worker.values())
    assert abs(total - per_worker_total) < 1e-9
    d = rep.to_dict()
    assert json.loads(json.dumps(d)) == d


# ------------------------------------------------- purity (REP009 end to end)
def test_attaching_obs_leaves_cluster_summary_byte_identical():
    sc = _shrunk(COLOCATED, 12)
    plain = _cluster_run(sc)
    base = json.dumps(plain.metrics.summary(), sort_keys=True)

    rt = sc.to_cluster()
    build = attach(rt.events)                # live subscriber tap
    rt.submit_trace(sc.trace())
    rt.run()
    assert json.dumps(rt.metrics.summary(), sort_keys=True) == base
    rep = build()
    assert rep["requests"]["n_finished"] == plain.metrics.summary()[
        "n_finished"]


def test_cluster_summary_regimes_param_merges_without_default_change():
    sc = _shrunk(COLOCATED, 10)
    rt = _cluster_run(sc)
    base = rt.metrics.summary()
    assert "regimes" not in base
    rep = bottleneck_report(rt.events.events)
    merged = rt.metrics.summary(regimes=regime_fractions(rep))
    assert merged["regimes"]["dominant"] == rep["regimes"]["dominant"]
    merged.pop("regimes")
    assert json.dumps(merged, sort_keys=True) == \
        json.dumps(base, sort_keys=True)


# ---------------------------------------------------------------- perfetto
def test_perfetto_export_is_valid_chrome_trace():
    events = _cluster_run(_shrunk(DISAGG, 16)).events.events
    ct = to_chrome_trace(events)
    assert set(ct) == {"traceEvents", "displayTimeUnit"}
    assert ct["displayTimeUnit"] == "ms"
    rows = ct["traceEvents"]
    assert json.loads(json.dumps(ct)) == ct     # pure-JSON serialisable

    workers = {e.worker for e in events if e.worker}
    procs = [r for r in rows
             if r["ph"] == "M" and r["name"] == "process_name"]
    assert len(procs) == len(workers)           # one track per worker
    assert {p["args"]["name"] for p in procs} == \
        {f"worker:{w}" for w in workers}
    pids = {p["pid"] for p in procs}
    assert len(pids) == len(procs)              # distinct tracks

    xs = [r for r in rows if r["ph"] == "X"]
    assert xs
    for r in xs:
        assert r["pid"] in pids and r["dur"] > 0 and r["ts"] >= 0
        assert r["name"] in PHASES
    cs = [r for r in rows if r["ph"] == "C"]
    assert {r["name"] for r in cs} == {"kv_pages", "batch"}
    assert all(r["ph"] in ("M", "X", "C") for r in rows)


# --------------------------------------------------------------------- CLI
def _write_trace(tmp_path, name=COLOCATED, n=10):
    events = _cluster_run(_shrunk(name, n)).events.events
    path = str(tmp_path / "trace.jsonl")
    dump_events(events, path)
    return path


def test_cli_report_text_and_json(tmp_path, capsys):
    path = _write_trace(tmp_path)
    assert obs_main(["report", path]) == 0
    out = capsys.readouterr().out
    assert "bottleneck report" in out and "dominant" in out
    assert obs_main(["report", path, "--json", "--window", "0.5"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["window_s"] == 0.5
    assert rep["regimes"]["dominant"] in REGIMES


def test_cli_perfetto_writes_loadable_json(tmp_path, capsys):
    path = _write_trace(tmp_path)
    out = str(tmp_path / "trace.perfetto.json")
    assert obs_main(["perfetto", path, "-o", out]) == 0
    with open(out) as f:
        ct = json.load(f)
    assert ct["traceEvents"]
    assert capsys.readouterr().out.startswith("wrote ")


def test_cli_exits_2_on_unreadable_or_empty_input(tmp_path, capsys):
    with pytest.raises(SystemExit) as exc:
        obs_main(["report", str(tmp_path / "missing.jsonl")])
    assert exc.value.code == 2
    bad = tmp_path / "bad.jsonl"
    bad.write_text("not json\n")
    with pytest.raises(SystemExit) as exc:
        obs_main(["report", str(bad)])
    assert exc.value.code == 2
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    with pytest.raises(SystemExit) as exc:
        obs_main(["perfetto", str(empty), "-o", str(tmp_path / "o.json")])
    assert exc.value.code == 2
    capsys.readouterr()


def test_render_text_mentions_every_regime_and_phase(tmp_path):
    events = _cluster_run(_shrunk(COLOCATED, 8)).events.events
    txt = render_text(bottleneck_report(events), title="x")
    for name in REGIMES + PHASES:
        assert name in txt


# ------------------------------------------------------- benchmark wiring
def _common():
    root = os.path.join(os.path.dirname(__file__), "..")
    if os.path.abspath(root) not in (os.path.abspath(p) for p in sys.path):
        sys.path.insert(0, root)
    from benchmarks import _common as mod
    return mod


def test_benchmark_report_flag_prints_after_engine_and_cluster_runs(capsys):
    mod = _common()
    sc = _shrunk(COLOCATED, 6)
    mod.set_report(True)
    try:
        mod.run_closed(sc, cap_tokens=64)
        out = capsys.readouterr().out
        assert "bottleneck report" in out and sc.name in out

        rt = mod.make_cluster(sc)
        rt.submit_trace(sc.trace())
        rt.run()
        out = capsys.readouterr().out
        assert "bottleneck report" in out       # printed on run_end
    finally:
        mod.set_report(False)
    mod.run_closed(sc, cap_tokens=64)
    assert "bottleneck report" not in capsys.readouterr().out


def test_run_closed_with_report_returns_both(capsys):
    mod = _common()
    sc = _shrunk(COLOCATED, 6)
    summary, rep = mod.run_closed_with_report(sc, cap_tokens=64)
    capsys.readouterr()
    assert summary["n_finished"] == rep["requests"]["n_finished"] == 6
    assert rep["regimes"]["dominant"] in REGIMES
