"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout. The first line names the devices; without a
TPU, or with fewer chips than the cell asks for, the run exits non-zero and
prints no result. Set-up draws the weights on the device from ``--seed``,
compiles or loads every program the cell's traffic uses, and starts the
traffic; then the window measures for ``--seconds``. With ``--trace 0`` the
result holds the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window. After the
window the served tokens are checked against the plain reference
(``chipbench/check.py``). The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` ``breakdown``, and last ``checked``, each compared
number beside its limit.

JAX's persistent compilation cache is kept in ``chipbench/.cache/jax``
inside the checkout.
"""
import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import gc                # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402
from typing import Dict, List, Optional   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[0] = ROOT          # import chipbench as a package, not its files

from chipbench import bench, check, devtrace, shapes, stats    # noqa: E402
from chipbench import traffic as traffic_lib                   # noqa: E402
from chipbench.compiles import CompileCounter                  # noqa: E402
from chipbench.harness import (SPANS, WINDOW_SPAN, Annotator, Client,  # noqa
                               warm)

CACHE_DIR = os.path.join(ROOT, "chipbench", ".cache", "jax")
SETTLE_LIMIT_S = 600.0       # a warm start that takes longer is a fault


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""
    cell: str
    seconds: float
    t_open: float
    t_close: float
    setup_s: float
    recs: List
    steps: List
    slots: int
    max_len: int
    chips: int
    dims: shapes.Dims
    peaks: Dict[str, float]
    compiles_in_window: int
    trace: Optional[devtrace.Trace] = None

    def window_steps(self):
        """Engine steps that started in the window."""
        return [s for s in self.steps if self.t_open <= s.t0 < self.t_close]


def model_config(cfg: Dict):
    """The program's ModelConfig for a configuration file: the registry's
    entry for ``serve.arch``, with every size as the file states it."""
    from repro.configs.registry import get_config
    base = get_config(cfg["serve"]["arch"])
    window = cfg.get("sliding_window")
    run = dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        attention="swa" if window else "full",
        swa_window=window or base.swa_window, qk_norm=cfg["qk_norm"],
        tie_embeddings=cfg["tie_word_embeddings"])
    if base.family != "dense" or base.attention not in ("full", "swa"):
        raise ValueError(f"{base.name}: the reference covers dense "
                         "full or sliding-window attention only")
    return run


def build(cfg: Dict, seed: int, devices):
    """The system under test: ``build_real_engine`` on the cell's mesh."""
    import jax.numpy as jnp
    from repro.launch.serve import build_real_engine
    from repro.parallel.sharding import ParallelContext, make_mesh
    mesh = cfg["serve"]["mesh"]
    ctx = None
    if mesh != [1, 1]:
        ctx = ParallelContext(mesh=make_mesh(
            tuple(mesh), ("data", "model"),
            devices=devices[:mesh[0] * mesh[1]]))
    return build_real_engine(model_config(cfg), dtype=jnp.bfloat16,
                             max_slots=cfg["serve"]["slots"],
                             max_len=cfg["serve"]["max_len"], seed=seed,
                             ctx=ctx)


def check_layout(cfg: Dict, params) -> None:
    """The reference draws the weights by the recipe of
    ``reference.leaves``; refuse a program whose parameters have other
    shapes (padded or tiled heads), which that recipe does not cover."""
    import jax
    from chipbench.reference import leaves
    want = {name: shape for name, shape, _, _ in leaves(cfg)}
    got = {"/".join(str(k.key) for k in path): tuple(leaf.shape)
           for path, leaf in jax.tree_util.tree_leaves_with_path(params)}
    if got != want:
        raise SystemExit(f"parameter layout differs from the reference's "
                         f"recipe: {sorted(set(got.items()) ^ set(want.items()))}")


def fresh_engine(eng0):
    """A new engine (scheduler, allocator, admission estimator) over the
    same runner, so that warm-up leaves nothing behind but compiled code."""
    from repro.core.engine import InferenceEngine
    return InferenceEngine(eng0.cfg_model, eng0.ecfg, eng0.runner,
                           virtual_clock=False)


def free(eng) -> None:
    """Delete the program's weights and cache on the device."""
    import jax
    for leaf in jax.tree_util.tree_leaves((eng.runner.params,
                                           eng.runner.state)):
        leaf.delete()
    gc.collect()


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True, control: bool = False,
             counter: Optional[CompileCounter] = None,
             rate: Optional[float] = None,
             t_start: float = T_START) -> Dict:
    """One run of one cell; returns the result object. ``control`` also
    reads the float8 control's gap, ``rate`` replaces an open loop's
    arrival rate, and set-up counts from ``t_start``
    (``chipbench/calibrate.py`` makes several runs in one process)."""
    import jax
    if seed < 0:
        raise SystemExit("--seed must be a whole number >= 0")
    cell = bench.load(root, workload)
    if rate is not None:
        cell.mix["rate_rps"] = rate
    devices = jax.devices()
    d0 = devices[0]
    print(f"[device] platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    if require_tpu:
        if d0.platform != "tpu":
            raise SystemExit(f"no TPU: JAX found {d0.platform}")
        if len(devices) < cell.chips:
            raise SystemExit(f"{workload} needs {cell.chips} chips, "
                             f"JAX found {len(devices)}")
    with open(os.path.join(root, "chipbench", "peaks.json")) as f:
        peaks_table = json.load(f)
    if require_tpu and d0.device_kind not in peaks_table:
        raise SystemExit(f"no peaks for device kind {d0.device_kind!r}")
    peaks = peaks_table.get(d0.device_kind, {"flops_per_s": float("nan"),
                                             "bytes_per_s": float("nan")})
    used = devices[:cell.chips]
    cfg = cell.config
    slots, max_len = cfg["serve"]["slots"], cfg["serve"]["max_len"]
    counter = counter or CompileCounter()

    # ---------------------------------------------------------- set-up
    eng0 = build(cfg, seed, used)
    jax.block_until_ready((eng0.runner.params, eng0.runner.state))
    check_layout(cfg, eng0.runner.params)
    lengths = traffic_lib.prompt_lengths(cell.mix, max_len)
    warm(eng0, lengths, cfg["vocab_size"])
    say(counter.line("warm-up") + f" prefill_lengths={len(lengths)}")
    eng = fresh_engine(eng0)
    del eng0
    reqs = traffic_lib.generate(cell.mix, seconds, cfg["vocab_size"],
                                max_len, seed)
    ann = Annotator(trace)
    drv = Client(eng, cell.mix["kind"] == "closed_loop", reqs, ann)
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    os.sync()        # the compile cache's writes land now, not in the window
    t0 = time.perf_counter()
    drv.start(t0)
    if cell.mix["kind"] == "closed_loop":
        drv.pump(t0 + SETTLE_LIMIT_S, stop=drv.settled)
        if not drv.settled():
            raise RuntimeError("the warm start did not settle")
        if trace:
            jax.profiler.start_trace(tdir)
        t_open = time.perf_counter()
    else:
        t_open = t0 + cell.mix["preroll_s"]
        if trace:
            drv.pump(t_open - 1.0)
            jax.profiler.start_trace(tdir)
        drv.pump(t_open)
    setup_s = t_open - t_start
    n_before = counter.n

    # ---------------------------------------------------------- window
    t_close = t_open + seconds
    ann.begin(WINDOW_SPAN)
    drv.pump(t_close)
    ann.end(WINDOW_SPAN)
    if trace:
        jax.profiler.stop_trace()
    compiles_in_window = counter.n - n_before
    say(counter.line("window") + f" in_window={compiles_in_window}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)

    sent = [r for r in drv.recs if r.sent_at is not None]
    late = stats.nearest_rank([r.sent_at - r.due_at for r in sent
                               if r.req.due is not None], 0.95)
    waiting = sum(not r.token_times or r.token_times[0] >= t_close
                  for r in sent)
    say(f"[traffic] sent={len(sent)} due_in_window="
        f"{sum(t_open <= r.due_at < t_close for r in sent)} "
        f"finished={sum(r.finished for r in sent)} "
        f"no_first_token_at_close={waiting} generator_late_p95_s={late}")
    worst = max((r for r in sent if r.req.due is not None),
                key=lambda r: r.sent_at - r.due_at, default=None)
    if worst is not None:
        say(f"[traffic] generator_late_max_s={worst.sent_at - worst.due_at} "
            f"at={worst.due_at - t_open:.3f}")

    say_steps(drv.steps, t_open, t_close)
    run = Run(workload, seconds, t_open, t_close, setup_s, drv.recs,
              drv.steps, slots, max_len, cell.chips, shapes.Dims.of(cfg),
              peaks, compiles_in_window)
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result: Dict = {}
    if trace:
        run.trace = devtrace.load(tdir, SPANS, WINDOW_SPAN)
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        result["breakdown"] = devtrace.breakdown(run.trace)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = bench.reader(root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # ---------------------------------------------------------- check
    items = check.sample(sent, seed)
    free(eng)
    del eng, drv
    gc.collect()
    checked = check_outputs(cfg, sent, items, seed, used, control)
    limit = cfg["check"]["widest_gap"]
    correct = (checked["wrong_lengths"]["value"] == 0
               and "widest_gap" in checked and limit is not None
               and checked["widest_gap"]["value"] <= limit
               and checked["tokens_compared"]["value"] >= check.MIN_TOKENS)
    for k, v in checked.items():
        say(f"[checked] {k} value={v['value']} limit={v['limit']}")
    return {"correct": bool(correct), "attempted": len(sent),
            "failed": sum(r.error is not None for r in sent),
            "metrics": metrics, "device": device, **result,
            "checked": checked}


def say_steps(steps: List, t_open: float, t_close: float, top: int = 5):
    """The window's longest engine steps and longest host gaps between
    steps, each with its start after the window opened: where a stall
    shows, and whether it was a step (and what it did) or the host."""
    ws = [s for s in steps if t_open <= s.t0 < t_close]
    if not ws:
        return
    dur = sorted(s.t1 - s.t0 for s in ws)
    say(f"[steps] n={len(ws)} median_s={dur[len(dur) // 2]:.4f} "
        f"max_s={dur[-1]:.4f}")
    for s in sorted(ws, key=lambda s: s.t0 - s.t1)[:top]:
        say(f"[steps] long at={s.t0 - t_open:.3f} s={s.t1 - s.t0:.4f} "
            f"decode={len(s.contexts)} prefills={s.prefills}")
    gaps = sorted(((b.t0 - a.t1, a.t1) for a, b in zip(ws, ws[1:])),
                  reverse=True)[:top]
    say("[steps] host_gaps " + " ".join(f"{g:.4f}@{t - t_open:.3f}"
                                        for g, t in gaps))


def check_outputs(cfg: Dict, sent: List, items: List, seed: int, used,
                  control: bool) -> Dict:
    """The numbers ``correct`` compares, each beside its limit: requests
    the engine ended with another token count than asked, the widest gap of the
    sample's served tokens under the plain reference (and the float8
    control's, with ``control``), and how many tokens were compared."""
    from chipbench.reference import Reference
    limit = cfg["check"]["widest_gap"]
    checked = {"wrong_lengths": {"value": sum(
        r.finished and len(r.tokens) != r.req.max_new for r in sent),
        "limit": 0}}
    say(f"[check] sample requests={len(items)} served_tokens="
        f"{sum(len(t) for _, t in items)} longest_context="
        f"{max((len(p) + len(t) for p, t in items), default=0)}")
    t_ref = time.perf_counter()
    tokens = 0
    if items:
        ref = Reference(cfg, seed, used)
        gaps = check.served_gaps(ref, items, control=control)
        ref.free()
        checked["widest_gap"] = {"value": gaps["widest_gap"], "limit": limit}
        if control:
            checked["control_gap"] = {"value": gaps["control_gap"],
                                      "limit": limit}
        tokens = int(gaps["tokens"])
    checked["tokens_compared"] = {"value": tokens, "limit": check.MIN_TOKENS}
    say(f"[check] reference_s={time.perf_counter() - t_ref:.3f}")
    return checked


def setup_jax() -> None:
    """Import the program from ``src``, keep the TPU runtime's logs under
    TMPDIR (it writes to /tmp/tpu_logs unless told), and keep every
    compiled program in the checkout's persistent cache."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(
        tempfile.gettempdir(), "chipbench-tpu-logs"))
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    setup_jax()
    result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
