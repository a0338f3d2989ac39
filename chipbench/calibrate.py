"""Several runs of one cell in one process, each read with the float8
control beside it: the readings that set a cell's correctness limit and,
with ``--rates``, the sweep that finds an open loop's knee.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1 2 3 ... [--rates 1.5 2 2.5 ...]

Each run is made as ``run.py`` makes it (compiled programs are kept in the
process and in the checkout's cache, so ``setup_s`` here is not a cold or a
warm process's). ``--rates``, one for each seed, replaces the mix's
``rate_rps``. The lower reading of the limit is the largest widest gap over
the seeds, the upper the smallest control gap;
``chipbench/configs/<config>.json`` ``check.widest_gap`` is set between
them. A rate is sustained when the requests due in the window are served
without a backlog that grows: few lack a first token at the close, and time
to first token stays near its unloaded value; the mix takes 0.8 x the
highest such rate. PERF.md gives the readings. One line per run, and last
one JSON object.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="+")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    rates = args.rates or [None] * len(args.seeds)
    if len(rates) != len(args.seeds):
        raise SystemExit("one seed for each rate")
    sys.path[0] = ROOT
    from chipbench import run
    from chipbench.compiles import CompileCounter
    run.setup_jax()
    counter = CompileCounter()
    rows = []
    for rate, seed in zip(rates, args.seeds):
        res = run.run_cell(ROOT, args.workload, seed, args.seconds, False,
                           control=True, counter=counter, rate=rate,
                           t_start=time.perf_counter())
        row = {"rate_rps": rate, "seed": seed, "correct": res["correct"],
               "attempted": res["attempted"],
               **{k: v["value"] for k, v in res["metrics"].items()},
               **{k: v["value"] for k, v in res["checked"].items()},
               "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
        print(f"[calibrate] {json.dumps(row)}", flush=True)
        rows.append(row)
    lower = max(r["widest_gap"] for r in rows)
    upper = min(r["control_gap"] for r in rows)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper, "ratio": upper / lower if lower else None,
                      "rows": rows}))


if __name__ == "__main__":
    main()
