"""CPU tests of the chip benchmark, run on their own:

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

Four virtual CPU devices stand in for a v5e host's four chips. The cells
run here at tiny widths, from a copy of the benchmark whose configurations
and mixes are cut down (``tiny_bench``).
"""
import json
import os
import shutil
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

TINY = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 8,
        "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 256}
# Readings at these sizes on the CPU: the program's widest gap 0.006-0.04
# over seeds, the float8 control's 0.3 and more.
TINY_LIMIT = 0.1
CELLS = {"danube3-4b.code": {"name": "danube3-4b.code",
                             "config": "danube3-4b", "traffic": "code",
                             "chips": 1, "why": "test"},
         "qwen3-14b-tp4.reasoning": {"name": "qwen3-14b-tp4.reasoning",
                                     "config": "qwen3-14b-tp4",
                                     "traffic": "reasoning", "chips": 4,
                                     "why": "test"}}


def make_tiny(dst: str) -> str:
    """A checkout-like directory whose BENCHMARK.json names the real cells,
    each on a tiny configuration and mix of the same kind."""
    bench = os.path.join(dst, "chipbench")
    shutil.copytree(os.path.join(ROOT, "chipbench"), bench,
                    ignore=shutil.ignore_patterns("tests", ".cache",
                                                  "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    # the reasoning cell on four chips waits for its chip runs (PERF.md,
    # Open questions); its configuration and mix are tested here already
    spec["workloads"] = [w for w in spec["workloads"]
                         if w["name"] not in CELLS] + [dict(c) for c in
                                                       CELLS.values()]
    for w in spec["workloads"]:
        for key, kind in (("config", "configs"), ("traffic", "traffic")):
            src = os.path.join(bench, kind, w[key] + ".json")
            with open(src) as f:
                data = json.load(f)
            if kind == "configs":
                data.update(TINY)
                if data.get("sliding_window"):
                    data["sliding_window"] = 64
                data["serve"].update(slots=4, max_len=256)
                data["check"]["widest_gap"] = TINY_LIMIT
            elif data["kind"] == "open_poisson":
                data.update(rate_rps=20.0, preroll_s=0.5,
                            prompt={"median": 64, "sigma": 0.6, "min": 16,
                                    "max": 128, "grid": 16},
                            output={"median": 8, "sigma": 0.8, "min": 2,
                                    "max": 32})
            else:
                data.update(clients=4,
                            prompt={"median": 20, "sigma": 0.45, "min": 16,
                                    "max": 64, "grid": 16},
                            output={"median": 150, "sigma": 1.05, "min": 16},
                            warm_start={"grid": 64})
            w[key] += "-tiny"
            with open(os.path.join(bench, kind, w[key] + ".json"), "w") as f:
                json.dump(data, f)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return dst


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    return make_tiny(str(tmp_path_factory.mktemp("tiny")))


def run_tiny(root: str, workload: str, seed: int = 2**31 + 5,
             seconds: float = 2.0, **kw):
    from chipbench import run
    return run.run_cell(root, workload, seed, seconds, False,
                        require_tpu=False, **kw)
