"""Seeded traffic from a mix file: what each request sends and when.

A mix is a JSON file under ``chipbench/traffic/`` whose ``kind`` names one of
the generators below; its other keys are the generator's parameters. A later
mix is a new file, never new code.

Every seed asks for the same work: the lengths and gaps are the
distribution's quantiles at the midpoints of ``n`` equal strata
(``stratified``). An open loop sends them in one order for every seed, drawn
from the mix's ``schedule_seed``: near its capacity a queue's tail depends on
the order of arrivals and sizes, not only on their set, so an order drawn
from the run's seed would change the work. There the run's seed draws the
token ids alone. A closed loop's seed deals fixed sets of requests to its
clients. So the spread between runs of different seeds is the system's, not
the generator's.

Length distributions are lognormal, as in the Natural-Reasoning fit of
``repro.data.reasoning`` (paper III-B: prompt median 95, sigma 0.45; output
median 4200, sigma 1.05), whose arithmetic is copied here so that a change
to the program cannot move the yardstick. Inter-arrival gaps of an open loop
are exponential, as ``repro.cluster.arrivals.PoissonProcess`` draws them.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    """One request as the client sends it. ``due`` is seconds after the
    traffic starts (open loop) or None (closed loop: due when the client's
    previous request ends). ``client`` is the closed-loop client, else -1."""
    prompt: List[int]
    max_new: int
    due: Optional[float] = None
    client: int = -1


def stratified(n: int, ppf) -> np.ndarray:
    """The ``ppf`` (inverse CDF) at the midpoints of ``n`` equal strata."""
    return np.array([ppf((i + 0.5) / n) for i in range(n)], np.float64)


def lognormal_ppf(median: float, sigma: float):
    z = NormalDist()
    return lambda p: median * math.exp(sigma * z.inv_cdf(p))


def lengths(spec: Dict, n: int, rng: np.random.Generator,
            cap: Optional[np.ndarray] = None) -> np.ndarray:
    """``n`` lengths from ``spec`` (lognormal ``median``/``sigma``, clipped
    to ``min``..``max``, rounded up to a multiple of ``grid``), permuted by
    ``rng``. ``cap`` (per position, after the permutation) clips further."""
    x = stratified(n, lognormal_ppf(spec["median"], spec["sigma"]))
    x = rng.permutation(x)
    lo, hi = spec.get("min", 1), spec.get("max", None)
    if cap is not None:
        hi = cap if hi is None else np.minimum(hi, cap)
    x = np.clip(x, lo, hi if hi is not None else np.inf)
    grid = spec.get("grid", 1)
    x = np.ceil(x / grid) * grid
    if hi is not None:
        # rounding up may cross the cap: step back a whole grid
        x = np.where(x > hi, np.floor(np.asarray(hi, np.float64) / grid) * grid, x)
    return x.astype(np.int64)


def tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(0, vocab, n).tolist()


def open_poisson(mix: Dict, seconds: float, vocab: int, max_len: int,
                 seed: int) -> List[Request]:
    """Independent users: requests due on a schedule whatever the server
    does. Exactly ``round(rate * (preroll + seconds))`` requests, due from 0
    to the end of the window, their gaps the exponential's stratified
    quantiles. The order of the gaps and of the requests comes from the
    mix's ``schedule_seed``, the same for every run; ``seed`` draws the
    token ids."""
    sched = np.random.default_rng(mix["schedule_seed"])
    span = mix["preroll_s"] + seconds
    n = max(int(round(mix["rate_rps"] * span)), 2)
    gaps = sched.permutation(stratified(n, lambda p: -math.log(1.0 - p)))
    due = np.cumsum(gaps)
    due *= span * n / (n + 1) / due[-1]
    isl, osl = _pairs(mix, n, max_len)
    order = sched.permutation(n)
    rng = np.random.default_rng(seed)
    return [Request(tokens(rng, int(isl[j]), vocab), int(osl[j]), float(t))
            for t, j in zip(due, order)]


def _pairs(mix: Dict, n: int, max_len: int):
    """``n`` (prompt, output) length pairs. Which prompt goes with which
    output is fixed (a permutation from a constant seed), so that every run
    asks for the same set of requests."""
    fixed = np.random.default_rng(0)
    isl = lengths(mix["prompt"], n, fixed)
    return isl, lengths(mix["output"], n, fixed, cap=max_len + 1 - isl)


def closed_loop(mix: Dict, seconds: float, vocab: int, max_len: int,
                seed: int) -> List[Request]:
    """``clients`` callers that each send their next request when the last
    one ends: ``per_client`` requests for each, listed client-major.

    Warm start: each client's first request is in the middle of its chain.
    A share of its output is sent as part of its prompt, so the window
    opens on a stationary mix of context lengths rather than on empty
    slots. The shares are a uniform's stratified quantiles; the prompt is
    then rounded down to a multiple of ``warm_start.grid`` (never below the
    plain prompt), which bounds how many prompt lengths exist. The first
    requests, with their shares, and the later ones are two fixed sets,
    dealt to the clients in a seeded order."""
    rng = np.random.default_rng(seed)
    c, k = mix["clients"], mix["per_client"]
    grid = mix["warm_start"]["grid"]
    isl, osl = _pairs(mix, c, max_len)
    share = np.random.default_rng(1).permutation(stratified(c, lambda p: p))
    first = []
    for prompt_len, new, u in zip(isl.tolist(), osl.tolist(), share):
        warm = (prompt_len + int(u * new)) // grid * grid
        if warm > prompt_len:
            new, prompt_len = new - (warm - prompt_len), warm
        first.append((prompt_len, max(new, 1)))
    later_isl, later_osl = _pairs(mix, c * (k - 1), max_len)
    later = list(zip(later_isl.tolist(), later_osl.tolist()))
    first = [first[j] for j in rng.permutation(c)]
    later = [later[j] for j in rng.permutation(len(later))]
    out: List[Request] = []
    for client in range(c):
        chain = [first[client]] + later[client * (k - 1):(client + 1) * (k - 1)]
        out.extend(Request(tokens(rng, p, vocab), o, None, client)
                   for p, o in chain)
    return out


def grid_lengths(spec: Dict) -> List[int]:
    """Every length ``lengths(spec, ...)`` can return without a cap."""
    g = spec.get("grid", 1)
    lo = -(-spec.get("min", 1) // g) * g
    return list(range(lo, spec["max"] + 1, g))


def open_prompt_lengths(mix: Dict, max_len: int) -> List[int]:
    return grid_lengths(mix["prompt"])


def closed_prompt_lengths(mix: Dict, max_len: int) -> List[int]:
    """The plain prompts' grid, and every warm-start length the largest
    share can reach: warm <= share * (max_len + 1) + (1 - share) * prompt."""
    g = mix["warm_start"]["grid"]
    top = (mix["clients"] - 0.5) / mix["clients"]
    bound = top * (max_len + 1) + (1 - top) * mix["prompt"]["max"]
    warm = range(g, int(bound) // g * g + 1, g)
    return sorted(set(grid_lengths(mix["prompt"])) | set(warm))


KINDS = {"open_poisson": (open_poisson, open_prompt_lengths),
         "closed_loop": (closed_loop, closed_prompt_lengths)}


def _kind(mix: Dict):
    if mix["kind"] not in KINDS:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}; "
                         f"known: {sorted(KINDS)}")
    return KINDS[mix["kind"]]


def generate(mix: Dict, seconds: float, vocab: int, max_len: int,
             seed: int) -> List[Request]:
    """The requests of one run of ``mix``, from ``seed``."""
    reqs = _kind(mix)[0](mix, seconds, vocab, max_len, seed)
    for r in reqs:
        if len(r.prompt) + r.max_new - 1 > max_len:
            raise ValueError(f"mix asks for {len(r.prompt)} + {r.max_new} "
                             f"positions; a slot holds {max_len}")
    return reqs


def prompt_lengths(mix: Dict, max_len: int) -> List[int]:
    """Every prompt length the mix can send, whatever the seed: the prefill
    shapes that set-up warms, the same for every run."""
    return _kind(mix)[1](mix, max_len)
