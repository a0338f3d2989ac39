"""Device time of the serving steps, matched to what the harness recorded
of each step.

A step's device work is found by the harness's own host spans, not by the
program's names for its jitted functions: every XLA program that starts on
a device while ``runner.decode`` is open belongs to a decode step (the
decode program and the argmax of its logits), and every one that starts
while ``runner.prefill`` is open belongs to a prefill (the prefill
program, the insert of its cache into the slot, the argmax). The runner
reads its tokens back before these calls return, so their device work
ends inside them.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from chipbench import shapes


def span_device_s(trace, span: str) -> Optional[Dict[str, float]]:
    """The number of ``span`` spans inside the traced window, and the
    device seconds of the programs that started in them (the mean over
    devices)."""
    opened = [(s, e) for n, s, e in trace.spans
              if n == span and trace.t0 <= s and e <= trace.t1]
    if not opened:
        return None
    starts = np.array([s for s, _ in opened])
    ends = np.array([e for _, e in opened])
    per_dev = []
    for evs in trace.modules.values():
        total = 0.0
        for _, s, e in evs:
            i = int(np.searchsorted(starts, s, side="right")) - 1
            if i >= 0 and s < ends[i]:
                total += e - s
        per_dev.append(total)
    if not per_dev or not any(per_dev):
        return None
    return {"calls": float(len(opened)), "seconds": float(np.mean(per_dev))}


def decode_work(run) -> List[Dict[str, float]]:
    return [run.dims.decode(s.contexts) for s in run.window_steps()
            if s.contexts]


def prefill_work(run) -> List[Dict[str, float]]:
    return [run.dims.prefill(n) for s in run.window_steps()
            for n in s.prefills]


def least_s(run, work: List[Dict[str, float]]) -> float:
    return sum(shapes.least_time(w, run.peaks, run.chips)["seconds"]
               for w in work)


def bound(run, work: List[Dict[str, float]]) -> str:
    """Which peak bounds most of the least time: compute or memory."""
    split = {"compute": 0.0, "memory": 0.0}
    for w in work:
        t = shapes.least_time(w, run.peaks, run.chips)
        split[t["bound"]] += t["seconds"]
    return max(split, key=split.get)
