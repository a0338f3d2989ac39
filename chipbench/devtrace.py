"""The profiler's trace, reduced to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps, for
each TPU, the executions of whole XLA programs (the "XLA Modules" line) and
of single operations (the "XLA Ops" line), and, from the host, the spans the
harness opened with ``jax.profiler.TraceAnnotation``. All times are seconds
on one clock. A ``Trace`` can be written to and read from JSON, which is how
the tests hold a small recorded one.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|"
                        r"allreduce|allgather|reducescatter", re.I)

Event = Tuple[str, float, float]          # name, start s, end s


@dataclasses.dataclass
class Trace:
    """``modules[d]`` and ``ops[d]``: events of device ``d``; ``spans``:
    the harness's host spans; ``t0``, ``t1``: the traced window."""
    modules: Dict[int, List[Event]]
    ops: Dict[int, List[Event]]
    spans: List[Event]
    t0: float
    t1: float

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def busy(self, device: int) -> List[Tuple[float, float]]:
        """The union of the intervals in which an operation ran, clipped to
        the window, sorted."""
        return union([(s, e) for _, s, e in self.ops.get(device, [])],
                     self.t0, self.t1)

    def busy_s(self) -> float:
        """Seconds with an operation running, the mean over devices."""
        if not self.ops:
            return 0.0
        return float(np.mean([sum(e - s for s, e in self.busy(d))
                              for d in self.ops]))

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        raw = json.loads(text)
        tup = lambda evs: [tuple(e) for e in evs]            # noqa: E731
        return cls(modules={int(d): tup(v) for d, v in raw["modules"].items()},
                   ops={int(d): tup(v) for d, v in raw["ops"].items()},
                   spans=tup(raw["spans"]), t0=raw["t0"], t1=raw["t1"])


def union(intervals: Sequence[Tuple[float, float]], lo: float = -np.inf,
          hi: float = np.inf) -> List[Tuple[float, float]]:
    """Sorted disjoint union of ``intervals``, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi] between ``busy`` intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def load(directory: str, span_names: Sequence[str], t0_name: str) -> Trace:
    """Read the one ``.xplane.pb`` under ``directory``. The window is the
    host span named ``t0_name``, which the harness opens around it."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {directory}: {paths}")
    pd = ProfileData.from_file(paths[0])
    modules: Dict[int, List[Event]] = {}
    ops: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    names = set(span_names) | {t0_name}
    window: Optional[Tuple[float, float]] = None
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[dev] = _events(line)
                elif line.name == "XLA Ops":
                    ops[dev] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        s = ev.start_ns * 1e-9
                        e = s + ev.duration_ns * 1e-9
                        if ev.name == t0_name:
                            window = (s, e)
                        else:
                            spans.append((ev.name, s, e))
    if window is None:
        raise RuntimeError(f"no {t0_name!r} span in the trace")
    if not ops:
        raise RuntimeError("the trace holds no TPU operations")
    return Trace(modules, ops, sorted(spans, key=lambda x: x[1]), *window)


def _events(line) -> List[Event]:
    out = []
    for ev in line.events:
        s = ev.start_ns * 1e-9
        out.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return out


def op_kind(name: str) -> str:
    """An operation's kind: its HLO name (a TPU trace names an operation by
    its whole HLO line, ``%fusion.12 = bf16[...] fusion(...)``) without the
    instance number, so ``fusion.12`` and ``fusion.3`` are both ``fusion``."""
    short = name.split(" = ")[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", short) or short


def self_times(evs: Sequence[Event], lo: float, hi: float
               ) -> List[Tuple[Event, float]]:
    """Each event with its own time inside [lo, hi]: its duration less that
    of the events nested in it (a loop holds its body's operations)."""
    out: List[Tuple[Event, float]] = []
    stack: List[List] = []                 # [event, own seconds]
    for ev in sorted(evs, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0][2] <= ev[1]:
            out.append(tuple(stack.pop()))
        own = max(0.0, min(ev[2], hi) - max(ev[1], lo))
        if stack:
            stack[-1][1] -= own
        stack.append([ev, own])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def segments(spans: Sequence[Event], lo: float, hi: float
             ) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut where any host span opens or closes, each piece
    labelled by the spans open in it, outermost first, joined by '>'
    ("none" where none is)."""
    edges = sorted([(s, 1, i) for i, (_, s, _) in enumerate(spans)]
                   + [(e, 0, i) for i, (_, _, e) in enumerate(spans)])
    out: List[Tuple[float, float, str]] = []
    active: Dict[int, Event] = {}
    t = lo
    for when, opens, i in edges + [(hi, 0, -1)]:
        when = min(max(when, lo), hi)
        if when > t:
            open_ = sorted(active.values(), key=lambda x: (x[1], -x[2]))
            out.append((t, when, ">".join(n for n, _, _ in open_) or "none"))
            t = when
        if i >= 0:
            if opens:
                active[i] = spans[i]
            else:
                active.pop(i, None)
    return out


def breakdown(tr: Trace, top: int = 10) -> Dict[str, List]:
    """The device operations that took most time (their own time, less that
    of the operations nested in them, summed over devices and divided by
    their number, grouped by program and kind), and the idle
    time of device 0 split by the host spans open during it, each list the
    ``top`` largest."""
    per: Dict[str, float] = {}
    n = max(len(tr.ops), 1)
    for d, evs in tr.ops.items():
        mods = sorted(tr.modules.get(d, []), key=lambda x: x[1])
        starts = [m[1] for m in mods]
        inside = [ev for ev in evs if ev[2] > tr.t0 and ev[1] < tr.t1]
        for (name, s, e), own in self_times(inside, tr.t0, tr.t1):
            i = int(np.searchsorted(starts, s, side="right")) - 1
            mod = mods[i][0] if i >= 0 and mods[i][2] >= e else "?"
            key = f"{module_name(mod)}/{op_kind(name)}"
            per[key] = per.get(key, 0.0) + own / n
    dev0 = min(tr.ops)
    idle: Dict[str, float] = {}
    segs = segments(tr.spans, tr.t0, tr.t1)
    j = 0
    for s, e in gaps(tr.busy(dev0), tr.t0, tr.t1):
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            a, b, label = segs[k]
            idle[label] = idle.get(label, 0.0) + min(b, e) - max(a, s)
            k += 1
    srt = lambda d: sorted(([k, v] for k, v in d.items() if v > 0),  # noqa
                           key=lambda kv: -kv[1])[:top]
    return {"device_ops": srt(per), "idle_gaps": srt(idle)}


def module_name(name: str) -> str:
    """A program's name without its run id: ``jit_f(12)`` is ``jit_f``."""
    return re.sub(r"\(\d+\)$", "", name)


def collective_s(tr: Trace, device: int) -> float:
    """Seconds of collective operations on ``device`` inside the window."""
    return sum(min(e, tr.t1) - max(s, tr.t0)
               for name, s, e in tr.ops.get(device, [])
               if COLLECTIVE.search(name) and e > tr.t0 and s < tr.t1)
