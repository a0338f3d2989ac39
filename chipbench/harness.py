"""One run of one cell: build the serving engine, warm it, drive it for the
window from the client's side, and keep the record the metrics read.

The system under test is ``repro.launch.serve.build_real_engine``
(``InferenceEngine`` over ``JaxRunner``). The harness submits each request
when it is due and calls ``engine.step()`` itself. A token's time is when
``step()`` returned it: the runner has copied the token ids to the host by
then (its ``argmax`` readback blocks), so that is when a client has it. The
engine's own clock and metrics are never read.

The harness wraps a few bound methods of the engine, scheduler and runner
(never their code) to count what each step did and, when tracing, to open
``jax.profiler.TraceAnnotation`` spans around them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import traffic as traffic_lib

SPANS = ("engine.step", "scheduler.plan_step", "runner.prefill",
         "runner.decode", "runner.prefill_slot", "runner.decode_slots",
         "readback")
WINDOW_SPAN = "chipbench.window"


@dataclasses.dataclass
class Rec:
    """A request as the client sees it. Times are ``time.perf_counter()``."""
    req: traffic_lib.Request
    due_at: Optional[float] = None
    sent_at: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    handle: object = None            # the engine's Request
    error: Optional[str] = None

    @property
    def tokens(self) -> List[int]:
        return list(self.handle.output) if self.handle is not None else []

    @property
    def finished(self) -> bool:
        """The engine has ended the request (whatever it served)."""
        return self.handle is not None and \
            self.handle.t_finished is not None


@dataclasses.dataclass
class Step:
    """What one engine step did: decode contexts (each active slot's keys
    after the step), the prompt lengths it prefilled, and when."""
    t0: float
    t1: float = 0.0
    contexts: List[int] = dataclasses.field(default_factory=list)
    prefills: List[int] = dataclasses.field(default_factory=list)


class Annotator:
    """Opens the harness's host spans when tracing, and does nothing else."""

    def __init__(self, on: bool):
        self.on = on
        self._open: Dict[str, object] = {}

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def begin(self, name: str):
        if self.on:
            import jax
            a = jax.profiler.TraceAnnotation(name)
            a.__enter__()
            self._open[name] = a

    def end(self, name: str):
        a = self._open.pop(name, None)
        if a is not None:
            a.__exit__(None, None, None)


def _wrap(obj, name: str, span: str, ann: Annotator,
          after: Optional[Callable] = None, close: Optional[str] = None):
    """Replace the bound method ``obj.name`` with one that runs it inside
    span ``span``, then, still inside, calls ``after`` with the result and
    the arguments and closes the open span ``close``."""
    inner = getattr(obj, name)

    def wrapped(*args, **kw):
        with ann.span(span):
            out = inner(*args, **kw)
            if after is not None:
                after(out, *args, **kw)
            if close is not None:
                ann.end(close)
        return out
    setattr(obj, name, wrapped)


class Client:
    """Sends one run's traffic to one engine, steps it, and records what the
    client sees."""

    def __init__(self, engine, closed_loop: bool,
                 reqs: List[traffic_lib.Request], ann: Annotator):
        self.eng = engine
        self.ann = ann
        self.recs = [Rec(r) for r in reqs]
        self.steps: List[Step] = []
        self._step: Optional[Step] = None
        self._inflight: List[Rec] = []
        self._closed = closed_loop
        self._next_open = 0                   # open loop: the next to send
        self._pending: List[Rec] = []         # closed loop: due, not sent
        self._chains: Dict[int, List[Rec]] = {}
        if closed_loop:                       # each client's requests, the
            for rec in reversed(self.recs):   # next one last, for pop()
                self._chains.setdefault(rec.req.client, []).append(rec)
        self._instrument()

    # ------------------------------------------------------------- wrapping
    def _instrument(self):
        eng, ann = self.eng, self.ann
        sched, runner = eng.sched, eng.runner

        def on_plan(plan):
            if self._step is not None:
                self._step.contexts = [r.context_len for r in plan.decode]

        def on_prefill(_, req, chunk):
            if self._step is not None:
                self._step.prefills.append(len(req.prompt) + req.resume_extra)

        def readback(*_, **__):
            ann.begin("readback")

        _wrap(sched, "plan_step", "scheduler.plan_step", ann, after=on_plan)
        # the token readback is what runner.prefill and runner.decode do
        # after their dispatch returns: a span from there to their return
        _wrap(runner, "prefill_slot", "runner.prefill_slot", ann,
              after=readback)
        _wrap(runner, "decode_slots", "runner.decode_slots", ann,
              after=readback)
        _wrap(runner, "prefill", "runner.prefill", ann, after=on_prefill,
              close="readback")
        _wrap(runner, "decode", "runner.decode", ann, close="readback")

    def step(self) -> bool:
        self._step = Step(time.perf_counter())
        with self.ann.span("engine.step"):
            busy = self.eng.step()
        self._step.t1 = time.perf_counter()
        if busy:
            self.steps.append(self._step)
        self._step = None
        self._harvest(time.perf_counter())
        return busy

    # -------------------------------------------------------------- traffic
    def _send(self, rec: Rec, now: float):
        rec.sent_at = now
        if rec.due_at is None:
            rec.due_at = now
        try:
            rec.handle = self.eng.submit(list(rec.req.prompt), rec.req.max_new)
        except ValueError as e:       # refused by the engine: a failure
            rec.error = str(e)
            return
        self._inflight.append(rec)

    def _harvest(self, t: float):
        keep = []
        for rec in self._inflight:
            n = len(rec.handle.output)
            while len(rec.token_times) < n:
                rec.token_times.append(t)
            if rec.finished:
                if self._closed:
                    self._next_in_chain(rec.req.client, t)
            else:
                keep.append(rec)
        self._inflight = keep

    def _next_in_chain(self, client: int, due: float):
        chain = self._chains.get(client)
        if chain:
            rec = chain.pop()
            rec.due_at = due
            self._pending.append(rec)

    def start(self, t0: float):
        """Traffic starts at ``t0``: open-loop due times count from it, and
        every closed-loop client sends its first request."""
        if self._closed:
            for client in sorted(self._chains):
                self._next_in_chain(client, t0)
        else:
            for rec in self.recs:
                rec.due_at = t0 + rec.req.due

    def pump(self, until: float, stop: Optional[Callable[[], bool]] = None):
        """Send what is due and step the engine until ``until`` (host
        clock) or until ``stop()`` says so."""
        while True:
            now = time.perf_counter()
            if now >= until or (stop is not None and stop()):
                return
            self._send_due(now)
            if self.eng.has_work:
                self.step()
            else:
                nxt = self._next_due()
                time.sleep(max(0.0, min(nxt, until) - now) if nxt else 0.0005)

    def _send_due(self, now: float):
        if self._closed:
            pend, self._pending = self._pending, []
            for rec in pend:
                self._send(rec, now)
            return
        while self._next_open < len(self.recs) and \
                self.recs[self._next_open].due_at <= now:
            self._send(self.recs[self._next_open], now)
            self._next_open += 1

    def _next_due(self) -> Optional[float]:
        if self._closed:
            return None
        if self._next_open < len(self.recs):
            return self.recs[self._next_open].due_at
        return None

    def settled(self) -> bool:
        """Closed loop warm start: every request sent has had its prefill
        or is held back by admission (the last step prefilled nothing)."""
        if self._pending:
            return False
        last = self.steps[-1] if self.steps else None
        return last is not None and not last.prefills and all(
            r.prefill_done for r in self.eng.sched.running)


def warm(eng, prompt_lengths: List[int], vocab: int):
    """Serve each prompt length once on ``eng``, two tokens each (a prefill
    and a decode step), so that every program the window will run is
    compiled or loaded before it opens."""
    rng = np.random.default_rng(0)
    for n in prompt_lengths:
        eng.submit(rng.integers(0, vocab, n).tolist(), 2)
        while eng.step():
            pass
