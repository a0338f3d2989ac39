"""Host spans of the real serving path, on the profiler's clock.

The real-execution engine and ``JaxRunner`` open these spans around each
step, the scheduler's plan, and the runner's dispatches and token
readbacks. They are ``jax.profiler`` annotations: with no profiler attached
one costs about a microsecond, and under ``jax.profiler.trace`` it lands in
the same trace as the device's programs, so a gap in the device timeline
can be laid against what the host was doing in it. Keyword arguments
become the event's stats (``rid``, ``n``), and the name stays as given.

Spans, outermost first (a ``.dispatch`` span covers the program calls and
the ``argmax`` enqueue, a ``.wait`` span only the blocking copy of the
tokens to the host):

    repro.engine.step                   one engine step (a step marker)
      repro.scheduler.plan_step         the scheduler's plan
      repro.runner.prefill              one prompt, ``rid``
        repro.runner.prefill.dispatch
        repro.runner.prefill.wait
      repro.runner.decode               one decode batch, ``n`` requests
        repro.runner.decode.dispatch
        repro.runner.decode.wait

This module imports JAX; the virtual-clock simulator never imports it.
"""
from __future__ import annotations

from jax.profiler import StepTraceAnnotation, TraceAnnotation

SPAN_NAMES = ("repro.engine.step", "repro.scheduler.plan_step",
              "repro.runner.prefill", "repro.runner.prefill.dispatch",
              "repro.runner.prefill.wait",
              "repro.runner.decode", "repro.runner.decode.dispatch",
              "repro.runner.decode.wait")


def span(name: str, **meta) -> TraceAnnotation:
    """A host span ``name`` whose ``meta`` become the event's stats."""
    return TraceAnnotation(name, **meta)


def step(step_num: int) -> StepTraceAnnotation:
    """The span of engine step ``step_num``, marked as a step so that a
    profile viewer groups the device's work by it."""
    return StepTraceAnnotation("repro.engine.step", step_num=step_num)
