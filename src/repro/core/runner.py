"""Model runners behind the engine.

``SimRunner``   — advances a virtual clock with the analytical perf model
                  (frontier-scale studies; H200 constants reproduce the
                  paper's figures, v5e constants drive TPU planning).
``JaxRunner``   — real execution on the device: slot-based decode cache,
                  whole-prompt prefill written into the slot, batched
                  masked decode. The paged-accounting layer in the
                  scheduler is identical in both modes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from repro.configs.base import ModelConfig
from repro.core import perf_model as pm
from repro.core.request import Request


class SimRunner:
    """Virtual-clock runner: returns iteration latencies, emits dummy tokens."""

    def __init__(self, cfg: ModelConfig, plan: pm.ParallelismPlan,
                 hw: pm.Hardware, dtype_bytes: int = 2):
        self.cfg = cfg
        self.plan = plan
        self.hw = hw
        self.dtype_bytes = dtype_bytes

    def iteration_time(self, prefill_tokens: int,
                       decode_reqs: List[Request]) -> float:
        """Modeled seconds of one iteration: a prefill of
        ``prefill_tokens`` and one decode step of ``decode_reqs``."""
        cfg, plan, hw = self.cfg, self.plan, self.hw
        t = 0.0
        if prefill_tokens:
            t += pm.prefill_step_time(cfg, prefill_tokens, plan, hw,
                                      self.dtype_bytes)["total"]
        if decode_reqs:
            mean_ctx = float(np.mean([r.context_len for r in decode_reqs]))
            d = pm.decode_step_time(cfg, len(decode_reqs), mean_ctx, plan, hw,
                                    self.dtype_bytes)
            bubble = pm.pp_bubble_factor(cfg, plan, hw, len(decode_reqs),
                                         mean_ctx, self.dtype_bytes)
            t += d["total"] * bubble \
                + pm.pp_transport_time(cfg, len(decode_reqs), plan, hw,
                                       self.dtype_bytes)
        return t

    def prefill(self, req: Request, chunk: int) -> int:
        return 0   # dummy token id

    def decode(self, reqs: List[Request]) -> List[int]:
        return [0] * len(reqs)

    def release(self, req: Request):
        pass


class JaxRunner:
    """Real execution with a slot-based decode cache: each running request
    owns one slot of ``max_len`` positions. Prefill runs the whole prompt and
    writes its cache into the slot; decode steps every slot at once and keeps
    the inactive ones unchanged: their caches because the step merges a new
    token only into active slots (``decode_step``), their lengths and
    recurrent states by a per-slot select. The cache dtype is the weights'
    dtype unless ``ctx.kv_cache_dtype`` says otherwise; on a mesh the cache
    is laid out by ``decode_state_shardings``. The jitted programs are named
    ``jit_init_decode_state``, ``jit_prefill``, ``jit_insert`` and
    ``jit_decode`` in compiled modules and device traces."""

    def __init__(self, cfg: ModelConfig, params, ctx, max_slots: int,
                 max_len: int):
        import jax
        import jax.numpy as jnp
        from repro.models import transformer as T
        from repro.trace.annotate import span
        self.cfg, self.params, self.ctx = cfg, params, ctx
        self.max_slots, self.max_len = max_slots, max_len
        self._jnp = jnp
        self._T = T
        self._span = span
        dt = params["embed"].dtype
        self._slot_axes = T.decode_slot_axes(cfg)
        state_sh = None if ctx.mesh is None \
            else T.decode_state_shardings(cfg, ctx)
        self._pin = (lambda st: st) if state_sh is None else (
            lambda st: jax.lax.with_sharding_constraint(st, state_sh))

        # jit names a program after its function, so each is a named def
        def init_decode_state():
            return T.init_decode_state(cfg, ctx, max_slots, max_len, dt)

        def prefill(p, tok):
            return T.prefill(p, tok, cfg, ctx, max_len=max_len,
                             cache_dtype=dt)

        def insert(state, fresh, slot):
            return self._insert(state, fresh, slot)

        def decode(params, state, tokens, active):
            return self._masked_decode(params, state, tokens, active)

        self.state = jax.jit(init_decode_state, out_shardings=state_sh)()
        self._free_slots = list(range(max_slots))[::-1]
        self._slot_of: Dict[int, int] = {}
        self._prefill_fn = jax.jit(prefill)
        # the state is donated: each step updates the cache in place
        self._insert_fn = jax.jit(insert, donate_argnums=(0,))
        self._decode_fn = jax.jit(decode, donate_argnums=(1,))

    def _insert(self, state, fresh, slot):
        import jax
        return self._pin(jax.tree_util.tree_map(
            lambda dst, src, ax: jax.lax.dynamic_update_slice_in_dim(
                dst, src.astype(dst.dtype), slot, ax),
            state, fresh, self._slot_axes))

    def _masked_decode(self, params, state, tokens, active):
        import jax
        T = self._T
        logits, new_state = T.decode_step(params, state, tokens, self.cfg,
                                          self.ctx, active)

        # decode_step leaves an inactive slot's positional caches (leaves
        # with a "cache_seq" axis) as they were; the other leaves (lens, the
        # recurrent states) are per-slot and small, and are selected here
        def keep_inactive(new, old, axes):
            if "cache_seq" in axes:
                return new
            shape = [1] * new.ndim
            shape[axes.index("cache_batch")] = self.max_slots
            return self._jnp.where(active.reshape(shape), new, old)
        merged = jax.tree_util.tree_map(keep_inactive, new_state, state,
                                        T.decode_state_axes(self.cfg))
        return logits[:, 0], self._pin(merged)

    # ------------------------------------------------------------------ api
    def prefill_slot(self, slot: int, tokens: List[int]):
        """Run a whole prompt and write its cache into ``slot``. Returns the
        logits (V,) at the prompt's last position."""
        last, fresh = self._prefill_fn(
            self.params, self._jnp.asarray([tokens], self._jnp.int32))
        self.state = self._insert_fn(self.state, fresh, slot)
        return last[0]

    def decode_slots(self, tokens: np.ndarray, active: np.ndarray):
        """One decode step over every slot: ``tokens`` (max_slots,) are the
        inputs, ``active`` (max_slots,) marks the slots to advance. Returns
        logits (max_slots, V)."""
        jnp = self._jnp
        # jnp.array copies: jnp.asarray may alias host memory that the
        # caller reuses while the step is still in flight
        logits, self.state = self._decode_fn(
            self.params, self.state, jnp.array(tokens[:, None], jnp.int32),
            jnp.array(active))
        return logits

    def prefill(self, req: Request, chunk: int) -> int:
        """Whole-prompt prefill into the request's slot; returns first token."""
        with self._span("repro.runner.prefill", rid=req.rid):
            toks = req.prompt + req.output[:req.resume_extra]
            # a slot holds max_len positions, and an out-of-range cache
            # write would be dropped silently on the device
            if req.isl + req.max_new_tokens - 1 > self.max_len:
                raise ValueError(
                    f"request {req.rid}: {req.isl} prompt + "
                    f"{req.max_new_tokens} new tokens exceed the "
                    f"{self.max_len}-position slot")
            if req.rid not in self._slot_of:
                self._slot_of[req.rid] = self._free_slots.pop()
            with self._span("repro.runner.prefill.dispatch"):
                logits = self.prefill_slot(self._slot_of[req.rid], toks)
                tok = self._jnp.argmax(logits)
            with self._span("repro.runner.prefill.wait"):
                return int(tok)

    def decode(self, reqs: List[Request]) -> List[int]:
        with self._span("repro.runner.decode", n=len(reqs)):
            slots = [self._slot_of[r.rid] for r in reqs]
            tokens = np.zeros((self.max_slots,), np.int32)
            active = np.zeros((self.max_slots,), bool)
            for r, s in zip(reqs, slots):
                tokens[s] = r.output[-1] if r.output else (
                    r.prompt[-1] if r.prompt else 0)
                active[s] = True
            with self._span("repro.runner.decode.dispatch"):
                logits = self.decode_slots(tokens, active)
                nxt = self._jnp.argmax(logits, axis=-1)
            with self._span("repro.runner.decode.wait"):
                nxt = np.asarray(nxt)
            return [int(nxt[s]) for s in slots]

    def release(self, req: Request):
        slot = self._slot_of.pop(req.rid, None)
        if slot is not None:
            self._free_slots.append(slot)
