"""The plain reference: a float32 forward pass in ``jax.numpy``, layer by
layer, at the highest matmul precision, with no cache, no batching and no
kernels. It imports nothing from the program.

Architecture (decoder-only, pre-norm): token embedding; per layer RMSNorm,
grouped-query attention (query head ``i`` reads key/value head
``i // (heads / kv_heads)``), optional RMSNorm of each query and key head
(Qwen3's qk-norm), rotary embedding on the two halves of each head
(``rotate_half``), causal attention inside a sliding window if the
configuration has one, output projection and residual; RMSNorm, SwiGLU
(``silu(x W_gate) * (x W_up) W_down``) and residual; final RMSNorm and the
vocabulary projection.

Weights are drawn from the run's seed by the program's documented recipe,
written out here: ``jax.random.split(PRNGKey(seed), n)`` over the
parameter leaves in sorted-key order, each matrix a standard normal over
``sqrt(fan_in)`` rounded to the served dtype, each norm weight one. Stacked
layer leaves carry the layer on their first axis. The values do not depend
on where they are placed (JAX's threefry is partitionable), so the reference
places them its own way: layers spread over the cell's chips, the
vocabulary split over them.

``quant="fp8"`` is the control: the same pass with every matrix rounded to
float8 (e4m3, one scale per output channel), the step below the served
bfloat16 that would tempt a later change. Its logits must fail the check.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 512          # attention runs over query blocks of this length;
                     # sequences are padded to BLOCK times a power of two
BATCH_POSITIONS = 8192    # positions in one call: sequences of one padded
                          # length go through a layer together
FP8_MAX = 448.0      # largest finite float8_e4m3fn


def leaves(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], Optional[int],
                                    Tuple[int, ...]]]:
    """(name, shape, fan_in or None for a norm weight of ones, contraction
    axes) of every parameter, in the program's flattening order."""
    L, d, f = cfg["num_hidden_layers"], cfg["hidden_size"], \
        cfg["intermediate_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    V = cfg["vocab_size"]
    layer = {
        "attn_norm": ((L, d), None, ()),
        "mlp_norm": ((L, d), None, ()),
        "w_down": ((L, f, d), f, (1,)),
        "w_gate": ((L, d, f), d, (1,)),
        "w_up": ((L, d, f), d, (1,)),
        "wk": ((L, d, kv, hd), d, (1,)),
        "wo": ((L, h, hd, d), h * hd, (1, 2)),
        "wq": ((L, d, h, hd), d, (1,)),
        "wv": ((L, d, kv, hd), d, (1,)),
    }
    if cfg.get("qk_norm"):
        layer["kn"] = ((L, hd), None, ())
        layer["qn"] = ((L, hd), None, ())
    out = [(f"dense_stack/{k}", *layer[k]) for k in sorted(layer)]
    out.append(("embed", (V, d), d, (1,)))
    out.append(("final_norm", (d,), None, ()))
    if not cfg["tie_word_embeddings"]:
        out.append(("lm_head", (d, V), d, (0,)))
    return out


def _placement(name: str, shape, mesh: Mesh) -> NamedSharding:
    n = mesh.devices.size
    if name.startswith("dense_stack/") and shape[0] % n == 0:
        return NamedSharding(mesh, P("x"))
    if name == "embed" and shape[0] % n == 0:
        return NamedSharding(mesh, P("x"))
    if name == "lm_head" and shape[1] % n == 0:
        return NamedSharding(mesh, P(None, "x"))
    return NamedSharding(mesh, P())


def draw(cfg: Dict, seed: int, devices: Sequence, dtype=jnp.bfloat16
         ) -> Dict[str, jax.Array]:
    """Every weight, by the recipe in the module docstring, in one jitted
    call on ``devices``."""
    spec = leaves(cfg)
    mesh = Mesh(np.asarray(devices), ("x",))

    def build(key):
        keys = jax.random.split(key, len(spec))
        out = {}
        for (name, shape, fan, _), k in zip(spec, keys):
            if fan is None:
                out[name] = jnp.ones(shape, dtype)
            else:
                std = 1.0 / jnp.sqrt(jnp.asarray(fan, jnp.float32))
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             * std).astype(dtype)
        return out

    shardings = {name: _placement(name, shape, mesh)
                 for name, shape, _, _ in spec}
    return jax.jit(build, out_shardings=shardings)(jax.random.PRNGKey(seed))


def fp8(w: jax.Array, axes: Tuple[int, ...]) -> jax.Array:
    """``w`` rounded to float8 e4m3 with one scale per output channel (the
    max over the contraction ``axes``), back in float32."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, theta):
    """x (S, heads, hd); position i rotates pair (j, j + hd/2) by
    i * theta^(-j / (hd/2))."""
    S, _, hd = x.shape
    half = hd // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window: int):
    """q (S, h, hd), k and v (S, kv, hd): causal softmax attention inside
    ``window`` (0: none), over query blocks of BLOCK positions."""
    S, h, hd = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * BLOCK, BLOCK, 0)
        qpos = i * BLOCK + jnp.arange(BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * hd ** -0.5
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(S // BLOCK))
    return out.reshape(S, h, hd)


def _layer(x, w, cfg_t, quant):
    eps, theta, window, qk_norm, axes = cfg_t
    mat = {n: (fp8(a, axes[n]) if quant else a.astype(jnp.float32))
           for n, a in w.items()}
    h = rmsnorm(x, mat["attn_norm"], eps)
    q = jnp.einsum("sd,dhe->she", h, mat["wq"], precision=HIGHEST)
    k = jnp.einsum("sd,dhe->she", h, mat["wk"], precision=HIGHEST)
    v = jnp.einsum("sd,dhe->she", h, mat["wv"], precision=HIGHEST)
    if qk_norm:
        q = rmsnorm(q, mat["qn"], eps)
        k = rmsnorm(k, mat["kn"], eps)
    o = attention(rope(q, theta), rope(k, theta), v, window)
    x = x + jnp.einsum("she,hed->sd", o, mat["wo"], precision=HIGHEST)
    h = rmsnorm(x, mat["mlp_norm"], eps)
    gate = jnp.einsum("sd,df->sf", h, mat["w_gate"], precision=HIGHEST)
    up = jnp.einsum("sd,df->sf", h, mat["w_up"], precision=HIGHEST)
    return x + jnp.einsum("sf,fd->sd", jax.nn.silu(gate) * up,
                          mat["w_down"], precision=HIGHEST)


class Reference:
    """The reference model of one configuration and seed on ``devices``."""

    def __init__(self, cfg: Dict, seed: int, devices: Sequence):
        self.cfg = cfg
        self.mesh = Mesh(np.asarray(devices), ("x",))
        self.w = draw(cfg, seed, devices)
        spec = leaves(cfg)
        # contraction axes of one layer's slice (the stacked axis dropped)
        self._axes = {n.split("/")[1]: tuple(a - 1 for a in ax)
                      for n, _, _, ax in spec if n.startswith("dense_stack/")}
        self._top_axes = {n: ax for n, _, _, ax in spec if "/" not in n}
        self._cfg_t = (cfg["rms_norm_eps"], float(cfg["rope_theta"]),
                       cfg.get("sliding_window") or 0,
                       bool(cfg.get("qk_norm")),
                       tuple(sorted(self._axes.items())))
        self._layer = jax.jit(self._layer_fn, static_argnums=(2,))
        self._embed = jax.jit(self._embed_fn, static_argnums=(2,))
        self._head = jax.jit(self._head_fn, static_argnums=(3,))

    def _layer_fn(self, x, w, quant):
        """x (batch, positions, d)."""
        eps, theta, window, qk_norm, axes = self._cfg_t
        cfg_t = (eps, theta, window, qk_norm, dict(axes))
        return jax.vmap(lambda one: _layer(one, w, cfg_t, quant))(x)

    def _embed_fn(self, table, tokens, quant):
        t = fp8(table, self._top_axes["embed"]) if quant else table
        return jnp.take(t, tokens, axis=0).astype(jnp.float32)

    def _head_fn(self, x, norm, w, quant):
        x = rmsnorm(x, norm, self.cfg["rms_norm_eps"])
        if self.cfg["tie_word_embeddings"]:
            t = fp8(w, self._top_axes["embed"]) if quant else w
            return jnp.einsum("sd,vd->sv", x, t.astype(jnp.float32),
                              precision=HIGHEST)
        t = fp8(w, self._top_axes["lm_head"]) if quant else w
        return jnp.einsum("sd,dv->sv", x, t.astype(jnp.float32),
                          precision=HIGHEST)

    def _layer_slices(self, l: int):
        """Layer ``l``'s weights, on the device that holds them."""
        out, dev = {}, None
        for name, arr in self.w.items():
            if not name.startswith("dense_stack/"):
                continue
            for sh in arr.addressable_shards:
                start = sh.index[0].start or 0
                if start <= l < start + sh.data.shape[0]:
                    out[name.split("/")[1]] = sh.data[l - start]
                    dev = sh.device
                    break
        return out, dev

    def logits(self, seqs: Sequence[Sequence[int]],
               rows: Sequence[Sequence[int]], quant: Optional[str] = None
               ) -> List[np.ndarray]:
        """For each token sequence, float32 logits (len(rows[i]), V) at the
        positions ``rows[i]``: each predicts the token after it."""
        q = quant == "fp8"
        if quant not in (None, "fp8"):
            raise ValueError(f"unknown quantisation {quant!r}")
        # few padded lengths, so few programs; one call per batch and layer
        batches: Dict[int, List[List[int]]] = {}
        for i, s in enumerate(seqs):
            n = BLOCK
            while n < len(s):
                n *= 2
            per = max(1, BATCH_POSITIONS // n)
            group = batches.setdefault(n, [[]])
            if len(group[-1]) == per:
                group.append([])
            group[-1].append(i)
        calls = []
        for n, groups in batches.items():
            for idx in groups:
                toks = jnp.asarray([list(seqs[i]) + [0] * (n - len(seqs[i]))
                                    for i in idx], jnp.int32)
                calls.append((idx, self._embed(self.w["embed"], toks, q)))
        # batch by batch through every layer: dispatch does not wait, so
        # where the layers lie on several chips the batches pipeline
        done = []
        for idx, x in calls:
            for l in range(self.cfg["num_hidden_layers"]):
                w, dev = self._layer_slices(l)
                x = self._layer(jax.device_put(x, dev), w, q)
            done.append((idx, x))
        calls = done
        head = self.w["embed"] if self.cfg["tie_word_embeddings"] \
            else self.w["lm_head"]
        out: List[Optional[np.ndarray]] = [None] * len(seqs)
        for idx, x in calls:
            for b, i in enumerate(idx):
                sel = jnp.take(x[b], jnp.asarray(list(rows[i]), jnp.int32),
                               axis=0)
                sel = jax.device_put(sel, NamedSharding(self.mesh, P()))
                out[i] = np.asarray(self._head(sel, self.w["final_norm"],
                                               head, q), np.float32)
        return out

    def free(self):
        for a in self.w.values():
            a.delete()
        self.w = {}
