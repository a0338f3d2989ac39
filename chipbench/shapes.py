"""Operations and bytes that a serving step needs, from the model's shapes.

These are the algorithm's counts, not what an implementation happens to do:
attention covers only the keys a query may see (causal, inside the window),
decode covers only the active slots, the vocabulary projection only the
positions whose logits are used, and every weight and cache byte is moved
once. So no implementation can beat the least time they imply, and a share
of the roofline built on them cannot pass 100%.

XLA's ``cost_analysis`` cannot stand in: it counts the body of a scanned
layer stack once.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Sequence


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    window: int          # 0: attention sees every earlier position
    qk_norm: bool
    dtype_bytes: int

    @classmethod
    def of(cls, cfg: Dict) -> "Dims":
        """From a configuration file (Hugging Face key names)."""
        return cls(layers=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg["head_dim"], ff=cfg["intermediate_size"],
                   vocab=cfg["vocab_size"],
                   window=cfg.get("sliding_window") or 0,
                   qk_norm=cfg.get("qk_norm", False),
                   dtype_bytes={"bfloat16": 2, "float32": 4}[cfg["dtype"]])

    @property
    def layer_matmul_params(self) -> int:
        attn = self.d * self.head_dim * (2 * self.heads + 2 * self.kv_heads)
        return attn + 3 * self.d * self.ff

    @property
    def layer_vector_params(self) -> int:
        return 2 * self.d + (2 * self.head_dim if self.qk_norm else 0)

    @property
    def weight_bytes(self) -> int:
        """Every weight a step reads: the layers, the final norm and the
        vocabulary projection (the embedding rows are counted per token)."""
        per_layer = self.layer_matmul_params + self.layer_vector_params
        return self.dtype_bytes * (self.layers * per_layer + self.d
                                   + self.d * self.vocab)

    @property
    def kv_bytes_per_position(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * self.dtype_bytes

    def seen(self, keys: int) -> int:
        return min(keys, self.window) if self.window else keys

    def _attn_flops(self, keys: int) -> int:
        """One query against ``keys`` keys, over every layer: QK^T and PV."""
        return 4 * self.layers * self.heads * self.head_dim * self.seen(keys)

    def decode(self, contexts: Sequence[int]) -> Dict[str, float]:
        """One decode step of the active slots; ``contexts`` holds each
        active slot's keys after the step (its cached positions plus the
        new one)."""
        b = len(contexts)
        flops = sum(2 * self.layers * self.layer_matmul_params
                    + self._attn_flops(c) + 2 * self.d * self.vocab
                    for c in contexts)
        kv_read = sum(self.seen(c) - 1 for c in contexts)
        nbytes = (self.weight_bytes
                  + b * self.d * self.dtype_bytes                 # embed rows
                  + (kv_read + b) * self.kv_bytes_per_position    # read, write
                  + b * self.vocab * self.dtype_bytes)            # logits
        return {"flops": float(flops), "bytes": float(nbytes)}

    def prefill(self, n: int) -> Dict[str, float]:
        """One prompt of ``n`` tokens, with the logits of its last position
        and its keys and values written once."""
        attn = sum(self._attn_flops(q) for q in range(1, n + 1)) \
            if self.window and self.window < n else \
            4 * self.layers * self.heads * self.head_dim * n * (n + 1) // 2
        flops = 2 * self.layers * self.layer_matmul_params * n + attn \
            + 2 * self.d * self.vocab
        nbytes = (self.weight_bytes + n * self.d * self.dtype_bytes
                  + n * self.kv_bytes_per_position
                  + self.vocab * self.dtype_bytes)
        return {"flops": float(flops), "bytes": float(nbytes)}


def least_time(work: Dict[str, float], peaks: Dict[str, float],
               chips: int) -> Dict[str, object]:
    """The least time ``chips`` chips could take for ``work``, and which
    peak bounds it."""
    t_flops = work["flops"] / (chips * peaks["flops_per_s"])
    t_bytes = work["bytes"] / (chips * peaks["bytes_per_s"])
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
