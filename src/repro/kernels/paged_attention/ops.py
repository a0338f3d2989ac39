"""jit'd public wrapper for the paged-attention decode kernel. ``interpret``
is the caller's choice: True runs the kernel in the Pallas interpreter on any
backend (the CPU tests), False compiles it for the TPU."""
from __future__ import annotations

from repro.kernels.paged_attention.kernel import paged_attention_kernel


def paged_attention(q, k_pages, v_pages, block_tables, lens, *,
                    interpret: bool, scale=None):
    """q (B,H,D) new-token queries (H = KV*G, kv-major); k/v_pages
    (P, page, KV, D); block_tables (B, max_blocks); lens (B,)."""
    B, H, D = q.shape
    KV = k_pages.shape[2]
    G = H // KV
    qk = q.reshape(B, KV, G, D)
    # the kernel reads a head-major pool; this transpose copies the whole
    # pool per call, which a pool stored head-major would not need
    out = paged_attention_kernel(qk, k_pages.transpose(2, 0, 1, 3),
                                 v_pages.transpose(2, 0, 1, 3), block_tables,
                                 lens, scale=scale, interpret=interpret)
    return out.reshape(B, H, D)
