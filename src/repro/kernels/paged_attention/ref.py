"""Dense-attention oracle for paged decode.

Gathers each sequence's pages into a contiguous (B, L, KV, d) view and runs
the exact ``attend_dense`` math from ``repro.models.attention`` (same f32
score cast, same ``NEG_INF`` additive mask, same softmax).  This is both the
kernel's correctness oracle and the serving engine's ``--attention dense``
execution path — the paged machinery (allocator, block tables, page writes)
is identical in both modes; only this attention call differs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables, lengths, *,
                               scale: float, window: int = 0, v_width: int = 0,
                               interpret=None):
    """Pure-jnp reference with the same signature as the kernel wrapper."""
    del interpret
    B, H, d = q.shape
    num_pages, KV, page_size, _ = k_pages.shape
    rep = H // KV
    max_pages = block_tables.shape[1]
    L = max_pages * page_size

    def gather(pool):                       # (P, KV, page, w) -> (B, L, KV, w)
        g = pool[block_tables]                           # (B, maxp, KV, page, w)
        return g.transpose(0, 1, 3, 2, 4).reshape(B, L, KV, pool.shape[-1])

    k = gather(k_pages)
    v = k[..., :v_width] if v_width else gather(v_pages)

    k_pos = jnp.arange(L, dtype=jnp.int32)
    valid = k_pos[None, :] < lengths[:, None]            # (B, L)
    if window > 0:
        valid &= k_pos[None, :] > (lengths[:, None] - 1 - window)
    bias = jnp.where(valid, 0.0, NEG_INF)                # (B, L)

    qg = q.reshape(B, KV, rep, d)
    s = jnp.einsum("bgrd,blgd->bgrl", qg, k).astype(jnp.float32) * scale
    s = s + bias[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bgrl,blgd->bgrd", p, v)
    return out.reshape(B, H, v.shape[-1])
