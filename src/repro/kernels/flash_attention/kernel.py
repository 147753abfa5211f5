"""Blockwise (flash) attention Pallas TPU kernel.

Grid: (batch*q_heads, num_q_blocks, num_kv_blocks) — the KV axis is the
innermost (sequential on TPU) dimension, so the online-softmax running state
(m, l, acc) lives in VMEM scratch and is carried across KV iterations.

BlockSpec tiling keeps the working set in VMEM:
    q tile   (1, BQ, D)        ~ BQ*D*4        bytes
    k/v tile (1, BK, D)        ~ BK*D*4        bytes
    acc      (BQ, D) f32 scratch
with BQ=BK=128 and D<=256 this is ≲0.5 MB — far under the ~16 MB v5e VMEM,
leaving headroom for double buffering.  MXU dims (BQ, D, BK) are multiples
of 128 when D is.

Supports causal masking and sliding-window attention via position offsets.
GQA is handled by the wrapper (ops.py) mapping q-heads onto kv-heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, causal: bool,
                  window: int, q_offset: int):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                    # (BQ, D)
    k = k_ref[0].astype(jnp.float32)                    # (BK, D)
    v = v_ref[0].astype(jnp.float32)                    # (BK, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = q_offset + qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(kb == nkb - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_bh(q, k, v, *, scale: float, causal: bool = True,
                       window: int = 0, q_offset: int = 0,
                       block_q: int = 128, block_k: int = 128,
                       interpret=None):
    """q: (BH, Sq, D); k, v: (BH, Sk, D) — batch*heads pre-flattened.

    Sq/Sk must be divisible by block sizes (the wrapper pads).
    """
    from repro.kernels import resolve_interpret
    interpret = resolve_interpret(interpret)
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    grid = (BH, Sq // block_q, Sk // block_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, q_offset=q_offset)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
