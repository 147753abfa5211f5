"""Paged-attention decode Pallas TPU kernel.

Single-token decode against a block-table **paged KV cache**: physical pages
of ``page_size`` tokens live in a shared head-major pool
``(num_pages, KV, page, d)`` and each sequence owns an ordered list of page
indices (its *block table*).
The kernel reuses the scalar-prefetched index-map routing proven in
``kernels/vb_scatter``: block tables and sequence lengths ride
``PrefetchScalarGridSpec`` so the K/V BlockSpec index maps dereference
``bt_ref[b, j]`` — page ``j`` of sequence ``b`` is DMA'd straight from
wherever it lives in the pool, no gather materialization.

Grid: ``(B, KV_heads, max_pages)`` — pages innermost (sequential on TPU), so
the online-softmax running state (m, l, acc) lives in VMEM scratch across
page iterations, exactly like ``kernels/flash_attention``.  Pages beyond a
sequence's length are skipped via ``@pl.when`` (their DMA still happens but
the FLOPs and state update do not; block tables point such slots at the
allocator's trash page 0, which is never handed out to a sequence).

MLA serving: pass ``v_width > 0`` and no value pool — the value is the
leading ``v_width`` lanes of the key block (the cache stores one fused
``c_kv ‖ k_rope`` pool; values are the latent prefix), so MLA decode reads
each page once.

Tiling (v5e): the pool is head-major so one grid step's K/V block is one
head's page ``(page, d)`` — the block's two minor dims are whole array
dims, which Mosaic accepts for any page size and head width.  (A
token-major ``(P, page, KV, d)`` pool would need a 1-row block in the
second-minor KV dim, which Mosaic refuses.)  The running softmax
statistics are ``(rep, 1)`` columns for the same reason.

VMEM per grid step: q tile ``(rep, d)``, one K page ``(page, d)`` (+V for
GQA), acc ``(rep, dv)`` f32 — ≲0.2 MB at page=16, d≤256: far under v5e's
~16 MB, with headroom for double buffering.  Both matmuls run at
``Precision.HIGHEST`` so an f32 pool gives f32 attention on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


def _paged_decode_kernel(bt_ref, len_ref, *refs, page_size: int, scale: float,
                         window: int, v_width: int):
    if v_width:                       # fused pool: V = K[:, :v_width] (MLA)
        q_ref, k_ref, o_ref = refs[:3]
        v_ref = None
    else:
        q_ref, k_ref, v_ref, o_ref = refs[:4]
    m_scr, l_scr, acc_scr = refs[-3:]

    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(j * page_size < length)
    def _page():
        q = q_ref[0, 0].astype(jnp.float32)              # (rep, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (page, d)
        rep = q.shape[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=HIGHEST,
                                preferred_element_type=jnp.float32) * scale
        k_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rep, page_size), 1)
        mask = k_pos < length                            # causal: q at length-1
        if window > 0:
            mask &= k_pos > length - 1 - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                              # (rep, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        v = k[:, :v_width] if v_width else v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           scale: float, window: int = 0, v_width: int = 0,
                           interpret=None):
    """One decode step of every sequence against its paged KV cache.

    q:            (B, H, d)        — this step's query (token at lengths-1)
    k_pages:      (P, KV, page, d) — shared head-major physical page pool
    v_pages:      (P, KV, page, dv) or None when ``v_width`` routes V out of
                  the key pool (MLA fused layout)
    block_tables: (B, max_pages) int32 — page j of seq b is k_pages[bt[b,j]];
                  slots beyond the sequence's pages must point at page 0
    lengths:      (B,) int32 — valid tokens per sequence (incl. this one)

    Returns (B, H, dv).
    """
    interpret = resolve_interpret(interpret)
    B, H, d = q.shape
    num_pages, KV, page_size, _ = k_pages.shape
    rep = H // KV
    max_pages = block_tables.shape[1]
    dv = v_width if v_width else v_pages.shape[-1]

    qg = q.reshape(B, KV, rep, d)
    grid = (B, KV, max_pages)

    q_spec = pl.BlockSpec((1, 1, rep, d),
                          lambda b, h, j, bt, ln: (b, h, 0, 0))
    k_spec = pl.BlockSpec((1, 1, page_size, d),
                          lambda b, h, j, bt, ln: (bt[b, j], h, 0, 0))
    o_spec = pl.BlockSpec((1, 1, rep, dv),
                          lambda b, h, j, bt, ln: (b, h, 0, 0))
    in_specs = [q_spec, k_spec]
    operands = [qg, k_pages]
    if not v_width:
        in_specs.append(pl.BlockSpec((1, 1, page_size, dv),
                                     lambda b, h, j, bt, ln: (bt[b, j], h, 0, 0)))
        operands.append(v_pages)

    kernel = functools.partial(_paged_decode_kernel, page_size=page_size,
                               scale=scale, window=window, v_width=v_width)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, dv), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), *operands)
    return out.reshape(B, H, dv)
