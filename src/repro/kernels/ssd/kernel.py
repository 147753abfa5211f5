"""Mamba-2 SSD (state-space duality) chunked-scan Pallas TPU kernel.

Grid: (batch*heads, num_chunks) — chunks are the innermost sequential axis,
so the (P, N) inter-chunk state lives in VMEM scratch and is carried across
chunk iterations (the TPU grid executes minor-most last, in order).

Per chunk the kernel computes the SSD dual form:
  intra-chunk:  y  = ((C·Bᵀ) ⊙ decay(t,s)) · (x·dt)      (chunk-local "attention")
  inter-chunk:  y += (C · h_prev) ⊙ decay(t,start)
  state update: h  = decay(chunk) * h_prev + Σ_s decay(end,s) (x·dt)_s ⊗ B_s

VMEM working set per step (chunk=CK, state N, head_dim P, f32):
  x (CK,P) + B,C (CK,N) + state (P,N) + decay (CK,CK)
  = 256*64 + 2*256*128 + 64*128 + 256*256  floats ≈ 0.7 MB — fits VMEM
with hardware-aligned MXU dims (CK, N, P multiples of 64/128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(dA_ref, x_ref, b_ref, c_ref, y_ref, hT_ref, h_scr, *,
                chunk: int):
    cb = pl.program_id(1)
    ncb = pl.num_programs(1)

    @pl.when(cb == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    dA = dA_ref[0].astype(jnp.float32)          # (CK,)   per-step log decay
    x = x_ref[0].astype(jnp.float32)            # (CK, P) dt-scaled input
    Bm = b_ref[0].astype(jnp.float32)           # (CK, N)
    Cm = c_ref[0].astype(jnp.float32)           # (CK, N)

    seg = jnp.cumsum(dA)                        # (CK,)
    # intra-chunk decay matrix decay(t,s) = exp(seg_t - seg_s) for s <= t
    # (mask before exp: masked entries would overflow)
    rel = seg[:, None] - seg[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1) <= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    decay = jnp.exp(jnp.where(tri, rel, -1e9))

    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(scores * decay, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: contribution of carried state
    h_prev = h_scr[...]                         # (P, N)
    decay_in = jnp.exp(seg)[:, None]            # (CK, 1)
    y += decay_in * jax.lax.dot_general(
        Cm, h_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: h = exp(seg_end) * h_prev + sum_s exp(seg_end - seg_s) x_s B_s^T
    decay_end = jnp.exp(seg[-1] - seg)[:, None] # (CK, 1)
    xw = x * decay_end
    h_scr[...] = jnp.exp(seg[-1]) * h_prev + jax.lax.dot_general(
        xw, Bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(cb == ncb - 1)
    def _emit_state():
        hT_ref[0] = h_scr[...].astype(hT_ref.dtype)


def ssd_bh(dA, x, Bm, Cm, *, chunk: int = 256, interpret=None):
    """Flattened (batch*heads)-major SSD scan.

    dA: (BH, S) log-decay per step; x: (BH, S, P) dt-scaled inputs;
    Bm, Cm: (BH, S, N).  S must divide by ``chunk``.
    Returns y (BH, S, P) and final state (BH, P, N).
    """
    from repro.kernels import resolve_interpret
    interpret = resolve_interpret(interpret)
    BH, S, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0
    grid = (BH, S // chunk)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, hT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk), lambda b, c: (b, c)),
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, P, N), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), x.dtype),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(dA, x, Bm, Cm)
    return y, hT
