"""RG-LRU diagonal linear recurrence Pallas TPU kernel.

    h_t = a_t ⊙ h_{t-1} + b_t

Grid: (batch, num_chunks) with chunks innermost-sequential; the (W,) hidden
state is carried in VMEM scratch.  Within a chunk the recurrence is unrolled
with ``fori_loop`` over time steps — each step is a (W,)-wide VPU op, with W
(the RG-LRU width, e.g. 4096) lane-aligned to multiples of 128.

VMEM working set: a,b chunks (CK, W) f32 ×2 + state (W,)
  = 2*64*4096*4 + 16 KB ≈ 2.1 MB for CK=64, W=4096 — fits VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h_ref, h_final_ref, state_scr, *, chunk: int):
    cb = pl.program_id(1)
    ncb = pl.num_programs(1)

    @pl.when(cb == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    a = a_ref[0].astype(jnp.float32)            # (CK, W)
    b = b_ref[0].astype(jnp.float32)            # (CK, W)

    def step(t, carry):
        h = carry
        h = a[t] * h + b[t]
        h_ref[0, t] = h.astype(h_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, state_scr[...])
    state_scr[...] = h

    @pl.when(cb == ncb - 1)
    def _emit():
        h_final_ref[0] = h.astype(h_final_ref.dtype)


def rglru_scan_b(a, b, *, chunk: int = 64, interpret=None):
    """a, b: (B, S, W) with a ∈ (0,1).  Returns h (B,S,W), h_final (B,W)."""
    from repro.kernels import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, S, W = a.shape
    assert S % chunk == 0
    grid = (B, S // chunk)
    kernel = functools.partial(_rglru_kernel, chunk=chunk)
    h, hT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, W), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, W), lambda i, c: (i, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, W), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, W), lambda i, c: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), a.dtype),
            jax.ShapeDtypeStruct((B, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((W,), jnp.float32)],
        interpret=interpret,
    )(a, b)
    return h, hT
