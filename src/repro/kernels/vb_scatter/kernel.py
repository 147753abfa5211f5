"""Virtual-batch reassembly Pallas TPU kernel (the eq. 4–5 prologue).

The TL orchestrator reassembles the virtual batch from concatenated node
payloads: ``out[perm[i]] = payload[i]`` where ``perm`` is the concatenated
``batch_positions`` (a permutation of ``0..N-1``).  XLA lowers each
``zeros_like(x).at[perm].set(x)`` to a *generic* scatter: it materializes
the zero-initialized destination buffer and then updates every row — two
full HBM writes of the reassembled X^(1) per tensor, issued once per
payload tensor (x1, δ^(L), ∂L/∂X^(1)), before the tail vjp reads X^(1)
back.  Because ``perm`` is a permutation, the zeros are dead: every
destination row is written exactly once.

This kernel copies each row exactly once instead, HBM to HBM by DMA.
``perm`` is scalar-prefetched into SMEM; the kernel body issues one DMA per
(row, tensor) from the source row to its destination row, then waits for
all of them — no zeros materialization, no VMEM staging, no scatter or
sort ops in the lowering.  Two row routings share one body:

* ``scatter``: read row ``i``, write row ``perm[i]`` (the reassembly);
* ``gather``:  read row ``idx[i]``, write row ``i`` (the reassembly's
  transpose — the custom-vjp backward gathers cotangents with the *same*
  ``perm``, no inverse permutation ever materializes).

All payload tensors ride one call, so the whole reassembly is one kernel
launch and one HBM pass over the payloads.

Layout (v5e): a DMA moves whole (sublane, lane) tiles, and a bare row of a
2-D array is a 1-sublane slice of its tiles.  So each ``(N, D)`` tensor is
viewed as ``(N, D/128, 128)`` — a row is then a leading-dim slab of full
tiles.  A row whose width is not a multiple of one native tile
(8 sublanes × 128 lanes × the dtype's packing: 1024 f32, 2048 bf16
elements) is zero-padded up to one and sliced back afterwards; the
production widths (seq × d_model, seq) need no padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

LANES = 128


def _row_tile(dtype) -> int:
    """Elements of one native (sublane, lane) tile: rows padded to a
    multiple of this are whole-tile slabs for every dtype's packing."""
    return 8 * LANES * max(1, 4 // jnp.dtype(dtype).itemsize)


def _as_row_slabs(t):
    n, width = t.shape
    tile = _row_tile(t.dtype)
    padded = -(-width // tile) * tile
    if padded != width:
        t = jnp.pad(t, ((0, 0), (0, padded - width)))
    return t.reshape(n, padded // LANES, LANES)


def _copy_rows_kernel(idx_ref, *refs, n_rows: int, mode: str):
    # refs = (in_0..in_{n-1}, out_0..out_{n-1}, dma semaphores)
    n = (len(refs) - 1) // 2
    ins, outs, sems = refs[:n], refs[n:2 * n], refs[-1]

    def copies(i):
        src, dst = (i, idx_ref[i]) if mode == "scatter" else (idx_ref[i], i)
        return [pltpu.make_async_copy(x.at[src], o.at[dst], sems.at[k])
                for k, (x, o) in enumerate(zip(ins, outs))]

    def start(i, carry):
        for cp in copies(i):
            cp.start()
        return carry

    def wait(i, carry):
        for cp in copies(i):
            cp.wait()
        return carry

    jax.lax.fori_loop(0, n_rows, start, 0)
    jax.lax.fori_loop(0, n_rows, wait, 0)


def permute_rows(idx, *tensors, mode: str = "scatter", interpret=None):
    """Route rows of every (N, D_t) tensor by ``idx`` in one fused pass.

    ``mode="scatter"``: ``out_t[idx[i]] = t[i]`` (``idx`` must be a
    permutation of ``0..N-1`` — each destination row is written exactly
    once).  ``mode="gather"``: ``out_t[i] = t[idx[i]]``.  The two modes are
    transposes of each other under the same ``idx``, which is exactly the
    scatter-by-permutation vjp pair.  Dtypes are per-ref (f32/bf16
    activations and int32 token rows mix freely).
    """
    if mode not in ("scatter", "gather"):
        raise ValueError(f"unknown row routing {mode!r}")
    interpret = resolve_interpret(interpret)
    n_rows = tensors[0].shape[0]
    slabs = [_as_row_slabs(t) for t in tensors]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[hbm] * len(slabs),
        out_specs=[hbm] * len(slabs),
        scratch_shapes=[pltpu.SemaphoreType.DMA((len(slabs),))],
    )
    outs = pl.pallas_call(
        functools.partial(_copy_rows_kernel, n_rows=n_rows, mode=mode),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(s.shape, s.dtype) for s in slabs],
        interpret=interpret,
    )(idx.astype(jnp.int32), *slabs)
    return [o.reshape(n_rows, -1)[:, :t.shape[1]]
            for o, t in zip(outs, tensors)]


def take_rows(idx, *tensors, interpret=None):
    """``out_t[i] = t[idx[i]]`` — :func:`permute_rows` in gather mode."""
    return permute_rows(idx, *tensors, mode="gather", interpret=interpret)
