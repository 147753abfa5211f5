"""Jit'd wrappers: compress/decompress arbitrary-shape activations, plus the
error-feedback accumulator step used by the transport's wire lanes."""
import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.act_compress.kernel import (CODECS, dequantize_rows,
                                               quantize_rows)


def _codec_of(q) -> str:
    """Recover the codec from a payload's wire dtype (int8 | fp8 e4m3)."""
    for name, (dtype, _) in CODECS.items():
        if q.dtype == dtype:
            return name
    raise ValueError(f"payload q has non-wire dtype {q.dtype}")


@functools.partial(jax.jit,
                   static_argnames=("codec", "block_rows", "interpret"))
def _compress(x, *, codec: str, block_rows: int, interpret: bool):
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    R = flat.shape[0]
    pad = (-R) % block_rows
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    q, s = quantize_rows(flat, codec=codec, block_rows=block_rows,
                         interpret=interpret)
    return {"q": q[:R], "scale": s[:R]}


def compress(x, *, codec: str = "int8", block_rows: int = 128,
             interpret=None):
    """x: (..., D) float -> dict(q int8|fp8, scale f32).  Rows padded to
    block.  ``codec`` picks the wire rung ("int8" | "fp8" e4m3, both with
    per-row f32 absmax scales); ``interpret`` resolves via
    ``REPRO_PALLAS_INTERPRET`` (see ``repro.kernels.resolve_interpret``)."""
    if not (hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)):
        raise TypeError(
            "act_compress.compress expects a floating-point tensor, got "
            f"dtype={getattr(x, 'dtype', type(x).__name__)}: quantizing "
            "integer/bool data through the float absmax grid would silently "
            "corrupt it — cast explicitly if that is really intended")
    return _compress(x, codec=codec, block_rows=block_rows,
                     interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("shape", "block_rows",
                                             "interpret", "out_dtype"))
def _decompress(payload, shape, *, out_dtype, block_rows: int,
                interpret: bool):
    q, s = payload["q"], payload["scale"]
    codec = _codec_of(q)
    R = q.shape[0]
    pad = (-R) % block_rows
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0)))
        s = jnp.pad(s, (0, pad))
    x = dequantize_rows(q, s, codec=codec, out_dtype=out_dtype,
                        block_rows=block_rows, interpret=interpret)
    return x[:R].reshape(shape)


def decompress(payload, shape, *, out_dtype=jnp.float32, block_rows: int = 128,
               interpret=None):
    """Inverse of :func:`compress`; the codec is recovered from the
    payload's wire dtype (same interpret-mode resolution)."""
    return _decompress(payload, shape, out_dtype=out_dtype,
                       block_rows=block_rows,
                       interpret=resolve_interpret(interpret))


def compressed_bytes(payload) -> int:
    """Wire size of one compressed payload: 1 B/element (int8 and fp8 are
    both single-byte dtypes) + one 4 B f32 scale per row."""
    return (payload["q"].size * payload["q"].dtype.itemsize
            + payload["scale"].size * 4)


def ef_compress(x, residual, *, codec: str = "int8", block_rows: int = 128,
                interpret=None):
    """One error-feedback step: compress ``x + residual``, return
    ``(payload, delivered, new_residual)``.

    The residual carries the quantization error *forward*: what this send
    loses, the next send of the same lane adds back in, so a repeatedly
    sent signal is transmitted losslessly in the limit (and a constant
    tensor exactly, from the first send — see ``kernel.py``).  ``residual``
    may be ``None`` (a fresh lane: zero residual).  All EF arithmetic runs
    in f32; ``delivered`` is cast back to ``x.dtype``."""
    xe = x.astype(jnp.float32)
    if residual is not None:
        # x + 0 must stay x bit-for-bit; a flushing ALU (XLA:CPU, TPU)
        # would zero a subnormal x in the add
        xe = jnp.where(residual == 0, xe, xe + residual)
    payload = compress(xe, codec=codec, block_rows=block_rows,
                       interpret=interpret)
    delivered = decompress(payload, xe.shape, out_dtype=jnp.float32,
                           block_rows=block_rows, interpret=interpret)
    new_residual = xe - delivered
    return payload, delivered.astype(x.dtype), new_residual
