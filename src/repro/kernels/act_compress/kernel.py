"""Activation compression Pallas TPU kernels (paper §5.2).

TL's wire traffic is first-layer activations + first/last-layer gradients;
the paper proposes compressing them.  These kernels perform per-row absmax
quantization (and dequantization) at two rungs — int8 and fp8 (e4m3) — so
a (tokens, d_model) activation block ships over ICI/DCN at ~4× fewer bytes
plus one f32 scale per row.

Quantizer formulation (shared by both rungs, and load-bearing for the
error-feedback lane in ``repro.core.transport``):

    scale = absmax(row)   (1 for an all-zero row)
    q     = round(x / scale * DENOM)        # int8: clip to ±127; fp8: cast
    x'    = q / DENOM * scale

i.e. the *scale is the raw absmax* and DENOM (127 / 256) divides at
dequant time.  A spatially-constant row then round-trips **bit-exactly**:
``x/scale = ±1.0`` and ``q/DENOM = ±1.0`` are exact float ops, so
``x' == x`` and the error-feedback residual of a constant tensor is
*exactly zero* — the lossless-in-the-limit property the transport's EF
accumulator tests pin.  (The historical ``scale = absmax/127`` form fails
this: ``fl(127 · fl(c/127)) != c`` in general.)  The scale is never
clamped up to an epsilon: a row whose absmax is tiny or subnormal would
then quantize to zero instead of to its rails.  Only an all-zero row needs
a stand-in scale, and any positive one dequantizes its zeros exactly.

Grid: row blocks.  BlockSpec tile (BR, D) f32 in, (BR, D) int8|fp8 +
(BR, 1) f32 scales out — a column block whose minor dim is the whole
array dim, which Mosaic tiles (a 1-D ``(BR,)`` block it refuses).  E.g.
BR=256, D=8192 → 8 MB in-tile, within VMEM for one buffer; use BR=128 for
d_model=8192 models to leave double-buffer headroom.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# codec -> (wire dtype, dequant denominator).  Exactness at the rails
# (q = ±DENOM ↔ |x| = absmax) is enforced by selects, not arithmetic — see
# ``quantize_levels`` / ``dequantize_levels``.  fp8 uses e4m3fn with a
# power-of-two denominator: 256 <= 448 (e4m3 max normal) so there is no
# overflow, ±256 is exactly representable, and e4m3's ~2^-4 relative
# precision is unchanged by which slice of the exponent range we use.
# int8 keeps the conventional 127 (the absmax/127 error bound is pinned by
# tests).
CODECS = {
    "int8": (jnp.int8, 127.0),
    "fp8": (jnp.float8_e4m3fn, 256.0),
}

_MIN_NORMAL_BITS = 0x00800000          # int32 bits of the smallest normal f32


def _check_codec(codec: str):
    if codec not in CODECS:
        raise ValueError(f"unknown wire codec {codec!r}; "
                         f"one of {sorted(CODECS)}")
    return CODECS[codec]


def _bits(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _magnitude_bits(x):
    """``|x|`` as int32 bits, which order like the magnitudes themselves —
    so a max over them is exact even where a flushing ALU would zero a
    subnormal float max."""
    return _bits(x) & 0x7FFFFFFF


def row_scale(x):
    """Per-row absmax as an (R, 1) column; an all-zero row gets 1."""
    top = jnp.max(_magnitude_bits(x), axis=-1, keepdims=True)
    absmax = jax.lax.bitcast_convert_type(top, jnp.float32)
    return jnp.where(top == 0, 1.0, absmax)


def quantize_levels(x, scale, codec: str):
    """f32 rows -> wire levels.  Elements with ``|x| == scale`` (compared
    bitwise) go straight to the rails ``±DENOM`` — the exactness the EF
    lane needs must not rest on ``x / scale`` rounding to 1, because XLA:CPU
    and the TPU flush subnormals: a row whose absmax is subnormal would
    otherwise divide 0/0.  Such a row's other elements are zero to a
    flushing ALU, and quantize to 0."""
    qdtype, denom = _check_codec(codec)
    normal = _bits(scale) >= _MIN_NORMAL_BITS
    u = x / jnp.where(normal, scale, 1.0) * denom
    rail = jnp.where(_bits(x) < 0, -denom, denom)
    u = jnp.where(_magnitude_bits(x) == _bits(scale), rail, u)
    if codec == "int8":
        return jnp.clip(jnp.round(u), -127, 127).astype(qdtype)
    # e4m3 cast rounds to nearest; |u| <= 256 < 448 max normal
    return u.astype(qdtype)


def dequantize_levels(qf, scale, denom):
    """Wire levels (as f32) -> values.  The rails ``q == ±DENOM`` select
    ``±scale`` itself, bit-exact: XLA is free to rewrite ``q / DENOM *
    scale`` into ``q · fl(1/DENOM) · scale`` or ``q · (scale/DENOM)``,
    either of which is an ulp off at the rails — and the rails are exactly
    where the error-feedback exactness argument lives (a constant row
    quantizes to all-rails and must round-trip bit-equal, so its residual is
    exactly zero).  Interior levels only need the bounded-error property,
    which any rewrite preserves."""
    interior = qf / denom * scale
    return jnp.where(jnp.abs(qf) == denom,
                     jnp.where(qf < 0, -scale, scale), interior)


def _make_quant_kernel(codec: str):
    def _quant_kernel(x_ref, q_ref, s_ref):
        x = x_ref[...].astype(jnp.float32)
        scale = row_scale(x)
        q_ref[...] = quantize_levels(x, scale, codec)
        s_ref[...] = scale

    return _quant_kernel


def _make_dequant_kernel(codec: str):
    _, denom = _check_codec(codec)

    def _dequant_kernel(q_ref, s_ref, x_ref):
        qf = q_ref[...].astype(jnp.float32)
        x_ref[...] = dequantize_levels(qf, s_ref[...], denom).astype(
            x_ref.dtype)

    return _dequant_kernel


def quantize_rows(x, *, codec: str = "int8", block_rows: int = 128,
                  interpret=None):
    """x: (R, D) -> (int8|fp8 (R, D), scales f32 (R,)). R % block_rows == 0."""
    from repro.kernels import resolve_interpret
    interpret = resolve_interpret(interpret)
    qdtype, _ = _check_codec(codec)
    R, D = x.shape
    assert R % block_rows == 0
    grid = (R // block_rows,)
    q, scales = pl.pallas_call(
        _make_quant_kernel(codec),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, D), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), qdtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return q, scales[:, 0]


def dequantize_rows(q, scales, *, codec: str = "int8", out_dtype=jnp.float32,
                    block_rows: int = 128, interpret=None):
    """Inverse of :func:`quantize_rows` (same ``codec``)."""
    from repro.kernels import resolve_interpret
    interpret = resolve_interpret(interpret)
    _check_codec(codec)
    R, D = q.shape
    assert R % block_rows == 0
    grid = (R // block_rows,)
    return pl.pallas_call(
        _make_dequant_kernel(codec),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), out_dtype),
        interpret=interpret,
    )(q, scales[:, None])
