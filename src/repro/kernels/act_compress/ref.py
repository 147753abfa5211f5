"""Pure-jnp oracles for the int8/fp8 row quantizers.

Same formulation as the Pallas kernels (``scale = absmax``, DENOM divides
at dequant time) — see ``kernel.py`` for why that form, not
``scale = absmax/DENOM``, is load-bearing for the error-feedback lane.
"""
import jax.numpy as jnp

from repro.kernels.act_compress.kernel import (CODECS, dequantize_levels,
                                               quantize_levels, row_scale)


def quantize_rows_ref(x, codec: str = "int8"):
    x = x.astype(jnp.float32)
    scale = row_scale(x)
    return quantize_levels(x, scale, codec), scale[:, 0]


def dequantize_rows_ref(q, scale, out_dtype=jnp.float32, codec: str = "int8"):
    _, denom = CODECS[codec]
    return dequantize_levels(q.astype(jnp.float32), scale[:, None],
                             denom).astype(out_dtype)
