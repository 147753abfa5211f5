"""Core layer primitives: norms, linear, SwiGLU, embeddings, RoPE / M-RoPE.

All parameters are plain dict pytrees of ``jnp.ndarray``; all apply functions
are pure.  Initializers take an explicit PRNG key.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------- init utils

def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32, scale: float = 1.0):
    std = scale / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * std).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ------------------------------------------------------------------- RMSNorm

def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


# -------------------------------------------------------------------- SwiGLU

def swiglu_init(key, d: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d, d_ff, dtype),
        "w_up": dense_init(k2, d, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d, dtype),
    }


@jax.custom_vjp
def swiglu_act(g, u):
    """``silu(g) * u``, computed once in its own elementwise pass.

    Left to autodiff, XLA fuses this activation (forward) and its gradient
    (backward) into the prologue of the matmul that consumes it, which
    re-evaluates the ``exp`` and re-reads ``g`` and ``u`` for every output
    tile.  The optimization barriers on the values crossing into and out of
    the matmuls keep the elementwise work out of every matmul fusion.
    """
    return _swiglu_act_fwd(g, u)[0]


def _swiglu_act_fwd(g, u):
    g_in, u_in = jax.lax.optimization_barrier((g, u))
    h = jax.nn.silu(g_in) * u_in
    return jax.lax.optimization_barrier(h), (g, u)


def _swiglu_act_bwd(res, dh):
    g, u, dh = jax.lax.optimization_barrier((*res, dh))
    s = jax.nn.sigmoid(g)
    dg = dh * u * s * (1 + g * (1 - s))
    du = dh * g * s
    return jax.lax.optimization_barrier((dg, du))


swiglu_act.defvjp(_swiglu_act_fwd, _swiglu_act_bwd)


def swiglu(params, x):
    g = jnp.einsum("...d,df->...f", x, params["w_gate"])
    u = jnp.einsum("...d,df->...f", x, params["w_up"])
    return jnp.einsum("...f,fd->...d", swiglu_act(g, u), params["w_down"])


# ---------------------------------------------------------------------- RoPE

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                         # (hd/2,)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,S,1,hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions_3d, theta: float = 10000.0, sections=(2, 3, 3)):
    """Qwen2-VL multimodal rotary embedding [arXiv:2409.12191].

    The rotary feature dim is split into three sections (temporal / height /
    width), each rotated by its own position-id stream.  ``positions_3d`` is
    (3, ..., S).  With text-only inputs all three streams coincide, matching
    vanilla RoPE behaviour.
    """
    hd = x.shape[-1]
    half = hd // 2
    total = sum(sections)
    bounds = []
    acc = 0
    for s in sections:
        acc += s
        bounds.append(half * acc // total)
    freqs = rope_freqs(hd, theta)                         # (half,)
    # pick which position stream drives each frequency slot
    sec_id = jnp.zeros((half,), jnp.int32)
    prev = 0
    for i, b in enumerate(bounds):
        sec_id = jnp.where((jnp.arange(half) >= prev) & (jnp.arange(half) < b), i, sec_id)
        prev = b
    # gather per-slot positions: positions_3d (3, B, S) -> per-slot (B, S, half)
    p = positions_3d.astype(jnp.float32)                  # (3, B, S)
    p_slot = p[sec_id]                                    # (half, B, S) via fancy index on axis 0
    p_slot = jnp.moveaxis(p_slot, 0, -1)                  # (B, S, half)
    ang = p_slot[..., None, :] * freqs                    # (B, S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------- causal depthwise conv

def causal_conv1d_init(key, channels: int, kernel: int, dtype=jnp.float32):
    return {"w": (jax.random.normal(key, (kernel, channels)) / math.sqrt(kernel)).astype(dtype),
            "b": jnp.zeros((channels,), dtype)}


def causal_conv1d(params, x):
    """Depthwise causal conv.  x: (B, S, C) -> (B, S, C)."""
    k = params["w"].shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1], :] * params["w"][i] for i in range(k))
    return out + params["b"]


def causal_conv1d_step(params, state, x_t):
    """Single decode step.  state: (B, k-1, C); x_t: (B, C)."""
    k = params["w"].shape[0]
    window = jnp.concatenate([state, x_t[:, None, :]], axis=1)      # (B, k, C)
    out = jnp.einsum("bkc,kc->bc", window, params["w"]) + params["b"]
    return window[:, 1:, :], out
