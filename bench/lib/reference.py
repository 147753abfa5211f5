"""Plain reference of the llama-style decoder both configurations use.

Straightforward ``jax.numpy``, no kernels, no cache, no batching tricks,
computed in float32 at ``highest`` matmul precision.  The control
(``precision="fp8"``) is the same reference one step below the precision
the configuration states for the program's matmuls (one bfloat16 pass):
every matmul, forward and backward, multiplies float8 e4m3 inputs, each
scaled by its absolute maximum along the contracted axes, and accumulates
in float32.  It imports nothing of the program and reads the weights tree
of ``bench.lib.weights``.

The architecture is DeepSeek LLM's (arXiv:2401.02954, Hugging Face
``LlamaForCausalLM``): pre-norm RMSNorm blocks, rotary embeddings on the
two halves of each head (``rotate_half``), causal attention with key/value
head ``h // (H / KV)`` serving query head ``h``, a SwiGLU MLP, a final
RMSNorm and an untied head.  One departure, which the configuration file
states as ``embedding_scale``: the program multiplies the embedding by
sqrt(hidden_size), and the reference does the same.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.counts import dims


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (B, S, H, hd); positions (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]      # (S, hd/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _fp8(x, axes):
    """``x`` rounded to float8 e4m3 after scaling each slice along ``axes``
    to the format's largest value; returned in float32."""
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / top
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _contracted(spec: str):
    """'ab,bc->ac' -> ((1,), (0,)): each operand's axes summed over."""
    ins, out = spec.split("->")
    a, b = ins.split(",")
    gone = set(a + b) - set(out)
    return (tuple(i for i, ch in enumerate(a) if ch in gone),
            tuple(i for i, ch in enumerate(b) if ch in gone))


def _fp8_einsum(spec: str):
    """einsum ``spec`` of two operands whose products, and both products of
    its backward pass, take float8 inputs."""
    ins, out = spec.split("->")
    a, b = ins.split(",")

    def mm(sp, x, y):
        cx, cy = _contracted(sp)
        return jnp.einsum(sp, _fp8(x, cx), _fp8(y, cy))

    @jax.custom_vjp
    def f(x, y):
        return mm(spec, x, y)

    def fwd(x, y):
        return f(x, y), (x, y)

    def bwd(res, g):
        x, y = res
        return mm(f"{out},{b}->{a}", g, y), mm(f"{a},{out}->{b}", x, g)

    f.defvjp(fwd, bwd)
    return f


def _mm(spec: str, x, y, precision: str):
    if precision == "fp8":
        return _fp8_einsum(spec)(x, y)
    return jnp.einsum(spec, x, y)


def _block(w, l, h, c, k, precision):
    B, S, d = h.shape
    H, KV, hd = k["H"], k["KV"], k["hd"]
    eps = c["rms_norm_eps"]
    m, f = w["mixer"], w["ffn"]

    def proj(x, wt):
        return _mm("bsd,df->bsf", x, wt, precision)

    x = _rmsnorm(h, w["norm1"]["scale"][l], eps)
    q = proj(x, m["w_q"][l]).reshape(B, S, H, hd)
    kk = proj(x, m["w_k"][l]).reshape(B, S, KV, hd)
    v = proj(x, m["w_v"][l]).reshape(B, S, KV, hd)
    pos = jnp.arange(S)
    q, kk = _rope(q, pos, c["rope_theta"]), _rope(kk, pos, c["rope_theta"])
    kk = jnp.repeat(kk, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, kk, precision) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    o = _mm("bhqk,bkhd->bqhd", p, v, precision).reshape(B, S, H * hd)
    h = h + proj(o, m["w_o"][l])
    x = _rmsnorm(h, w["norm2"]["scale"][l], eps)
    g = proj(x, f["w_gate"][l])
    u = proj(x, f["w_up"][l])
    return h + proj(jax.nn.silu(g) * u, f["w_down"][l])


def logits(w, c: dict, tokens, precision: str = "highest"):
    """(B, S) int tokens -> (B, S, V) float32 logits."""
    k = dims(c)
    h = w["embed"][tokens] * jnp.float32(math.sqrt(k["d"]))
    blk = w["cycles"][0]
    for l in range(k["L"]):
        h = _block(blk, l, h, c, k, precision)
    h = _rmsnorm(h, w["final_norm"]["scale"], c["rms_norm_eps"])
    return _mm("bsd,dv->bsv", h, w["head"], precision)


def _nll_sum(w, c, tokens, targets, precision):
    lg = logits(w, c, tokens, precision)
    logp = jax.nn.log_softmax(lg, -1)
    return -jnp.take_along_axis(logp, targets[..., None], -1).sum()


def make_grad_fn(c: dict, precision: str = "highest"):
    """jitted (weights, tokens, targets) -> (summed NLL, its gradient)."""
    jf = jax.jit(jax.value_and_grad(
        lambda w, tokens, targets: _nll_sum(w, c, tokens, targets,
                                            precision)))

    def call(w, tokens, targets):
        with jax.default_matmul_precision("highest"):
            return jf(w, tokens, targets)
    return call


def loss_and_grad(grad_fn, w, tokens, targets, rows_per_block: int):
    """Mean next-token NLL over all rows and its gradient, accumulated over
    blocks of rows so that the activations of one block fit."""
    tokens, targets = np.asarray(tokens), np.asarray(targets)
    n_tok = tokens.size
    total, acc = 0.0, None
    for r in range(0, tokens.shape[0], rows_per_block):
        s, g = grad_fn(w, tokens[r:r + rows_per_block],
                       targets[r:r + rows_per_block])
        total += float(s)
        acc = g if acc is None else _add(acc, g)
        del g
    return total / n_tok, _scale(acc, 1.0 / n_tok)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, g):
    return jax.tree.map(jnp.add, acc, g)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scale(acc, k):
    return jax.tree.map(lambda a: a * k, acc)


def adamw_init(w):
    z = lambda a: jnp.zeros(a.shape, jnp.float32)   # noqa: E731
    return {"step": 0, "m": jax.tree.map(z, w), "v": jax.tree.map(z, w)}


def make_adamw_update(opt: dict):
    """AdamW as the traffic file states it: moments (b1, b2), bias
    correction, eps outside the root, decoupled weight decay added to the
    update, constant learning rate, optional clipping by global norm."""
    lr, b1, b2 = opt["lr"], opt["b1"], opt["b2"]
    eps, wd, clip = opt["eps"], opt["weight_decay"], opt.get("clip_norm")

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update(w, g, m, v, step):
        if clip is not None:
            norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
            g = jax.tree.map(
                lambda x: x * jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-9)),
                g)
        t = step.astype(jnp.float32)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t

        def one(p, gi, mi, vi):
            mi = b1 * mi + (1 - b1) * gi
            vi = b2 * vi + (1 - b2) * gi * gi
            u = (mi / bc1) / (jnp.sqrt(vi / bc2) + eps) + wd * p
            return p - lr * u, mi, vi

        leaves, treedef = jax.tree.flatten(w)
        out = [one(p, gi, mi, vi) for p, gi, mi, vi in zip(
            leaves, jax.tree.leaves(g), jax.tree.leaves(m),
            jax.tree.leaves(v))]
        pick = lambda i: jax.tree.unflatten(treedef,   # noqa: E731
                                            [o[i] for o in out])
        return pick(0), pick(1), pick(2), g

    def call(w, g, m, v, step):
        with jax.default_matmul_precision("highest"):
            return update(w, g, m, v, jnp.asarray(step, jnp.int32))

    return call
