"""Paged-KV model runner: prefill into pages, decode against block tables.

Execution contract (what makes the engine oracle-equivalent, pinned by
``tests/test_serve.py``):

* **Prefill** literally runs ``transformer.prefill`` on a contiguous
  single-sequence cache sized exactly to the prompt, then scatters the
  resulting K/V rows into the sequence's pages via its block table — so the
  engine's prefill logits are the *same floats* as the static-batch oracle's.
* **Decode** projects q/k/v through the same ``gqa_project`` /
  ``mla_project`` helpers the oracle uses, writes the new token's K/V into
  the page at ``lengths[b]``, and attends over ``lengths+1`` positions with
  either the Pallas paged kernel (``attention_impl="paged"``) or the dense
  gather reference (``"dense"``) — both masked with the oracle's
  ``NEG_INF`` bias, so padded page tails are exact no-ops.
* Everything is **row-independent** (attention per sequence, MoE routing
  groups = batch rows), so co-batched sequences can never perturb each
  other's tokens — the property continuous batching needs.

Page pools mirror the oracle cache pytree ({prefix, cycles, suffix}) and
are head-major, ``(num_pages, KV, page, d)``, the layout the paged kernel
tiles; MLA stores one fused ``c_kv ‖ k_rope`` pool per layer (one head;
values are the latent prefix, ``v_width`` in the kernel), keeping the MLA
cache-memory saving.

Compiled callables are cached per ``(cfg.name, …)`` at module level —
jax's own shape cache handles varying batch buckets and prompt lengths.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import resolve_interpret
from repro.kernels.paged_attention import (paged_decode_attention,
                                           paged_decode_attention_ref)
from repro.models import attention, blocks, moe, transformer
from repro.models.layers import rmsnorm, swiglu


def check_servable(cfg: ModelConfig) -> None:
    """The paged engine serves decoder-only, all-attention, rope/none-pos
    stacks with full (non-windowed) attention or MLA.  Everything else
    (ssm/rglru mixers, sliding-window ring caches, mrope frontends,
    enc-dec) stays on the static-batch oracle path."""
    reasons = []
    if cfg.is_encdec:
        reasons.append("encoder-decoder")
    if cfg.frontend:
        reasons.append(f"frontend={cfg.frontend}")
    if any(k != "attn" for k in cfg.pattern):
        reasons.append("non-attention mixers in block pattern")
    if cfg.attention not in ("full", "mla"):
        reasons.append(f"attention={cfg.attention!r} (need full or mla)")
    if cfg.rope == "mrope":
        reasons.append("mrope positions")
    if reasons:
        raise ValueError(
            f"{cfg.name} is not servable by the paged engine: "
            + "; ".join(reasons))


# ------------------------------------------------------------------ page pools

def _layer_pool(cfg: ModelConfig, num_pages: int, page_size: int, dtype):
    if cfg.attention == "mla":
        m = cfg.mla
        width = m.kv_lora_rank + m.qk_rope_head_dim
        return {"kv": jnp.zeros((num_pages, 1, page_size, width), dtype)}
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {"k": jnp.zeros((num_pages, KV, page_size, hd), dtype),
            "v": jnp.zeros((num_pages, KV, page_size, hd), dtype)}


def init_pages(cfg: ModelConfig, num_pages: int, page_size: int,
               dtype=jnp.float32):
    """Physical page pools, one per layer, mirroring the oracle cache pytree
    ({"prefix": tuple, "cycles": stacked tuple, "suffix": tuple})."""
    plan = transformer.stack_plan(cfg)
    one = lambda: _layer_pool(cfg, num_pages, page_size, dtype)
    pref = tuple(one() for _ in plan.prefix)
    suff = tuple(one() for _ in plan.suffix)
    if plan.n_cycles:
        cyc = tuple(
            jax.tree.map(
                lambda x: jnp.zeros((plan.n_cycles,) + x.shape, x.dtype),
                one())
            for _ in plan.pattern)
    else:
        cyc = None
    return {"prefix": pref, "cycles": cyc, "suffix": suff}


# ---------------------------------------------------------------- decode step

def _attn_decode(mp, cfg, page_size, xn, pool, tables, lengths, attn_fn,
                 interpret):
    """One layer's paged decode.  xn: (B,1,d) normed hidden; lengths: tokens
    already cached per row (the new token lands at position ``lengths[b]``)."""
    B = xn.shape[0]
    q_pos = lengths[:, None].astype(jnp.int32)
    pidx = jnp.take_along_axis(tables, (lengths // page_size)[:, None],
                               axis=1)[:, 0]
    off = lengths % page_size
    n_valid = lengths + 1

    if cfg.attention == "mla":
        m = cfg.mla
        q_full, c_kv, k_rope = attention.mla_project(mp, cfg, xn, q_pos)
        val = jnp.concatenate([c_kv, k_rope], axis=-1)[:, 0]       # (B, width)
        kv = pool["kv"].at[pidx, 0, off].set(val.astype(pool["kv"].dtype))
        scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
        out_lat = attn_fn(q_full[:, 0], kv, None, tables, n_valid,
                          scale=scale, v_width=m.kv_lora_rank,
                          interpret=interpret)
        out = attention.mla_output(mp, cfg, out_lat[:, None])
        return out, {"kv": kv}

    H, hd = cfg.n_heads, cfg.resolved_head_dim
    q, k, v = attention.gqa_project(mp, cfg, xn, q_pos)
    # advanced indices split by the head slice: the update is (B, KV, hd)
    kp = pool["k"].at[pidx, :, off].set(k[:, 0].astype(pool["k"].dtype))
    vp = pool["v"].at[pidx, :, off].set(v[:, 0].astype(pool["v"].dtype))
    out = attn_fn(q[:, 0], kp, vp, tables, n_valid,
                  scale=1.0 / math.sqrt(hd), interpret=interpret)
    out = jnp.einsum("bse,ed->bsd", out.reshape(B, 1, H * hd), mp["w_o"])
    return out, {"k": kp, "v": vp}


def _serve_block(bp, cfg, page_size, ffn, h, pool, tables, lengths, attn_fn,
                 interpret):
    """Residual block on the paged path — same math as ``blocks.block_apply``
    (attn mixer only; MoE aux loss dropped, decode never uses it)."""
    mixed, pool = _attn_decode(bp["mixer"], cfg, page_size,
                               rmsnorm(bp["norm1"], h, cfg.norm_eps),
                               pool, tables, lengths, attn_fn, interpret)
    h = h + mixed
    if ffn == "dense":
        h = h + swiglu(bp["ffn"], rmsnorm(bp["norm2"], h, cfg.norm_eps))
    elif ffn == "moe":
        out, _ = moe.moe_apply(bp["ffn"], cfg,
                               rmsnorm(bp["norm2"], h, cfg.norm_eps))
        h = h + out
    return h, pool


def make_decode_fn(cfg: ModelConfig, *, page_size: int,
                   attention_impl: str = "paged", interpret=None):
    """Jitted ``step(params, pages, tokens, lengths, tables) ->
    (logits (B,V), new_pages)``.

    tokens (B,) this step's input tokens · lengths (B,) tokens already in
    cache · tables (B, max_pages) block tables (trash page 0 beyond each
    row's pages; padded rows all-trash with length 0 — row independence
    makes their garbage logits harmless).
    """
    check_servable(cfg)
    if attention_impl not in ("paged", "dense"):
        raise ValueError(f"attention_impl={attention_impl!r}")
    plan = transformer.stack_plan(cfg)
    interp = resolve_interpret(interpret)
    attn_fn = (paged_decode_attention if attention_impl == "paged"
               else paged_decode_attention_ref)
    ffn_prefix = [blocks.ffn_kind(cfg, i) for i in plan.prefix]
    ffn_cycle = [blocks.ffn_kind(cfg, plan.cycle_start + j)
                 for j in range(len(plan.pattern))]
    ffn_suffix = [blocks.ffn_kind(cfg, i) for i in plan.suffix]

    def step(params, pages, tokens, lengths, tables):
        lengths = lengths.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        h = transformer.embed_tokens(params, cfg, tokens[:, None])
        new_prefix = []
        for i, bp in enumerate(params["prefix"]):
            h, pool = _serve_block(bp, cfg, page_size, ffn_prefix[i], h,
                                   pages["prefix"][i], tables, lengths,
                                   attn_fn, interp)
            new_prefix.append(pool)
        new_cycles = pages["cycles"]
        if plan.n_cycles:
            def body(hh, xs):
                cp, cpools = xs
                outs = []
                for j in range(len(plan.pattern)):
                    hh, pj = _serve_block(cp[j], cfg, page_size, ffn_cycle[j],
                                          hh, cpools[j], tables, lengths,
                                          attn_fn, interp)
                    outs.append(pj)
                return hh, tuple(outs)
            h, new_cycles = jax.lax.scan(
                body, h, (params["cycles"], pages["cycles"]))
        new_suffix = []
        for i, bp in enumerate(params["suffix"]):
            h, pool = _serve_block(bp, cfg, page_size, ffn_suffix[i], h,
                                   pages["suffix"][i], tables, lengths,
                                   attn_fn, interp)
            new_suffix.append(pool)
        logits = transformer._logits(params, cfg, h)[:, 0]
        return logits, {"prefix": tuple(new_prefix), "cycles": new_cycles,
                        "suffix": tuple(new_suffix)}

    return jax.jit(step)


# -------------------------------------------------------------------- prefill

def make_prefill_fn(cfg: ModelConfig, *, page_size: int):
    """Jitted ``prefill(params, pages, prompt (1,P), table (max_pages,)) ->
    (logits (1,V), new_pages)``.

    Runs the *oracle's* ``transformer.prefill`` on a contiguous cache sized
    exactly (1, P), then scatters the cache rows into the sequence's pages —
    identical prefill floats to the static-batch path by construction.
    Compiles once per distinct prompt length (the engine buckets arrivals).
    """
    check_servable(cfg)
    plan = transformer.stack_plan(cfg)

    def prefill(params, pages, prompt, table):
        P = prompt.shape[1]
        table = table.astype(jnp.int32)
        cache = transformer.init_cache(cfg, 1, P)
        logits, cache = transformer.prefill(params, cfg, cache, prompt)
        pos = jnp.arange(P, dtype=jnp.int32)
        pidx = table[pos // page_size]
        off = pos % page_size

        def write(pool, rows):
            # pool (num_pages, KV, page, w) <- rows (P, KV, w): advanced
            # indices split by the head slice put the token axis first
            return pool.at[pidx, :, off].set(rows.astype(pool.dtype))

        def copy(pool, cl, stacked):
            if cfg.attention == "mla":
                val = jnp.concatenate([cl["c_kv"], cl["k_rope"]], axis=-1)
                rows = {"kv": val[..., None, :]}  # one fused head
            else:
                rows = {"k": cl["k"], "v": cl["v"]}
            if stacked:                       # (n_cycles, 1, P, KV, w)
                return {n: jax.vmap(write)(pool[n], r[:, 0])
                        for n, r in rows.items()}
            return {n: write(pool[n], r[0]) for n, r in rows.items()}

        new_prefix = tuple(copy(pages["prefix"][i], cache["prefix"][i], False)
                           for i in range(len(plan.prefix)))
        new_suffix = tuple(copy(pages["suffix"][i], cache["suffix"][i], False)
                           for i in range(len(plan.suffix)))
        new_cycles = pages["cycles"]
        if plan.n_cycles:
            new_cycles = tuple(
                copy(pages["cycles"][j], cache["cycles"][j], True)
                for j in range(len(plan.pattern)))
        return logits, {"prefix": new_prefix, "cycles": new_cycles,
                        "suffix": new_suffix}

    return jax.jit(prefill)


# ------------------------------------------------- per-config compile caches

_PREFILL_CACHE: dict = {}
_DECODE_CACHE: dict = {}


def get_prefill_fn(cfg: ModelConfig, *, page_size: int):
    key = (cfg.name, page_size)
    if key not in _PREFILL_CACHE:
        _PREFILL_CACHE[key] = make_prefill_fn(cfg, page_size=page_size)
    return _PREFILL_CACHE[key]


def get_decode_fn(cfg: ModelConfig, *, page_size: int,
                  attention_impl: str = "paged", interpret=None):
    key = (cfg.name, page_size, attention_impl, resolve_interpret(interpret))
    if key not in _DECODE_CACHE:
        _DECODE_CACHE[key] = make_decode_fn(
            cfg, page_size=page_size, attention_impl=attention_impl,
            interpret=interpret)
    return _DECODE_CACHE[key]
