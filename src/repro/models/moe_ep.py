"""Expert-parallel MoE via shard_map + explicit all_to_all (beyond-paper).

The GSPMD path (`moe.moe_apply`) lets the partitioner infer collectives for
the dispatch scatter/gather — correct, but the §Perf analysis showed it can
pick pessimal layouts.  This module is the hand-scheduled alternative used
by real EP systems:

  per (data, model) shard, locally:
    route -> build per-destination-shard buffers (TP, E_local, C, d)
  all_to_all over the model axis          (tokens travel to expert owners)
  local expert FFN over (E_local, TP*C, d)
  all_to_all back                         (results return to token owners)
  local combine with the saved slot map   (no metadata exchange: the return
                                           trip preserves the send layout)

Exactly two all_to_all collectives per MoE layer, each of
``tokens_local · top_k · d`` bytes — the information-theoretic minimum for
capacity routing.  Differentiable (all_to_all transposes to all_to_all).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import swiglu


def _local_route(x_flat, router_w, cfg: ModelConfig, tp: int, cap: int):
    """Route local tokens; build per-destination buffers and the slot map.

    x_flat: (T, d).  Returns (buffers (tp, E_loc, cap, d), slot map (T, k),
    gates (T, k), keep (T, k), aux).
    """
    m = cfg.moe
    E, k = m.n_routed_experts, m.top_k
    E_loc = E // tp
    T, d = x_flat.shape

    logits = (x_flat @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, k)                  # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    one_hot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
    f = one_hot.sum(axis=1).mean(axis=0)
    aux = (E * (f / k * probs.mean(axis=0)).sum()) * m.router_aux_weight

    # rank within (expert) over the local tokens — stable sort, token-major
    flat_e = expert_idx.reshape(T * k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(T * k) - starts[sorted_e]
    rank = jnp.zeros_like(flat_e).at[order].set(rank_sorted)    # (T*k,)

    keep = (rank < cap).reshape(T, k)
    dest = flat_e // E_loc                                      # target shard
    e_loc = flat_e % E_loc
    slot = jnp.where(rank < cap,
                     (dest * E_loc + e_loc) * cap + rank,
                     tp * E_loc * cap)                          # sink row
    buf = jnp.zeros((tp * E_loc * cap + 1, d), x_flat.dtype)
    buf = buf.at[slot].add(jnp.repeat(x_flat, k, axis=0))
    return (buf[:-1].reshape(tp, E_loc, cap, d), slot.reshape(T, k),
            gate.astype(x_flat.dtype), keep, aux)


def moe_apply_ep(params, cfg: ModelConfig, x, mesh: Mesh, *,
                 model_axis: str = "model", data_axis=("data",)):
    """Drop-in for ``moe.moe_apply`` with explicit expert parallelism.

    x: (B, S, d).  Must be called under ``mesh``; batch is expected sharded
    over ``data_axis``, experts shard over ``model_axis``.
    """
    m = cfg.moe
    tp = int(mesh.shape[model_axis])
    E, k = m.n_routed_experts, m.top_k
    assert E % tp == 0, "experts must divide the model axis"
    B, S, d = x.shape
    n_data = 1
    for a in data_axis:
        n_data *= int(mesh.shape[a])
    # tokens shard over data (batch) AND model (sequence): every chip routes
    # only its own slice — without this, all tp model-chips of a data row
    # dispatch the same tokens redundantly (tp× wasted expert compute)
    seq_shard = tp if S % tp == 0 else 1
    T_loc = (B // n_data) * (S // seq_shard)
    cap = max(1, int(math.ceil(T_loc * k * m.capacity_factor / E)))

    def local_fn(x_loc, router_w, w_gate, w_up, w_down):
        # x_loc: (B_loc, S_loc, d); expert weights: (E_loc, ...) local slices
        Bl, Sl = x_loc.shape[0], x_loc.shape[1]
        x_flat = x_loc.reshape(Bl * Sl, d)
        buf, slot, gate, keep, aux = _local_route(x_flat, router_w, cfg, tp,
                                                  cap)
        # tokens -> expert owners (split dim0 across model, gather sources)
        recv = jax.lax.all_to_all(buf, model_axis, split_axis=0,
                                  concat_axis=0, tiled=True)    # (tp,E_loc,cap,d)
        h_in = recv.transpose(1, 0, 2, 3).reshape(
            recv.shape[1], tp * cap, d)                         # (E_loc, tp*cap, d)
        g = jnp.einsum("ecd,edf->ecf", h_in, w_gate)
        u = jnp.einsum("ecd,edf->ecf", h_in, w_up)
        h_out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down)
        # results -> token owners (same layout back)
        send = h_out.reshape(recv.shape[1], tp, cap, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(send, model_axis, split_axis=0,
                                  concat_axis=0, tiled=True)    # (tp,E_loc,cap,d)
        out_buf = jnp.concatenate(
            [back.reshape(tp * back.shape[1] * cap, d),
             jnp.zeros((1, d), back.dtype)], axis=0)
        gathered = out_buf[slot.reshape(-1)].reshape(Bl * Sl, k, d)
        w = (gate * keep).astype(gathered.dtype)
        y = (gathered * w[..., None]).sum(axis=1).reshape(Bl, Sl, d)
        # aux is a local mean over this shard's tokens; average over shards
        aux_mean = jax.lax.pmean(aux, axis_name=model_axis)
        for a in data_axis:
            aux_mean = jax.lax.pmean(aux_mean, axis_name=a)
        return y, aux_mean

    dp = data_axis if len(data_axis) > 1 else data_axis[0]
    dspec = P(dp, model_axis if seq_shard > 1 else None, None)
    out = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(dspec, P(), P(model_axis, None, None),
                  P(model_axis, None, None), P(model_axis, None, None)),
        out_specs=(dspec, P()),
        check_vma=False,
    )(x, params["router"].astype(x.dtype), params["w_gate"], params["w_up"],
      params["w_down"])
    y, aux = out
    if m.n_shared_experts:
        y = y + swiglu(params["shared"], x)
    return y, aux
