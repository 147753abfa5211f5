"""Production TL training/serving steps for the multi-pod mesh.

The TPU-native realization of Traversal Learning (DESIGN.md §2):

* the virtual batch shards over the composite (pod, data) mesh axis — one
  shard per logical *node*;
* the node phase computes ``embed → block0`` locally (X^(1) and δ^(L) are
  per-shard values);
* the orchestrator phase is ``jax.checkpoint(tail, policy=nothing_saveable)``
  — the backward pass *recomputes* every activation beyond block 0 from
  X^(1) and the current parameters, exactly the paper's eq. 4–5 recompute,
  then backpropagates (eq. 6–11);
* gradient aggregation across nodes (eq. 6/12) is the psum GSPMD inserts
  for the data-parallel reduce — a single cross-pod collective per step.

``remat_mode`` selects the activation policy:
  "tl"       — paper-faithful: save only X^(1) (+ the node-local embed/block0
               residuals), recompute the whole tail during BP;
  "none"     — beyond-paper baseline: save everything (memory-bound);
  "per_layer"— beyond-paper middle ground: scan-level remat, save each
               cycle's inputs (the usual production policy).

``reassembly`` puts the orchestrator's virtual-batch reassembly on the
production hot path: the loader hands batches node-major (traversal order),
and the centralized phase reassembles X^(1) — and every row-aligned
consumer (targets, MTP tokens, masks) — into shuffled batch order before
the recompute-from-X^(1) BP, exactly the protocol simulator's
``.at[perm].set`` step.  ``"xla"`` uses the generic scatter lowering,
``"pallas"`` the fused ``repro.kernels.vb_scatter`` row-gather kernel
(bit-identical values, one HBM pass, differentiable through the TL loss via
its custom vjp), ``"none"`` skips reassembly (the historical driver).  The
scatter sits behind a ``shard_map`` boundary over the (pod, data) batch
axes: the batch dict's ``perm`` is *shard-local* (each shard's block of
``B/n_dp`` rows holds a permutation of ``0..B/n_dp``, the ranks of that
shard's rows' global batch positions — see ``launch.engine``), so
reassembly adds zero collective traffic at any node count.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.dist.sharding import (batch_axes, param_specs, tokens_pspec,
                                 cache_pspec)
from repro.models import transformer
from repro.models.model import (MTP_WEIGHT, Model, cross_entropy,
                                mtp_shift_targets)


# ------------------------------------------------------------- reassembly

def _make_row_permuter(mesh: Optional[Mesh], strategy: str) -> Callable:
    """Row reassembly ``out[perm[i]] = t[i]`` over batch-leading tensors.

    ``strategy`` selects the lowering ("xla" generic scatter vs "pallas"
    fused vb_scatter kernel).  With a mesh whose (pod, data) axes shard the
    batch, the permutation runs inside a ``shard_map`` over those axes —
    each shard scatters its own rows by its shard-local perm, so the
    reassembly never crosses a chip boundary.  Batches the data axes don't
    divide fall back to a global (replicated) permute, mirroring
    ``tokens_pspec``'s sharding decision for the batch itself.
    """
    def permute(perm, *tensors):
        if strategy == "pallas":
            from repro.kernels.vb_scatter import scatter_rows
            return scatter_rows(perm, tensors)
        return tuple(jnp.zeros_like(t).at[perm].set(t) for t in tensors)

    dp = batch_axes(mesh) if mesh is not None else ()
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if n_dp <= 1:
        return permute

    def sharded(perm, *tensors):
        B = perm.shape[0]
        if B % n_dp != 0 or B < n_dp:
            return permute(perm, *tensors)
        specs = tuple(P(dp, *([None] * (t.ndim - 1))) for t in tensors)
        return jax.shard_map(permute, mesh=mesh, in_specs=(P(dp),) + specs,
                             out_specs=specs, check_vma=False)(perm, *tensors)

    return sharded


# ------------------------------------------------------------------ TL loss

def tl_loss_fn(model: Model, cfg: ModelConfig, remat_mode: str = "tl",
               reassembly: str = "none", mesh: Optional[Mesh] = None):
    """Loss whose autodiff graph *is* the TL protocol."""
    F = cfg.frontend_tokens if (cfg.frontend and not cfg.is_encdec) else 0
    if reassembly not in ("none", "xla", "pallas"):
        raise ValueError(f"unknown reassembly strategy: {reassembly!r}")

    if cfg.is_encdec:
        if reassembly != "none":
            raise ValueError("reassembly applies to the decoder-LM TL "
                             "split; enc-dec losses take the model.loss "
                             "path")
        # TL boundary for enc-dec: decoder block 0.  The encoder runs in the
        # node phase (it consumes node-local frontend embeddings).
        def loss(params, batch):
            with jax.named_scope("tl_loss"):
                return model.loss(params, batch)[0]
        return loss

    permute_rows = (_make_row_permuter(mesh, reassembly)
                    if reassembly != "none" else None)

    def tail_fn(params, h1, tokens):
        # scoped inside the checkpointed function, so the recompute carries
        # the scope under ``rematted_computation``
        with jax.named_scope("tl_tail"):
            return transformer.tail(params, cfg, h1, return_hidden=True)

    if remat_mode == "tl":
        tail_exec = jax.checkpoint(
            tail_fn, policy=jax.checkpoint_policies.nothing_saveable)
    elif remat_mode == "none":
        tail_exec = tail_fn
    elif remat_mode == "dots":
        # beyond-paper middle ground: keep matmul outputs, recompute the rest
        tail_exec = jax.checkpoint(
            tail_fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    else:
        raise ValueError(remat_mode)

    def loss(params, batch):
        tokens, targets = batch["tokens"], batch["targets"]
        mask = batch.get("mask")
        extra = batch.get("embeds")
        # ---- node phase: first-layer activations X^(1)
        with jax.named_scope("tl_node"):
            h0 = transformer.embed_tokens(params, cfg, tokens, extra)
            h1, aux0 = transformer.block0(params, cfg, h0)
        if permute_rows is not None:
            # ---- centralized-phase prologue: reassemble the node-major
            # virtual batch into shuffled batch order (shard-local perms,
            # see module docstring) — X^(1) plus every row-aligned consumer
            rows = {"h1": h1, "targets": targets}
            if cfg.mtp_depth:
                rows["tokens"] = tokens
            if mask is not None:
                rows["mask"] = mask
            # the barriers fence the reassembly off from its neighbours so
            # XLA compiles both phases alike under either lowering, and the
            # strategies give the same floats (on the TPU a generic scatter
            # fused into its consumers moved the loss by a few ulps)
            fence = jax.lax.optimization_barrier
            with jax.named_scope("tl_reassembly"):
                rows = dict(zip(rows, fence(permute_rows(
                    batch["perm"], *fence(tuple(rows.values()))))))
            h1, targets = rows["h1"], rows["targets"]
            tokens = rows.get("tokens", tokens)
            mask = rows.get("mask", mask)
        # ---- orchestrator phase: recompute-from-X^(1) BP.  Scoped here as
        # well: what depends on no input of the checkpointed function (the
        # RoPE tables) is recomputed without the scope of its inside
        with jax.named_scope("tl_tail"):
            logits, h_final, aux = tail_exec(params, h1, tokens)
        with jax.named_scope("tl_loss"):
            logits_txt = logits[:, F:] if F else logits
            ce = cross_entropy(logits_txt, targets, mask)
            total = ce + aux + aux0
            if cfg.mtp_depth:
                h_txt = h_final[:, F:] if F else h_final
                mtp = transformer.mtp_logits(params, cfg, tokens, h_txt)
                t2, valid = mtp_shift_targets(targets)
                total = total + MTP_WEIGHT * cross_entropy(mtp, t2, valid)
        return total

    return loss


# ------------------------------------------------------------- train step

def make_train_step(model: Model, cfg: ModelConfig, optimizer, *,
                    remat_mode: str = "tl", microbatch: int = 1,
                    reassembly: str = "none",
                    mesh: Optional[Mesh] = None) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, loss).

    jit/lower with in_shardings from :func:`train_shardings`; GSPMD then
    realizes the TL node axis + orchestrator reduction.

    ``microbatch > 1`` splits the virtual batch into that many sequential
    micro-batches with gradient accumulation (beyond-paper: the update stays
    bit-identical to the full-batch TL update — mean of micro-grads — while
    activation peak memory drops ~microbatch×).

    ``reassembly`` ("none" | "xla" | "pallas") reassembles the virtual
    batch inside the loss (module docstring); the batch dict then carries a
    shard-local ``perm``.  ``mesh`` places the shard_map boundary.
    """
    if reassembly != "none" and microbatch > 1:
        # the perm is defined over the full virtual batch; gradient
        # accumulation slices the batch before reassembly is well-defined
        raise ValueError("reassembly requires microbatch == 1")
    loss_fn = tl_loss_fn(model, cfg, remat_mode, reassembly=reassembly,
                         mesh=mesh)

    if microbatch <= 1:
        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            with jax.named_scope("tl_optimizer"):
                params, opt_state = optimizer.update(params, grads, opt_state)
            return params, opt_state, loss
        return step

    def step(params, opt_state, batch):
        def reshape(x):
            return x.reshape((microbatch, x.shape[0] // microbatch)
                             + x.shape[1:])
        micro = {k: reshape(v) for k, v in batch.items()}

        def body(carry, mb):
            acc, loss_acc = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, mb)
            acc = jax.tree.map(jnp.add, acc, grads)
            return (acc, loss_acc + loss), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss_sum), _ = jax.lax.scan(body, (zeros, 0.0), micro)
        grads = jax.tree.map(lambda g, p: (g / microbatch).astype(p.dtype),
                             grads, params)
        with jax.named_scope("tl_optimizer"):
            params, opt_state = optimizer.update(params, grads, opt_state)
        return params, opt_state, loss_sum / microbatch

    return step


def train_shardings(params, opt_state, cfg: ModelConfig, mesh: Mesh,
                    shape: InputShape, *, with_embeds: bool = False,
                    with_perm: bool = False):
    """(in_shardings, out_shardings) pytrees for make_train_step's step.

    ``with_perm=True`` adds the reassembly permutation's spec: ``perm``
    shards with the batch rows (``tokens_pspec``'s batch entry) so each
    shard holds exactly the local perm for its own rows."""
    pspecs = param_specs(params, cfg, mesh)

    # optimizer slots mirror their parameter's sharding rule (paths align
    # because slot trees are tree_map'd off params); scalars replicate
    from repro.dist.sharding import _mesh_sizes, param_pspec
    sizes = _mesh_sizes(mesh)

    def slot_spec(path, leaf):
        if leaf.ndim == 0:
            return P()
        return param_pspec(path, leaf, cfg, axis_sizes=sizes)
    opt_specs = jax.tree_util.tree_map_with_path(slot_spec, opt_state)

    tok_spec = tokens_pspec(mesh, shape.global_batch)
    batch_specs = {"tokens": tok_spec, "targets": tok_spec}
    if with_embeds:
        batch_specs["embeds"] = P(tok_spec[0], None, None)
    if with_perm:
        batch_specs["perm"] = P(tok_spec[0])
    named = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))
    in_sh = (named(pspecs), named(opt_specs), named(batch_specs))
    out_sh = (named(pspecs), named(opt_specs), NamedSharding(mesh, P()))
    return in_sh, out_sh


# ------------------------------------------------------------- serve step

def make_serve_step(model: Model, cfg: ModelConfig) -> Callable:
    """(params, cache, token, cache_len) -> (logits, cache)."""
    def step(params, cache, token, cache_len):
        return model.decode_step(params, cache, token, cache_len)
    return step


def serve_shardings(params, cache, cfg: ModelConfig, mesh: Mesh,
                    shape: InputShape, *, cache_seq_shard: bool = False,
                    fsdp: Optional[bool] = None):
    """``cache_seq_shard=True`` additionally shards the KV-cache *sequence*
    dim over the ``model`` axis (flash-decoding layout, beyond-paper): each
    model shard owns a contiguous chunk of the context and decode attention
    reduces partial softmax statistics instead of all-gathering the cache.
    ``fsdp=False`` serves with TP-only weight sharding (no per-step weight
    all-gathers)."""
    pspecs = param_specs(params, cfg, mesh, fsdp=fsdp)
    B = shape.global_batch

    def cache_spec(path, leaf):
        name = "/".join(str(getattr(e, "key", getattr(e, "idx", e)))
                        for e in path)
        last = name.split("/")[-1]
        nd = leaf.ndim
        # leading stacked-layer axis inside "cycles"/"self" stacks
        lead = 1 if ("cycles" in name or "self" in name) else 0
        core = nd - lead
        if last == "pos":
            return P(*((None,) * nd))
        kind = "state" if last in ("state", "h", "conv", "enc_out") else "kv"
        base = tuple(cache_pspec(mesh, B, kind))
        if cache_seq_shard and kind == "kv":
            # (B, S, ...): batch on dp when divisible; sequence on model
            # (plus dp when the batch dim can't shard, e.g. batch=1)
            if base and base[0] is not None:
                base = (base[0], "model")
            else:
                base = (None, ("model",) + tuple(batch_axes(mesh)))
        spec = list(((None,) * lead + base + (None,) * nd)[:nd])
        # drop axes that don't divide their dim
        for i, (dim, ax) in enumerate(zip(leaf.shape, spec)):
            if ax is None:
                continue
            axes_tuple = ax if isinstance(ax, tuple) else (ax,)
            size = 1
            for a in axes_tuple:
                size *= mesh.shape[a]
            if dim % size != 0:
                spec[i] = None
        return P(*spec)

    cspecs = jax.tree_util.tree_map_with_path(cache_spec, cache)
    dp = batch_axes(mesh)
    import numpy as np
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    tok_spec = P(dp) if B % n_dp == 0 and B >= n_dp else P()
    named = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))
    in_sh = (named(pspecs), named(cspecs), named(tok_spec),
             NamedSharding(mesh, P()))
    out_sh = (named(P(dp) if B % n_dp == 0 and B >= n_dp else P()),
              named(cspecs))
    return in_sh, out_sh
