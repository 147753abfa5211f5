"""Model configuration and weights, made by the benchmark from the seed.

The configuration file holds Hugging Face ``config.json`` keys; the weights
are random normals at the usual initial scales, made on the device in one
jitted call, in the layout the program's decoder takes (one scanned cycle
of ``num_hidden_layers`` blocks).  The plain reference reads the same tree,
so neither side takes weights that the other made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.counts import dims


def seed_key(seed: int):
    """A PRNG key from any whole seed (the driver's exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def model_config(c: dict, name: str):
    """The program's ``ModelConfig`` for a llama-style decoder file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=name, arch_type="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c.get("head_dim") or 0,
        attention="full", rope="rope", rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]))


def weight_shapes(c: dict) -> dict:
    k = dims(c)
    d, H, KV, hd, ff, L, V = (k[n] for n in ("d", "H", "KV", "hd", "ff",
                                              "L", "V"))
    block = {
        "norm1": {"scale": (L, d)},
        "mixer": {"w_q": (L, d, H * hd), "w_k": (L, d, KV * hd),
                  "w_v": (L, d, KV * hd), "w_o": (L, H * hd, d)},
        "norm2": {"scale": (L, d)},
        "ffn": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                "w_down": (L, ff, d)},
    }
    return {"embed": (V, d), "final_norm": {"scale": (d,)}, "head": (d, V),
            "prefix": (), "suffix": (), "cycles": (block,)}


def _is_shape(x):
    return (isinstance(x, tuple) and len(x) > 0
            and all(isinstance(i, int) for i in x))


def _init_leaf(key, path, shape):
    name = jax.tree_util.keystr(path)
    if "scale" in name:
        return jnp.ones(shape, jnp.float32)
    if name.endswith("['embed']"):
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    fan_in = shape[-2]
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)


_MAKERS = {}


def make_weights(c: dict, seed: int, shardings=None):
    """All weights, f32, from ``seed`` in one jitted call (placed by
    ``shardings`` when given, a tree of the same structure).  The call is
    built once per configuration and placement."""
    shapes = weight_shapes(c)
    key = (repr(shapes), None if shardings is None
           else tuple(jax.tree.leaves(shardings)))
    if key not in _MAKERS:
        leaves, treedef = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=_is_shape)

        def make(k):
            keys = jax.random.split(k, len(leaves))
            return jax.tree_util.tree_unflatten(
                treedef,
                [_init_leaf(kk, p, s) for kk, (p, s) in zip(keys, leaves)])
        _MAKERS[key] = jax.jit(make, out_shardings=shardings)
    return _MAKERS[key](seed_key(seed))
