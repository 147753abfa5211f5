"""Per-assigned-architecture smoke tests (reduced configs, CPU).

For each registered architecture: instantiate the reduced same-family
variant, run one forward and one TL train step, assert output shapes and
finiteness; run decode and check it matches the full forward.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, get_config
from repro.core.tl_step import make_train_step
from repro.models import build_model
from repro.optim import adam

ARCH_IDS = sorted(ARCHS)


def _batch(cfg, key, B=2, S=16):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
    extra = None
    if cfg.frontend:
        extra = jax.random.normal(key, (B, cfg.frontend_tokens, cfg.d_model)) * 0.02
        batch["embeds"] = extra
    return batch, extra


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finiteness(arch):
    cfg = get_config(arch, reduced=True)
    assert cfg.n_layers <= 3 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.n_routed_experts <= 4
    m = build_model(cfg)
    key = jax.random.PRNGKey(0)
    p = m.init(key)
    batch, extra = _batch(cfg, key)
    logits, aux = m.forward(p, batch["tokens"], extra)
    B, S = batch["tokens"].shape
    F = cfg.frontend_tokens if (cfg.frontend and not cfg.is_encdec) else 0
    assert logits.shape == (B, S + F, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_tl_train_step(arch):
    cfg = get_config(arch, reduced=True)
    m = build_model(cfg)
    key = jax.random.PRNGKey(1)
    p = m.init(key)
    opt = adam(1e-3)
    st = opt.init(p)
    step = jax.jit(make_train_step(m, cfg, opt))
    batch, _ = _batch(cfg, key)
    p2, st2, loss = step(p, st, batch)
    assert bool(jnp.isfinite(loss))
    # parameters actually moved
    moved = any(float(jnp.abs(a - b).max()) > 0
                for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p2)))
    assert moved
    # no NaNs anywhere in the updated tree
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(p2))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_forward(arch):
    cfg = get_config(arch, reduced=True)
    m = build_model(cfg)
    key = jax.random.PRNGKey(2)
    p = m.init(key)
    B, S = 2, 12
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    extra = None
    if cfg.frontend:
        extra = jax.random.normal(key, (B, cfg.frontend_tokens, cfg.d_model)) * 0.02
    cache = m.init_cache(B, max_len=S)
    if cfg.is_encdec:
        from repro.models import encdec
        cache["enc_out"] = encdec.encode(p, cfg, extra)
    outs = []
    for t in range(S):
        lg, cache = m.decode_step(p, cache, tokens[:, t],
                                  jnp.asarray(t, jnp.int32))
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    ref, _ = m.forward(p, tokens, extra if cfg.is_encdec else None)
    ref = ref[:, :S] if not cfg.frontend or cfg.is_encdec else \
        m.forward(p, tokens, None)[0][:, :S]
    rel = float(jnp.abs(dec - ref).max() / (jnp.abs(ref).max() + 1e-9))
    assert rel < 2e-3, f"decode diverges from forward: {rel}"


@pytest.mark.parametrize("arch", ["starcoder2-3b", "mamba2-780m",
                                  "recurrentgemma-9b"])
def test_long_context_archs_have_bounded_caches(arch):
    """The long_500k-eligible archs must have O(window/state) caches."""
    cfg = get_config(arch)          # FULL config
    m = build_model(cfg)
    cache = jax.eval_shape(lambda: m.init_cache(1, 524_288))
    total = sum(int(jnp.prod(jnp.asarray(l.shape))) * l.dtype.itemsize
                for l in jax.tree.leaves(cache))
    # bounded: far below a dense 500k KV cache of the same model
    assert total < 4e9, f"cache {total/1e9:.1f}GB is not bounded"
