"""Model-component unit tests: attention paths, MoE invariants, analytics."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.analysis.hlo_flops import _shape_elems_bytes
from repro.analysis.roofline import collective_bytes, shape_bytes
from repro.configs import get_config
from repro.models import moe as moe_mod
from repro.models.attention import attend_blockwise, attend_dense
from repro.models.layers import apply_mrope, apply_rope, causal_conv1d, \
    causal_conv1d_init, causal_conv1d_step, rmsnorm, rmsnorm_init, swiglu, \
    swiglu_init


def test_blockwise_attention_equals_dense():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, S, H, D = 2, 300, 4, 32
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, 2, D))
    v = jax.random.normal(ks[2], (B, S, 2, D))
    pos = jnp.arange(S)
    d = attend_dense(q, k, v, pos, pos, 0, 1 / math.sqrt(D))
    b = attend_blockwise(q, k, v, pos, pos, 0, 1 / math.sqrt(D), block=64)
    np.testing.assert_allclose(np.asarray(d), np.asarray(b), atol=1e-5)


def test_blockwise_sliding_window():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, S, H, D, W = 1, 200, 2, 16, 32
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    pos = jnp.arange(S)
    d = attend_dense(q, k, v, pos, pos, W, 1 / math.sqrt(D))
    b = attend_blockwise(q, k, v, pos, pos, W, 1 / math.sqrt(D), block=64)
    np.testing.assert_allclose(np.asarray(d), np.asarray(b), atol=1e-5)


def test_rope_relative_property():
    """RoPE: scores depend only on relative distance."""
    k = jax.random.PRNGKey(2)
    q = jax.random.normal(k, (1, 1, 1, 32))
    pos_a = jnp.asarray([[5]])
    pos_b = jnp.asarray([[9]])
    qa = apply_rope(q, pos_a)
    qb = apply_rope(q, pos_b)
    ka = apply_rope(q, pos_a + 3)
    kb = apply_rope(q, pos_b + 3)
    s_a = float(jnp.sum(qa * ka))
    s_b = float(jnp.sum(qb * kb))
    assert abs(s_a - s_b) < 1e-4


def test_mrope_text_only_equals_rope():
    k = jax.random.PRNGKey(3)
    x = jax.random.normal(k, (2, 6, 4, 32))
    pos = jnp.broadcast_to(jnp.arange(6), (2, 6))
    p3 = jnp.broadcast_to(jnp.arange(6), (3, 2, 6))
    np.testing.assert_allclose(np.asarray(apply_rope(x, pos)),
                               np.asarray(apply_mrope(x, p3)), atol=1e-5)


def test_causal_conv_step_matches_full():
    k = jax.random.PRNGKey(4)
    p = causal_conv1d_init(k, channels=8, kernel=4)
    x = jax.random.normal(k, (2, 10, 8))
    full = causal_conv1d(p, x)
    state = jnp.zeros((2, 3, 8))
    outs = []
    for t in range(10):
        state, o = causal_conv1d_step(p, state, x[:, t])
        outs.append(o)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(full), atol=1e-5)


def test_rmsnorm_scale_invariance():
    p = rmsnorm_init(16)
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 16))
    y1 = rmsnorm(p, x)
    y2 = rmsnorm(p, x * 7.3)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)


# ---------------------------------------------------------------- SwiGLU

def _plain_swiglu(params, x):
    g, u = x @ params["w_gate"], x @ params["w_up"]
    return (jax.nn.silu(g) * u) @ params["w_down"]


@pytest.mark.parametrize("case", ["train", "decode", "remat"])
def test_swiglu_matches_the_plain_expression(case):
    """The fenced activation with its hand-written gradient computes what
    autodiff of ``silu(x Wg) * (x Wu) Wd`` computes: the value and the
    gradients of x and of the three weights, in f32."""
    kp, kx, kc = jax.random.split(jax.random.PRNGKey(11), 3)
    d, d_ff = 32, 88
    lead = (1, 1) if case == "decode" else (2, 24)
    params = swiglu_init(kp, d, d_ff)
    x = jax.random.normal(kx, (*lead, d)) * 2.0
    cot = jax.random.normal(kc, (*lead, d))
    fn = swiglu
    if case == "remat":
        fn = jax.checkpoint(
            swiglu, policy=jax.checkpoint_policies.nothing_saveable)

    def loss(f):
        return lambda p, x: jnp.sum(f(p, x) * cot)

    np.testing.assert_allclose(np.asarray(fn(params, x)),
                               np.asarray(_plain_swiglu(params, x)),
                               rtol=1e-6, atol=1e-6)
    got = jax.grad(loss(fn), argnums=(0, 1))(params, x)
    want = jax.grad(loss(_plain_swiglu), argnums=(0, 1))(params, x)
    jax.tree_util.tree_map_with_path(
        lambda path, g, w: np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-5,
            err_msg=jax.tree_util.keystr(path)), got, want)


# ------------------------------------------------------------------- MoE

def _moe_cfg():
    return get_config("deepseek-v2-236b", reduced=True)


def test_moe_capacity_and_combine_weights():
    cfg = _moe_cfg()
    key = jax.random.PRNGKey(6)
    p = moe_mod.moe_init(key, cfg)
    x = jax.random.normal(key, (2, 8, cfg.d_model)) * 0.1
    out, aux = moe_mod.moe_apply(p, cfg, x)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all())
    assert float(aux) >= 0


def test_moe_permutation_equivariance_within_group():
    """Dropless routing: permuting tokens in a group permutes outputs."""
    cfg = _moe_cfg()
    key = jax.random.PRNGKey(7)
    p = moe_mod.moe_init(key, cfg)
    x = jax.random.normal(key, (1, 8, cfg.d_model)) * 0.1
    out, _ = moe_mod.moe_apply(p, cfg, x)
    perm = jnp.asarray([3, 1, 7, 0, 2, 6, 4, 5])
    out_p, _ = moe_mod.moe_apply(p, cfg, x[:, perm])
    np.testing.assert_allclose(np.asarray(out[:, perm]), np.asarray(out_p),
                               atol=1e-4)


def test_moe_grads_flow_to_experts():
    cfg = _moe_cfg()
    key = jax.random.PRNGKey(8)
    p = moe_mod.moe_init(key, cfg)
    x = jax.random.normal(key, (2, 8, cfg.d_model)) * 0.1

    def loss(pp):
        out, aux = moe_mod.moe_apply(pp, cfg, x)
        return jnp.sum(out ** 2) + aux
    g = jax.grad(loss)(p)
    assert float(jnp.abs(g["w_gate"]).max()) > 0
    assert float(jnp.abs(g["router"]).max()) > 0


# --------------------------------------------------------------- analytics

def test_shape_bytes_parser():
    assert shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert shape_bytes("(bf16[8,4], s32[])") == 8 * 4 * 2 + 4
    assert shape_bytes("f32[]") == 4


def test_collective_regex_counts_types():
    hlo = """
  %ag = f32[64,128]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = bf16[32]{0} all-reduce(%y), to_apply=%add
  %rs = f32[16,8]{1,0} reduce-scatter(%z), dimensions={0}
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 64 * 128 * 4
    assert out["all-reduce"] == 32 * 2 * 2
    assert out["reduce-scatter"] == 16 * 8 * 4


@given(dims=st.lists(st.integers(1, 64), min_size=0, max_size=4))
@settings(max_examples=20, deadline=None)
def test_shape_elems_bytes_property(dims):
    s = "f32[" + ",".join(map(str, dims)) + "]"
    elems, nbytes = _shape_elems_bytes(s)
    expect = int(np.prod(dims)) if dims else 1
    assert elems == expect and nbytes == 4 * expect


def test_param_count_analytics_match_actual():
    """ModelConfig.n_params() tracks the real initialized tree within 10%."""
    import jax
    from repro.models import build_model
    for arch in ["deepseek-7b", "mamba2-780m"]:
        cfg = get_config(arch, reduced=True)
        m = build_model(cfg)
        params = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))
        actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        est = cfg.n_params()
        assert 0.6 < est / actual < 1.6, (arch, est, actual)
