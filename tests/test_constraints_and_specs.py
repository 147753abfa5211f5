"""dist.constraints behaviour + launch.specs shapes."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, get_shape
from repro.dist.constraints import (activation_sharding, constrain_batch,
                                    set_activation_mesh)
from repro.launch.specs import input_specs, text_len


def test_constrain_noop_without_mesh():
    set_activation_mesh(None)
    x = jnp.ones((4, 8))
    assert constrain_batch(x) is x


def test_activation_sharding_context_restores():
    set_activation_mesh(None)
    with activation_sharding(("data",)):
        pass
    x = jnp.ones((4, 8))
    assert constrain_batch(x) is x     # restored to None


def test_constraint_lowers_inside_jit():
    """with_sharding_constraint must trace under a (1-device) mesh."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    with activation_sharding(("data",)):
        with mesh:
            out = jax.jit(lambda x: constrain_batch(x) * 2)(jnp.ones((2, 3)))
    assert out.shape == (2, 3)


@pytest.mark.parametrize("shape_name", ["train_4k", "prefill_32k",
                                        "decode_32k", "long_500k"])
def test_input_specs_shapes(shape_name):
    shape = get_shape(shape_name)
    for arch in ["deepseek-7b", "qwen2-vl-72b", "seamless-m4t-medium"]:
        cfg = get_config(arch)
        specs = input_specs(cfg, shape)
        if shape.kind == "train":
            B, S = specs["tokens"].shape
            assert B == shape.global_batch
            total = S + (cfg.frontend_tokens if (cfg.frontend and
                                                 not cfg.is_encdec) else 0)
            assert total == shape.seq_len
            if cfg.frontend:
                assert specs["embeds"].shape == (B, cfg.frontend_tokens,
                                                 cfg.d_model)
        elif shape.kind == "decode":
            assert specs["token"].shape == (shape.global_batch,)
            assert specs["cache_len"].shape == ()


def test_vlm_text_len_accounts_frontend():
    cfg = get_config("qwen2-vl-72b")
    assert text_len(cfg, get_shape("train_4k")) == 4096 - 256
    enc = get_config("seamless-m4t-medium")
    assert text_len(enc, get_shape("train_4k")) == 4096   # enc-dec: decoder full len
