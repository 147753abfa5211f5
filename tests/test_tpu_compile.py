"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Nothing here runs on a chip.  The TPU compiler, which is installed beside
jax, compiles each kernel at real widths for a *described* v5e: it refuses
what the chip's compiler would refuse (block shapes that break the
(8, 128) tiling, layouts Mosaic cannot match, DMAs of partial tiles) — all
of which interpret mode on the CPU lets through.  Each case asserts the
compiled module holds the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.act_compress import compress, decompress
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.vb_scatter import scatter_rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one:
    keep these out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiles_with_kernel(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def _spec(sharding):
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


# deepseek-7b decode: MHA, 32 heads of 128; 8 sequences of up to 34 pages
# of 16 tokens (prompt 512 + 32 new).  deepseek-v2 MLA: 128 heads over one
# fused c_kv ‖ k_rope pool of width 512 + 64, values the 512-wide prefix.
PAGED_CASES = {
    "deepseek-7b-mha": dict(H=32, KV=32, d=128, v_width=0),
    "deepseek-v2-mla": dict(H=128, KV=1, d=576, v_width=512),
}


@pytest.mark.parametrize("case", sorted(PAGED_CASES))
def test_paged_decode_attention_compiles(one_chip, case):
    c = PAGED_CASES[case]
    B, page, maxp = 8, 16, 34
    pages = B * maxp + 1
    S = _spec(one_chip)
    pool = S((pages, c["KV"], page, c["d"]))
    q, bt, lens = (S((B, c["H"], c["d"])), S((B, maxp), jnp.int32),
                   S((B,), jnp.int32))
    if c["v_width"]:                      # MLA: V is the fused pool's prefix
        _compiles_with_kernel(
            lambda q, k, bt, lens: paged_decode_attention(
                q, k, None, bt, lens, scale=0.088, v_width=c["v_width"],
                interpret=False),
            q, pool, bt, lens)
    else:
        _compiles_with_kernel(
            lambda q, k, v, bt, lens: paged_decode_attention(
                q, k, v, bt, lens, scale=0.088, interpret=False),
            q, pool, pool, bt, lens)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("direction", ["forward", "vjp"])
def test_vb_scatter_compiles(one_chip, dtype, direction):
    S = _spec(one_chip)
    N, D = 64, 4096
    perm, x = S((N,), jnp.int32), S((N, D), dtype)

    def forward(p, x):
        return scatter_rows(p, (x,), interpret=False)[0]

    if direction == "forward":
        _compiles_with_kernel(forward, perm, x)
    else:
        _compiles_with_kernel(
            lambda p, x, g: jax.vjp(lambda x: forward(p, x), x)[1](g),
            perm, x, S((N, D), dtype))


@pytest.mark.parametrize("codec", ["int8", "fp8"])
def test_act_compress_compiles(one_chip, codec):
    S = _spec(one_chip)
    x = S((1024, 4096))

    def roundtrip(x):
        payload = compress(x, codec=codec, interpret=False)
        return decompress(payload, x.shape, interpret=False)

    _compiles_with_kernel(roundtrip, x)
