"""Pallas TPU kernels for the compute hot-spots of the assigned architectures.

Each kernel ships as a package: ``kernel.py`` (pl.pallas_call + BlockSpec
VMEM tiling), ``ops.py`` (jit'd public wrapper), ``ref.py`` (pure-jnp
oracle).  BlockSpecs are written for TPU v5e VMEM.

Packages:
  flash_attention — causal/windowed flash attention (GQA), §node-phase FP
  ssd             — Mamba-2 state-space duality chunked scan
  rglru           — RecurrentGemma RG-LRU chunked scan
  act_compress    — per-row absmax int8 wire compression (paper §5.2)
  vb_scatter      — differentiable virtual-batch reassembly: the TL
                    orchestrator's ``out[perm[i]] = payload[i]`` scatter of
                    X^(1)/δ^(L)/∂L∂X^(1) as one multi-ref row-gather pass
                    (custom_vjp; backward is the inverse gather), replacing
                    XLA's generic scatter lowering on the fused-step and
                    production-reassembly hot paths
  paged_attention — paged-KV decode attention for the serving engine: block
                    tables + lengths ride the same scalar-prefetch routing
                    as vb_scatter so K/V BlockSpecs DMA pages straight from
                    the shared pool; online-softmax over pages (flash-style)
                    with an MLA fused-pool mode (V = latent prefix of K)

Interpret mode is resolved process-wide by :func:`resolve_interpret`: the
``REPRO_PALLAS_INTERPRET`` env var (``1``/``0``) overrides, else kernels
interpret on CPU backends and lower for real on TPU hosts.  The tests run
interpreted on the CPU; ``tests/test_tpu_compile.py`` compiles the
main-path kernels for a described v5e, and ``chip_smoke.py`` runs them on
the chip (refusing to start if they would be interpreted).
"""
import os


def resolve_interpret(interpret=None) -> bool:
    """Resolve a kernel's Pallas interpret-mode flag.

    Explicit ``interpret=`` wins; else ``REPRO_PALLAS_INTERPRET`` (truthy
    strings enable, ``0``/``false``/``off`` disable); else interpret on CPU
    backends only.  Read at trace time — jitted wrappers resolve *before*
    their jit boundary so an env change takes effect on the next call, not
    the next process.
    """
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env.strip().lower() not in ("0", "false", "off", "no", "")
    import jax
    return jax.default_backend() == "cpu"
