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
import re

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


# Instructions that move or name data and compute nothing; the scope check
# below leaves them out.  Instructions the compiler adds on its own (layout
# iotas and pads, ConcatBitcast of sliced stacks) carry no op_name at all.
HLO_EXEMPT = {"parameter", "constant", "tuple", "get-tuple-element",
              "bitcast", "copy", "copy-start", "copy-done", "slice-start",
              "slice-done"}
TL_SCOPES = {"tl_node", "tl_reassembly", "tl_tail", "tl_loss",
             "tl_optimizer"}


def _computations(text):
    """{computation name: its instruction lines} of an HLO module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and line.strip().startswith(("%", "ROOT")):
            cur.append(line.strip())
    return comps


def _called(line):
    return re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", line)


def _device_ops(text):
    """(instruction line, opcode, op_name) of every instruction the device
    runs as an op of its own: those of the entry computation and of the
    computations its control flow calls, not of fused or applied ones."""
    comps = _computations(text)
    inner = {c for lines in comps.values() for ln in lines
             for c in _called(ln)}
    out = []
    for c, lines in comps.items():
        if c in inner:
            continue
        for ln in lines:
            rest = ln.split(" = ", 1)[1]
            if rest.startswith("("):          # a tuple shape: skip it whole
                depth = 0
                for i, ch in enumerate(rest):
                    depth += (ch == "(") - (ch == ")")
                    if depth == 0:
                        break
                rest = rest[i + 1:]
            else:
                rest = rest.split(" ", 1)[1]
            opcode = re.match(r"\s*([\w\-]+)", rest).group(1)
            name = re.search(r'op_name="([^"]*)"', ln)
            out.append((ln, opcode, name.group(1) if name else None))
    return out


def test_tl_step_phases_are_scoped(one_chip, monkeypatch):
    """The production TL step, compiled at small widths with the Pallas
    reassembly, names its phases for the benchmark's trace reduction:
    every op the program traced carries exactly one TL scope, the
    reassembly kernel's custom calls still match the reader that finds
    them, and the tail appears both recomputed and transposed."""
    from bench.lib import spec
    from bench.lib.scopes import PHASE_RE, phase_of
    from bench.lib.trace import op_name
    from repro.configs import get_config
    from repro.core.tl_step import make_train_step
    from repro.models import build_model
    from repro.optim import adamw

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    cfg = get_config("deepseek-7b", reduced=True)
    model, opt = build_model(cfg), adamw(3e-3)
    step = make_train_step(model, cfg, opt, reassembly="pallas")
    S = _spec(one_chip)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(opt.init, params)
    B, T = 8, 128
    batch = {"tokens": S((B, T), jnp.int32), "targets": S((B, T), jnp.int32),
             "perm": S((B,), jnp.int32)}
    text = jax.jit(step, donate_argnums=(0, 1)).lower(
        jax.tree.map(lambda a: S(a.shape, a.dtype), params),
        jax.tree.map(lambda a: S(a.shape, a.dtype), state),
        batch).compile().as_text()

    ops = _device_ops(text)
    seen = set()
    for line, opcode, path in ops:
        if opcode in HLO_EXEMPT or path is None:
            continue
        scopes = set(PHASE_RE.findall(path))
        assert len(scopes) == 1 and scopes <= TL_SCOPES, line[:300]
        seen.add(phase_of(path))
    assert {p for p, _ in seen} == TL_SCOPES
    assert {("tl_tail", "recompute"), ("tl_tail", "bwd")} <= seen

    pattern = spec.load_module("vb_scatter_roofline").KERNELS["vb_scatter"][0]
    calls = [op_name(line) for line, _, _ in ops if "tpu_custom_call" in line]
    assert len(calls) == 2, calls      # the forward scatter, its transpose
    assert all(re.search(pattern, c) for c in calls), calls


def _matmul_fusions_with_exp(text):
    """Fused computations that hold a convolution (the TPU's matmul) and,
    in themselves or in what they call, an exponential."""
    comps = _computations(text)

    def body(name, seen):
        if name not in seen:
            seen.add(name)
            for ln in comps[name]:
                for c in _called(ln):
                    body(c, seen)
        return seen

    return [name for name, lines in comps.items()
            if any(" convolution(" in ln for ln in lines)
            and any(" exponential(" in ln for c in body(name, set())
                    for ln in comps[c])]


def test_swiglu_keeps_its_activation_out_of_the_matmuls(one_chip):
    """``grad`` of RMSNorm -> SwiGLU -> residual at deepseek-7b's MLP widths:
    no matmul fusion re-evaluates silu or its derivative in its prologue.
    The same graph written as the plain expression does, so the check is
    live."""
    from repro.models.layers import rmsnorm, swiglu

    def plain(params, x):
        g, u = x @ params["w_gate"], x @ params["w_up"]
        return (jax.nn.silu(g) * u) @ params["w_down"]

    def grad_of(mlp):
        def loss(p, x):
            y = x + mlp(p["ffn"], rmsnorm(p["norm"], x))
            return jnp.sum(y * y)
        return jax.grad(loss)

    S = _spec(one_chip)
    d, d_ff = 4096, 11008
    params = {"norm": {"scale": S((d,))},
              "ffn": {"w_gate": S((d, d_ff)), "w_up": S((d, d_ff)),
                      "w_down": S((d_ff, d))}}
    x = S((2, 128, d))
    texts = {name: jax.jit(grad_of(mlp)).lower(params, x).compile().as_text()
             for name, mlp in (("plain", plain), ("swiglu", swiglu))}
    assert _matmul_fusions_with_exp(texts["plain"])
    assert " convolution(" in texts["swiglu"]
    assert _matmul_fusions_with_exp(texts["swiglu"]) == []


def _collectives(text):
    """(instruction line, result shapes, op_name) of every device op that
    exchanges data between chips: a collective instruction, or a fusion
    that holds one (the TPU compiler's async and reduce-scatter fusions).
    The shapes are those of the collectives, where a fusion holds them."""
    comps = _computations(text)
    found = re.compile(r"= (\S+) (all-gather|all-reduce|reduce-scatter|"
                       r"collective-permute|all-to-all)(?:-start|-done)?\(")

    def inside(name, seen):
        if name in seen:
            return []
        seen.add(name)
        out = []
        for ln in comps.get(name, ()):
            m = found.search(ln)
            if m:
                out.append((m.group(2), m.group(1)))
            for c in _called(ln):
                out += inside(c, seen)
        return out

    out = []
    for line, _, path in _device_ops(text):
        m = found.search(line)
        held = [(m.group(2), m.group(1))] if m else []
        for c in _called(line):
            held += inside(c, set())
        if held:
            out.append((line, held, path))
    return out


def _scope_paths(text):
    """(upstream, downstream): functions from an instruction's name to the
    op_names it stands for.  Its own, where it has one; else, for one the
    compiler added with none (the start of an async collective, a permute
    that realigns a reduce-scatter's rows, and the slices and tuple reads
    around them), those of the nearest named instructions it reads from
    (upstream) or that read from it (downstream)."""
    rest, users = {}, {}
    for body in _computations(text).values():
        for ln in body:
            name, _, r = ln.removeprefix("ROOT ").partition(" = ")
            name = name.lstrip("%")
            rest[name] = r
            # the operands: the %names before the first attribute
            for arg in set(re.findall(r"%([\w.\-]+)", r.split("), ", 1)[0])):
                users.setdefault(arg, []).append(name)
    operands = {n: re.findall(r"%([\w.\-]+)", r.split("), ", 1)[0])
                for n, r in rest.items()}

    def walk(edges):
        memo = {}

        def paths(name, seen=frozenset()):
            if name not in memo:
                own = re.search(r'op_name="([^"]*)"', rest.get(name, ""))
                memo[name] = {own.group(1)} if own else set().union(*(
                    paths(n, seen | {name}) for n in edges.get(name, ())
                    if n not in seen))
            return memo[name]
        return paths
    return walk(operands), walk(users)


def test_ds67b_stage_fits_keeps_gqa_local_and_scopes_collectives(topo):
    """A 2-layer stage of DeepSeek LLM 67B at its published widths (64 query
    heads over 8 KV heads, d_ff 22 016), vocabulary cut to 12 800, trained
    as the engine jits its step: global batch 16 x 1024, the Pallas
    reassembly, AdamW, on a (2, 2) data x model mesh of a described v5e
    host.  It fits a chip (arguments and temporaries under 15 GiB of 16),
    no all-gather or all-to-all moves q, k or v (each KV head's 8 query
    heads stay on the chip that holds it), and every collective carries the
    scope of one TL phase: its own op_name's, or, where the compiler added
    it with none, that of the data it moves."""
    import dataclasses
    import numpy as np
    from jax.sharding import Mesh
    from bench.lib.scopes import PHASE_RE
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.core.tl_step import make_train_step, train_shardings
    from repro.models import build_model
    from repro.optim import adamw

    cfg = dataclasses.replace(get_config("deepseek-67b"), n_layers=2,
                              vocab_size=12800)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    model = build_model(cfg)
    opt = adamw(3.2e-4, weight_decay=0.1, b1=0.9, b2=0.95, eps=1e-8)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(opt.init, params)
    B, S = 16, 1024
    with mesh:
        in_sh, out_sh = train_shardings(params, state, cfg, mesh,
                                        InputShape("ds67b", S, B, "train"),
                                        with_perm=True)
    shapes = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        (params, state), in_sh[:2])
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "targets": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "perm": jax.ShapeDtypeStruct((B,), jnp.int32)}
    step = make_train_step(model, cfg, opt, reassembly="pallas", mesh=mesh)
    compiled = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=(0, 1)).lower(*shapes, batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15 * 2 ** 30

    text = compiled.as_text()
    colls = _collectives(text)
    upstream, downstream = _scope_paths(text)
    kinds = {k for _, held, _ in colls for k, _ in held}
    assert {"all-gather", "all-reduce"} <= kinds, kinds
    hd = cfg.resolved_head_dim
    qkv = re.compile(rf"\[\d+,{S},\d+,{hd}\]")      # (rows, S, heads, hd)
    for line, held, path in colls:
        for kind, shape in held:
            assert not (kind in ("all-gather", "all-to-all")
                        and qkv.search(shape)), line[:300]
        # what the compiler adds on its own (the start of an async
        # collective, a permute that realigns a reduce-scatter's rows) has
        # no op_name: it takes the scope of the phase whose data it moves,
        # or, for a weight gathered ahead of its phase, of the phase that
        # reads what it gathered
        name = line.split(" = ", 1)[0].lstrip("%")
        scopes = set(PHASE_RE.findall(path)) if path is not None else set()
        for paths in (upstream, downstream):
            if path is None and not scopes:
                scopes = {s for pt in paths(name)
                          for s in PHASE_RE.findall(pt)}
        assert len(scopes) == 1 and scopes <= TL_SCOPES, line[:300]
