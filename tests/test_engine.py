"""Unified training-engine coverage (``repro.launch.engine``).

* Pipelined-vs-serial equivalence on the production pjit path: the 2-deep
  host->device prefetch queue is a pure transfer-timing reordering, so
  ``Engine(pipeline=True)`` must match the strictly batch-serial jit path
  to float32 ULP over >=4 steps — on the (2,2) debug mesh, the forced-8-
  device CPU host mesh, and a multi-pod-axes (pod, data, model) smoke cell.
* Roofline check: the sharded step's measured collective bytes (via
  ``repro.analysis.hlo_flops``) sit inside the band of
  ``predict_train_collective_bytes``'s no-CSE upper bound, and a (1,1)
  mesh measures exactly zero.
* CLI smoke: ``python -m repro.launch.train --steps 3 --mesh debug`` runs
  green (fast tier — the production entrypoint can never silently rot).
* Sim facade: ``Engine(mode="sim")`` reproduces the orchestrator paths.

Sharded cells run in subprocesses so the forced host-device count never
leaks into other tests.
"""
import json
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_ENV_BASE = dict(os.environ, PYTHONPATH=os.path.abspath("src"),
                 XLA_FLAGS="--xla_force_host_platform_device_count=8")

EQUIV_SCRIPT = textwrap.dedent("""
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.data.pipeline import (VirtualBatchLoader, shard_corpus,
                                     synthetic_corpus)
    from repro.launch.engine import Engine
    from repro.launch.mesh import (make_debug_mesh, make_host_mesh,
                                   make_multipod_debug_mesh)
    from repro.models import build_model
    from repro.optim import adamw

    mesh = {"debug": lambda: make_debug_mesh(2, 2),
            "host": make_host_mesh,
            "multipod": make_multipod_debug_mesh}[os.environ["TEST_MESH"]]()
    cfg = get_config("deepseek-7b", reduced=True)
    model = build_model(cfg)
    B, S, STEPS = 8, 32, 4
    shape = InputShape("t", S, B, "train")

    def run(pipeline):
        docs = synthetic_corpus(4 * 16, S, cfg.vocab_size, seed=1)
        loader = VirtualBatchLoader(shard_corpus(docs, 4), B, seed=0)
        eng = Engine(model, cfg, adamw(3e-3, clip_norm=1.0), mesh, shape,
                     pipeline=pipeline)
        eng.init(jax.random.PRNGKey(0))
        res = eng.run(loader, steps=STEPS)
        return res

    a, b = run(True), run(False)
    assert a.steps == b.steps == STEPS
    eps = np.finfo(np.float32).eps
    worst = 0.0
    for pa, pb in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        x = np.asarray(pa, np.float64)
        y = np.asarray(pb, np.float64)
        tol = 16 * eps * max(1.0, float(np.abs(x).max()))
        worst = max(worst, float(np.abs(x - y).max()) / tol)
    print("RESULT", json.dumps({
        "ulp_excess": worst,
        "loss_diff": float(np.abs(a.losses - b.losses).max()),
        "mesh_axes": list(mesh.axis_names)}))
""")


@pytest.mark.parametrize("mesh_kind", ["debug", "host", "multipod"])
def test_engine_pipelined_matches_serial(mesh_kind):
    """Engine(pipeline=True) == serial jit path to float32 ULP, per mesh.

    ``debug`` is the (2,2) debug mesh, ``host`` the forced-8-device CPU
    mesh, ``multipod`` the smallest (pod, data, model) mesh — the composite
    (pod, data) batch-axis smoke cell."""
    env = dict(_ENV_BASE, TEST_MESH=mesh_kind)
    proc = subprocess.run([sys.executable, "-c", EQUIV_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    data = json.loads(line.split("RESULT ")[1])
    assert data["ulp_excess"] <= 1.0, data
    assert data["loss_diff"] < 1e-6, data
    if mesh_kind == "multipod":
        assert data["mesh_axes"] == ["pod", "data", "model"]


REASSEMBLY_SCRIPT = textwrap.dedent("""
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.data.pipeline import (VirtualBatchLoader, shard_corpus,
                                     synthetic_corpus)
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_debug_mesh
    from repro.models import build_model
    from repro.optim import adamw

    mesh = make_debug_mesh(2, 2)              # data axis of 2: sharded perms
    cfg = get_config("deepseek-7b", reduced=True)
    model = build_model(cfg)
    B, S, STEPS = 8, 32, 4
    shape = InputShape("t", S, B, "train")

    def run(reassembly, pipeline):
        docs = synthetic_corpus(4 * 16, S, cfg.vocab_size, seed=1)
        loader = VirtualBatchLoader(shard_corpus(docs, 4), B, seed=0)
        eng = Engine(model, cfg, adamw(3e-3, clip_norm=1.0), mesh, shape,
                     pipeline=pipeline, reassembly=reassembly)
        eng.init(jax.random.PRNGKey(0))
        return eng.run(loader, steps=STEPS)

    a = run("xla", False)
    b = run("pallas", False)
    c = run("pallas", True)
    eps = np.finfo(np.float32).eps
    def ulp_excess(t1, t2):
        worst = 0.0
        for pa, pb in zip(jax.tree.leaves(t1.params),
                          jax.tree.leaves(t2.params)):
            x = np.asarray(pa, np.float64)
            y = np.asarray(pb, np.float64)
            tol = 16 * eps * max(1.0, float(np.abs(x).max()))
            worst = max(worst, float(np.abs(x - y).max()) / tol)
        return worst
    print("RESULT", json.dumps({
        "xla_vs_pallas": ulp_excess(a, b),
        "pallas_serial_vs_pipelined": ulp_excess(b, c),
        "loss_diff": float(np.abs(a.losses - b.losses).max())}))
""")


def test_engine_pallas_reassembly_matches_xla_sharded():
    """Production acceptance: on a mesh whose data axis shards the batch,
    the shard_map'd pallas reassembly matches the XLA-scatter path to
    float32 ULP over 4 steps (in practice bit-identically), and stays
    pipeline-invariant."""
    proc = subprocess.run([sys.executable, "-c", REASSEMBLY_SCRIPT],
                          env=_ENV_BASE, capture_output=True, text=True,
                          timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    data = json.loads(line.split("RESULT ")[1])
    assert data["xla_vs_pallas"] <= 1.0, data
    assert data["pallas_serial_vs_pipelined"] <= 1.0, data
    assert data["loss_diff"] < 1e-6, data


ROOFLINE_SCRIPT = textwrap.dedent("""
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro.analysis.hlo_flops import analyze
    from repro.analysis.roofline import predict_train_collective_bytes
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.core.tl_step import make_train_step, train_shardings
    from repro.launch.mesh import make_debug_mesh
    from repro.models import build_model
    from repro.optim import sgd

    cfg = get_config("deepseek-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = sgd(0.1)
    st = opt.init(params)
    B, S = 8, 32
    shape = InputShape("t", S, B, "train")
    step = make_train_step(model, cfg, opt)
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "targets": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    out = {}
    for name, mesh in [("debug22", make_debug_mesh(2, 2)),
                       ("debug11", make_debug_mesh(1, 1))]:
        with mesh:
            in_sh, out_sh = train_shardings(params, st, cfg, mesh, shape)
            hlo = jax.jit(step, in_shardings=in_sh,
                          out_shardings=out_sh).lower(
                params, st, batch).compile().as_text()
        costs = analyze(hlo)
        pred = predict_train_collective_bytes(cfg, shape, mesh, params)
        out[name] = {"measured": float(costs.coll_total),
                     "predicted": float(pred["total"]),
                     "breakdown": {k: float(v) for k, v in costs.coll.items()}}
    print("RESULT", json.dumps(out))
""")


def test_sharded_step_collective_bytes_match_roofline_model():
    """ROADMAP item: measure the sharded step's collective bytes against the
    roofline model.  The prediction is a no-CSE all-reduce upper bound, so
    the measurement must land in [predicted/4, 1.5x predicted] on the (2,2)
    debug mesh; the (1,1) mesh must predict and measure exactly zero."""
    proc = subprocess.run([sys.executable, "-c", ROOFLINE_SCRIPT],
                          env=_ENV_BASE, capture_output=True, text=True,
                          timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    data = json.loads(line.split("RESULT ")[1])

    sharded = data["debug22"]
    assert sharded["predicted"] > 0
    ratio = sharded["measured"] / sharded["predicted"]
    assert 0.25 <= ratio <= 1.5, data
    # FSDP gathers + the data-axis gradient psum must both be present
    assert sharded["breakdown"].get("all-gather", 0) > 0, data
    assert sharded["breakdown"].get("all-reduce", 0) \
        + sharded["breakdown"].get("reduce-scatter", 0) > 0, data

    degenerate = data["debug11"]
    assert degenerate["predicted"] == 0
    assert degenerate["measured"] == 0, data


def test_train_cli_smoke():
    """The production entrypoint itself (module __main__, not a helper) runs
    3 steps green on the debug mesh — fast tier, no --runslow."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--steps", "3",
         "--mesh", "debug", "--nodes", "2", "--batch", "4", "--seq", "32"],
        env=_ENV_BASE, capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "final loss" in proc.stdout
    assert re.search(r"input wait \d+\.\d{3} s of \d+\.\d{3} s", proc.stdout)
    assert "mesh=debug(2, 2)" in proc.stdout     # 8 forced devices -> (2,2)


# ---------------------------------------------------------------- in-process


def _sim_shards(sizes, seed=5):
    from repro.core.baselines import ShardData
    from repro.configs.paper_models import DATRET
    r = np.random.default_rng(seed)
    return [ShardData(
        r.normal(size=(n,) + DATRET.in_shape).astype(np.float32),
        r.integers(0, DATRET.n_classes, n)) for n in sizes]


@pytest.mark.parametrize("pipeline", [False, True],
                         ids=["sim-serial", "sim-pipelined"])
def test_engine_sim_facade_matches_orchestrator(pipeline):
    """mode="sim" is a faithful facade: same params as driving the
    TLOrchestrator directly with the matching pipelined flag."""
    import jax
    from repro.configs.paper_models import DATRET
    from repro.core.node import TLNode
    from repro.core.orchestrator import TLOrchestrator
    from repro.core.plan import PlanSpec
    from repro.core.transport import Transport
    from repro.launch.engine import Engine
    from repro.models.small import SmallModel
    from repro.optim import sgd

    shards = _sim_shards([20, 12])
    model = SmallModel(DATRET)

    eng = Engine(model, DATRET, sgd(0.05), mode="sim", pipeline=pipeline,
                 batch_size=16, seed=0)
    res = eng.run(shards, epochs=2)

    nodes = [TLNode(i, model, s.x, s.y) for i, s in enumerate(shards)]
    orch = TLOrchestrator(model, nodes, sgd(0.05), Transport(),
                          batch_size=16, plan=PlanSpec(seed=0),
                          pipelined=pipeline)
    orch.initialize(jax.random.PRNGKey(0))
    ref = [s for _ in range(2) for s in orch.train_epoch()]

    for pa, pb in zip(jax.tree.leaves(res.params), jax.tree.leaves(orch.params)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
    assert res.steps == len(ref)
    np.testing.assert_allclose(res.losses, [s.loss for s in ref], rtol=1e-6)
    assert len(res.epoch_stats) == 2


def test_engine_prefetch_is_double_buffered():
    """The producer thread fills the prefetch queue up to PREFETCH_DEPTH
    ahead of the consumer (and never further), preserves order, and runs
    off the consumer's thread.  Only scheduling-independent invariants are
    asserted — the slot semaphore upper-bounds the lookahead, it does not
    pin an exact interleaving."""
    import threading
    import time

    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_debug_mesh
    from repro.optim import sgd

    cfg = get_config("deepseek-7b", reduced=True)
    eng = Engine(object(), cfg, sgd(0.1), make_debug_mesh(1, 1),
                 InputShape("t", 8, 4, "train"))
    events = []        # (kind, item, thread_ident); appends are GIL-atomic
    eng._put_batch = lambda hb: (
        events.append(("put", hb, threading.get_ident())), hb)[1]

    gen = eng._device_batches(iter(range(6)))
    first = next(gen)
    assert first == 0
    # with the consumer idle, the producer must fill the whole double
    # buffer on its own: item 0 is held by the consumer (slot unreleased),
    # item 1 materializes behind it — and nothing beyond PREFETCH_DEPTH
    deadline = time.monotonic() + 10.0
    while sum(e[0] == "put" for e in events) < 2:
        assert time.monotonic() < deadline, events
        time.sleep(0.001)
    time.sleep(0.05)   # give an (incorrect) over-eager producer rope
    puts_before_consume = [e[1] for e in events if e[0] == "put"]
    assert puts_before_consume == list(range(Engine.PREFETCH_DEPTH))

    events.append(("yield", first, threading.get_ident()))
    seen = [first]
    for item in gen:
        events.append(("yield", item, threading.get_ident()))
        seen.append(item)
    assert seen == list(range(6))

    # puts happen on the producer thread, not the consumer's
    consumer = threading.get_ident()
    assert all(t != consumer for k, _, t in events if k == "put")
    # at every prefix, materialized-ahead batches never exceed the depth:
    # put k+PREFETCH_DEPTH is gated on the consumer finishing item k
    outstanding = 0
    for kind, _, _ in events:
        outstanding += 1 if kind == "put" else -1
        assert outstanding <= Engine.PREFETCH_DEPTH, events


def test_engine_step_compiles_once():
    """The first step sees freshly initialized (uncommitted) state, every
    later step the step's own committed outputs.  The engine commits the
    state to the step's shardings before the first step, so both hit one
    executable: a second run (and every step after the first) compiles
    nothing."""
    import jax

    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.data.pipeline import (VirtualBatchLoader, shard_corpus,
                                     synthetic_corpus)
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_debug_mesh
    from repro.models import build_model
    from repro.optim import adamw

    cfg = get_config("deepseek-7b", reduced=True)
    docs = synthetic_corpus(4 * 16, 16, cfg.vocab_size, seed=1)
    loader = VirtualBatchLoader(shard_corpus(docs, 4), 8, seed=0)
    eng = Engine(build_model(cfg), cfg, adamw(3e-3, clip_norm=1.0),
                 make_debug_mesh(1, 1), InputShape("t", 16, 8, "train"),
                 pipeline=False, reassembly="pallas")
    eng.init(jax.random.PRNGKey(0))
    compiles = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    eng.run(loader, steps=1)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        res = eng.run(loader, steps=3)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert res.steps == 3
    assert compiles == []


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["pipelined", "serial"])
def test_engine_host_spans(tmp_path, pipeline):
    """A profiled 3-step production run carries the engine's host spans,
    nested as ``Engine._production_pass`` documents them, in the compact
    trace the benchmark reads; the input-wait counter lies within the
    run; and the spans change no arithmetic: the profiled run's losses
    equal an unprofiled run's on the other path, bit for bit."""
    import jax

    from bench.lib import scopes
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.data.pipeline import (VirtualBatchLoader, shard_corpus,
                                     synthetic_corpus)
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_debug_mesh
    from repro.models import build_model
    from repro.optim import adamw

    cfg = get_config("deepseek-7b", reduced=True)
    model = build_model(cfg)
    docs = synthetic_corpus(4 * 16, 16, cfg.vocab_size, seed=1)

    def run(pipe):
        eng = Engine(model, cfg, adamw(3e-3), make_debug_mesh(1, 1),
                     InputShape("t", 16, 8, "train"), pipeline=pipe,
                     reassembly="pallas")
        eng.init(jax.random.PRNGKey(0))
        loader = VirtualBatchLoader(shard_corpus(docs, 4), 8, seed=0)
        return eng, loader

    eng, loader = run(pipeline)
    eng.run(loader, steps=1)               # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = eng.run(loader, steps=3)
    finally:
        jax.profiler.stop_trace()
    assert res.steps == 3
    assert 0 <= res.input_wait_s <= res.wall_s

    spans = {}
    for nm, s, d in scopes.extract(str(tmp_path))["host"]:
        if nm.startswith("tl_"):
            spans.setdefault(nm, []).append((s, s + d))
    (run_lo, run_hi), = spans["tl_run"]
    assert len(spans["tl_step"]) == 3
    assert len(spans["tl_put_batch"]) == 3
    assert len(spans["tl_input_wait"]) == 4    # three batches, then the end
    assert len(spans["tl_sync"]) == 1
    for nm, iv in spans.items():
        assert all(run_lo <= s and e <= run_hi for s, e in iv), nm
    # the loop waits for a batch, then dispatches it; the sync comes last
    waits, steps = sorted(spans["tl_input_wait"]), sorted(spans["tl_step"])
    for (_, w_end), (s_lo, s_hi), (w_next, _) in zip(waits, steps,
                                                      waits[1:]):
        assert w_end <= s_lo and s_hi <= w_next
    assert steps[-1][1] <= spans["tl_sync"][0][0]
    if not pipeline:
        # the serial path loads and puts each batch inside its wait
        for s, e in spans["tl_put_batch"]:
            assert any(a <= s and e <= b for a, b in waits)

    other, other_loader = run(not pipeline)
    other.run(other_loader, steps=1)
    np.testing.assert_array_equal(res.losses,
                                  other.run(other_loader, steps=3).losses)
