"""TL step-time benchmark: eager reference vs fused vs pipelined hot path.

Measures steps/sec of the protocol simulator's full TL round (model
redistribution + node visits + centralized BP + update) at 2/4/8 simulated
nodes, for

* ``eager`` — the seed's op-by-op path: unjitted node visits, per-node
  ``.at[].set`` scatters, an un-jitted tail vjp per virtual batch, host
  syncs inside every visit;
* ``fused`` — jitted node visits with device-resident stats, one batched
  scatter reassembly, and a single compiled (donated) vjp+update step;
* ``pipelined`` — the fused path driven by the double-buffered epoch engine
  (``repro.core.pipeline``): batch k+1's visits produced while batch k's
  centralized BP consumes;
* ``reassembly`` — the fused path's virtual-batch reassembly strategy:
  ``xla`` (one generic ``.at[perm].set`` scatter per payload tensor, the
  fused column above) vs ``pallas`` (the fused ``repro.kernels.vb_scatter``
  row-routing kernel — one launch, one HBM pass).  On this CPU container
  the kernel runs in interpret mode, so the wall-clock column is a
  correctness-under-load signal, not the TPU speedup; the HBM-byte claim
  is asserted analytically (``predict_reassembly_hbm_bytes`` + the HLO
  scatter accounting in ``tests/test_analysis.py``).

Pipelining is a *clock* optimization in the protocol simulator, so besides
wall-clock steps/sec the benchmark runs a simulated-time epoch (nonzero node
compute + centralized-BP cost on a WAN network model) serial vs pipelined
and records ``Transport.clock_s`` for each — the measurable counterpart of
runtime_model's eq. 19 pipelined form.  The clock columns are the
headline signal: the steps/sec columns share one process's executable
caches (later configurations run warmer), so cross-column wall-clock
ratios carry cache noise the simulated clock does not.

Since PR 3 the full run also measures the *production* path (subprocess
with 8 forced host devices, the (4, 2) host mesh):

* ``production_dryrun`` — the pjit TL step exactly as ``repro.launch.
  engine`` jits it (train_shardings in/out, remat-from-X^(1)) at a scaled
  production shape: compile time, measured CPU step time, and the
  roofline-projected v5e step time from the compiled HLO's FLOPs / HBM /
  collective bytes (the open ROADMAP "production-shape dryrun" column);
* ``engine_clock`` — serial (strictly batch-serial, the historical driver
  semantics) vs pipelined (2-deep host->device prefetch) engine wall-clock
  over the same compiled step at 2/4/8 logical nodes — the device-path
  counterpart of the simulator's ``clock_s`` columns;
* ``elastic_recovery`` — the elastic engine's full detect -> reshrink ->
  restore -> re-jit -> replay recovery wall-clock (a scripted chip kill at
  step 3) at 2/4/8 simulated devices, at rollback depth 1 (``ckpt_every=2``)
  vs depth 3 (``ckpt_every=4``, only the step-0 anchor behind the kill) —
  the measured counterpart of ``runtime_model.recovery_cost``; re-jit for
  the shrunken mesh dominates, replay scales with rollback depth.

The full run also measures the *hierarchy* column: flat vs two-tier
(``HierarchicalOrchestrator``) simulated clock at 64/256/1024 nodes under
a uniform one-batch-per-epoch composition, next to the eq. 19 two-tier
analytic prediction (``runtime_model.runtime_tl(hierarchy=...)``) — the
clock-vs-node-count chart of the hierarchical-TL tentpole.  The 64-node
point runs standalone as ``benchmarks/run.py --only hierarchy_smoke``.

``BENCH_tl_step.json`` at the repo root is the repo's step-time perf
*trajectory*: a list of runs keyed by git rev, appended to (never
overwritten) on each invocation; run via ``benchmarks/run.py`` (smoke) or
directly: ``PYTHONPATH=src python benchmarks/bench_tl_step.py``.
"""
import json
import os
import subprocess
import sys
import textwrap
import time
from typing import Optional

import jax
import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(REPO_ROOT, "BENCH_tl_step.json")

TOTAL_SAMPLES = 512
BATCH_SIZE = 64

# simulated cost model for the clock columns: node FP+local-BP compute per
# sample, and orchestrator centralized-BP per virtual-batch sample
SIM_COMPUTE_S_PER_SAMPLE = 1e-4
SIM_BP_S_PER_SAMPLE = 5e-4

# ---- two-tier hierarchy column (clock vs node count) -----------------------
# 2 samples/node and batch_size = 2·n_nodes give exactly ONE virtual batch
# per epoch in which every node contributes exactly 2 rows — the uniform
# composition runtime_model's two-tier branch assumes, so the analytic
# prediction is byte-exact against the measured transport clock.  rtt=0
# keeps the alignment exact (the same regime the existing eq. 19 alignment
# test pins); the 8 Gb/s link keeps the serialized root merge (one gradient
# pytree per subtree) from drowning the parallel-lane win.
HIER_NODE_COUNTS = (64, 256, 1024)
HIER_SUBTREES = {64: 8, 256: 16, 1024: 32}
HIER_SAMPLES_PER_NODE = 2
HIER_BW = 1e9
HIER_RTT = 0.0


def _git_rev() -> str:
    try:
        return subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                              cwd=REPO_ROOT, capture_output=True, text=True,
                              check=True).stdout.strip()
    except Exception:
        return "unknown"


def _build_orchestrator(n_nodes: int, *, fused: bool, pipelined: bool = False,
                        simulate_time: bool = False,
                        reassembly: str = "xla", wire=None):
    from repro.configs.paper_models import DATRET
    from repro.core.node import TLNode
    from repro.core.orchestrator import TLOrchestrator
    from repro.core.plan import PlanSpec
    from repro.core.transport import Transport
    from repro.models.small import SmallModel
    from repro.optim import sgd

    cfg = DATRET
    model = SmallModel(cfg)
    per_node = TOTAL_SAMPLES // n_nodes
    r = np.random.default_rng(0)
    nodes = [TLNode(i, model,
                    r.normal(size=(per_node,) + cfg.in_shape).astype(np.float32),
                    r.integers(0, cfg.n_classes, per_node),
                    jit_visits=fused)
             for i in range(n_nodes)]
    time_kw = {}
    if simulate_time:
        time_kw = dict(
            compute_time_fn=lambda k: SIM_COMPUTE_S_PER_SAMPLE * k,
            bp_time_fn=lambda n: SIM_BP_S_PER_SAMPLE * n)
    orch = TLOrchestrator(model, nodes, sgd(0.05), Transport(wire=wire),
                          batch_size=BATCH_SIZE, plan=PlanSpec(seed=0),
                          fused=fused, donate=fused, pipelined=pipelined,
                          reassembly=reassembly, **time_kw)
    orch.initialize(jax.random.PRNGKey(0))
    return orch


# Each epoch reshuffles the traversal plan, so segment lengths — and with
# them the bucket-padded visit shapes and eager pad/slice executables —
# keep producing NEW compilations for the first ~3 epochs before the shape
# space is covered.  A single warmup epoch (the original methodology) puts
# epoch 1's ~84 compiles inside the measured window and understates
# steps/sec by ~10x for whichever configuration runs first in the process.
WARMUP_EPOCHS = 4


def _measure(orch, epochs: int) -> float:
    """Steps/sec over `epochs` epochs after a shape-space-covering warmup."""
    for _ in range(WARMUP_EPOCHS):                         # warmup + compile
        orch.train_epoch()
    jax.block_until_ready(orch.params)
    steps = 0
    t0 = time.perf_counter()
    for _ in range(epochs):
        steps += len(orch.train_epoch())
    jax.block_until_ready(orch.params)
    return steps / (time.perf_counter() - t0)


def _wire_compression(n_nodes: int, epochs: int) -> dict:
    """The compressed-traversal-wire column: steps/s, cumulative visit wire
    bytes, and the measured raw/wire bytes ratio per rung on the fused
    path.  The ratio is the bandwidth headline (the acceptance bar is
    >=3.5x under int8); the steps/s shows the quant/dequant cost on this
    backend.  Model-parameter bytes are identical across rungs by
    construction (the "model" tag never quantizes)."""
    from repro.core.transport import WirePolicy
    col = {}
    for key, pol in (("off", None),
                     ("int8", WirePolicy.visits("int8")),
                     ("fp8_ef", WirePolicy.visits("fp8",
                                                  error_feedback=True))):
        orch = _build_orchestrator(n_nodes, fused=True, wire=pol)
        sps = _measure(orch, epochs)
        tr = orch.transport
        tag = "activations_grads"
        col[key] = {
            "steps_per_s": round(sps, 2),
            "visit_bytes": tr.bytes_sent[tag],
            "bytes_ratio": round(
                tr.raw_bytes[tag] / max(tr.bytes_sent[tag], 1), 2),
        }
    return col


def _build_hier_orchestrator(n_nodes: int, n_subtrees: Optional[int]):
    """Flat (``n_subtrees=None``) or two-tier simulated-time orchestrator at
    the hierarchy column's uniform composition: 2 samples/node, one virtual
    batch per epoch spanning the whole dataset."""
    from repro.configs.paper_models import DATRET
    from repro.core.hierarchy import HierarchicalOrchestrator
    from repro.core.node import TLNode
    from repro.core.orchestrator import TLOrchestrator
    from repro.core.plan import PlanSpec
    from repro.core.transport import NetworkModel, Transport
    from repro.models.small import SmallModel
    from repro.optim import sgd

    cfg = DATRET
    model = SmallModel(cfg)
    k = HIER_SAMPLES_PER_NODE
    r = np.random.default_rng(0)
    nodes = [TLNode(i, model,
                    r.normal(size=(k,) + cfg.in_shape).astype(np.float32),
                    r.integers(0, cfg.n_classes, k), jit_visits=True)
             for i in range(n_nodes)]
    tr = Transport(network=NetworkModel(bandwidth_bytes_per_s=HIER_BW,
                                        rtt_s=HIER_RTT))
    kw = dict(plan=PlanSpec(seed=0, batch_size=k * n_nodes),
              compute_time_fn=lambda m: SIM_COMPUTE_S_PER_SAMPLE * m,
              bp_time_fn=lambda m: SIM_BP_S_PER_SAMPLE * m, fused=True)
    if n_subtrees is None:
        orch = TLOrchestrator(model, nodes, sgd(0.05), tr, **kw)
    else:
        orch = HierarchicalOrchestrator(model, nodes, sgd(0.05), tr,
                                        n_subtrees=n_subtrees, **kw)
    orch.initialize(jax.random.PRNGKey(0))
    return orch


def _hier_spec(n_nodes: int, model_bytes: int):
    """The WorkloadSpec matching ``_build_hier_orchestrator`` byte for byte
    and tick for tick (SIM_* seconds re-expressed as FLOPs / FLOP rates)."""
    from repro.configs.paper_models import DATRET
    from repro.core.runtime_model import WorkloadSpec
    client = 1e12
    return WorkloadSpec(
        n_nodes=n_nodes, samples_per_node=HIER_SAMPLES_PER_NODE,
        batch_size=HIER_SAMPLES_PER_NODE * n_nodes,
        model_bytes=model_bytes,
        first_layer_bytes_per_sample=DATRET.hidden[0] * 4,
        logits_bytes_per_sample=DATRET.n_classes * 4,
        first_layer_param_bytes=(DATRET.in_shape[0] + 1)
        * DATRET.hidden[0] * 4,
        flops_per_sample_fwd=SIM_COMPUTE_S_PER_SAMPLE / 2 * client,
        flops_per_sample_bwd=SIM_COMPUTE_S_PER_SAMPLE / 2 * client,
        client_flops_per_s=client,
        server_flops_per_s=client * SIM_COMPUTE_S_PER_SAMPLE
        / SIM_BP_S_PER_SAMPLE,
        bandwidth_bytes_per_s=HIER_BW, rtt_s=HIER_RTT)


def _hierarchy_clock(node_counts=HIER_NODE_COUNTS) -> dict:
    """Clock vs node count, flat vs two-tier, measured (transport clock of a
    real simulated epoch) and predicted (eq. 19 two-tier branch).  The flat
    clock grows with the serial ΣT_comp,client + full-batch BP; the
    hierarchy divides both across subtree lanes and pays a serialized
    per-subtree merge — the crossover is the column's point."""
    from repro.core.runtime_model import runtime_tl
    from repro.core.transport import payload_bytes
    col = {}
    for n in node_counts:
        s = HIER_SUBTREES[n]
        flat = _build_hier_orchestrator(n, None)
        flat.train_epoch()
        jax.block_until_ready(flat.params)
        flat_clock = flat.transport.clock_s
        hier = _build_hier_orchestrator(n, s)
        hier.train_epoch()
        jax.block_until_ready(hier.params)
        hier_clock = hier.transport.clock_s
        spec = _hier_spec(n, payload_bytes(flat.params))
        pred_flat = runtime_tl(spec, hierarchy=1)
        pred_hier = runtime_tl(spec, hierarchy=s)
        col[str(n)] = {
            "n_subtrees": s,
            "flat_clock_s": round(flat_clock, 6),
            "two_tier_clock_s": round(hier_clock, 6),
            "speedup": round(flat_clock / hier_clock, 3),
            "predicted_flat_clock_s": round(pred_flat, 6),
            "predicted_two_tier_clock_s": round(pred_hier, 6),
            "predicted_err_flat": round(abs(pred_flat - flat_clock), 9),
            "predicted_err_two_tier": round(abs(pred_hier - hier_clock), 9),
        }
        print(f"bench_tl_step/hierarchy_nodes={n},"
              f"{hier_clock * 1e6:.0f},subtrees={s},"
              f"flat={flat_clock:.4f}s,two_tier={hier_clock:.4f}s,"
              f"speedup={flat_clock / hier_clock:.2f}x,"
              f"pred_err={abs(pred_hier - hier_clock):.2e}s")
    return col


def hierarchy_main(smoke: bool = False) -> dict:
    """Standalone hierarchy column (``benchmarks/run.py --only
    hierarchy_smoke`` runs the 64-node point as the CI smoke)."""
    counts = (64,) if smoke else HIER_NODE_COUNTS
    return {"model": "datret-mlp",
            "samples_per_node": HIER_SAMPLES_PER_NODE,
            "bandwidth_bytes_per_s": HIER_BW, "rtt_s": HIER_RTT,
            "backend": jax.default_backend(),
            "nodes": _hierarchy_clock(counts)}


def _simulated_clock(n_nodes: int, *, pipelined: bool) -> float:
    """Transport clock after one simulated-time epoch (fused path)."""
    orch = _build_orchestrator(n_nodes, fused=True, pipelined=pipelined,
                               simulate_time=True)
    orch.train_epoch()
    jax.block_until_ready(orch.params)
    return orch.transport.clock_s


_PRODUCTION_SCRIPT = textwrap.dedent("""
    import json, os, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.analysis.hlo_flops import analyze
    from repro.analysis.roofline import (HBM_BW, ICI_BW, PEAK_FLOPS)
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.core.tl_step import make_train_step, train_shardings
    from repro.data.pipeline import (VirtualBatchLoader, shard_corpus,
                                     synthetic_corpus)
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.optim import adamw

    cfg = get_config("deepseek-7b", reduced=True)
    model = build_model(cfg)
    mesh = make_host_mesh()                       # (4, 2) over 8 devices

    # ---- production-shape dryrun: the engine's pjit step, timed ---------
    B, S = 16, 64
    shape = InputShape("dryrun", S, B, "train")
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw(3e-4, clip_norm=1.0)
    st = opt.init(params)
    step = make_train_step(model, cfg, opt)
    r = np.random.default_rng(0)
    batch = {"tokens": r.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)}
    batch["targets"] = np.roll(batch["tokens"], -1, 1)
    t0 = time.perf_counter()
    with mesh:
        in_sh, out_sh = train_shardings(params, st, cfg, mesh, shape)
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
        compiled = jitted.lower(params, st, batch).compile()
    t_compile = time.perf_counter() - t0
    costs = analyze(compiled.as_text())
    roofline_s = max(costs.flops / PEAK_FLOPS, costs.hbm_bytes / HBM_BW,
                     costs.coll_total / ICI_BW)
    p, s = params, st
    times = []
    for _ in range(6):
        t = time.perf_counter()
        p, s, loss = jitted(p, s, batch)
        jax.block_until_ready((p, loss))
        times.append(time.perf_counter() - t)
    dryrun = {"arch": cfg.name, "mesh_shape": list(mesh.devices.shape),
              "global_batch": B, "seq": S,
              "t_compile_s": round(t_compile, 3),
              "step_time_s_cpu": round(float(np.median(times[1:])), 4),
              "roofline_step_s_v5e": float(f"{roofline_s:.3e}"),
              "flops_per_chip": float(f"{costs.flops:.3e}"),
              "coll_bytes_per_chip": int(costs.coll_total)}

    # ---- engine wall-clock: serial vs pipelined at 2/4/8 nodes ----------
    # The loader carries a simulated IO-bound ingest latency per batch
    # (INGEST_S of sleep — disk/tokenizer wait, not CPU), mirroring how the
    # simulator columns use a simulated WAN clock: on a CPU backend the
    # "device" shares cores with the host, so pure-CPU host work cannot
    # demonstrate overlap.  Serial loading pays ingest on the critical path
    # every step; the 2-deep prefetch queue hides it behind device compute.
    # The step is kept small (1 layer, d=128) so ingest is a visible
    # fraction of the step, and this column runs on the single-device mesh:
    # the overlap is a property of the engine's producer thread, not of the
    # sharding, and the forced-8-device mesh's XLA thread pools oversubscribe
    # small CPU hosts so badly that compute jitter swamps the signal (the
    # sharded step's cost lives in the production_dryrun column above).
    from repro.launch.mesh import make_debug_mesh
    INGEST_S = 0.02
    import dataclasses
    ecfg = dataclasses.replace(cfg, name="engine-clock", n_layers=1,
                               d_model=128, n_heads=2, n_kv_heads=2,
                               d_ff=256, vocab_size=256)
    emodel = build_model(ecfg)
    EB, ES, STEPS = 8, 32, 32
    eng = Engine(emodel, ecfg, adamw(3e-4, clip_norm=1.0),
                 make_debug_mesh(1, 1),
                 InputShape("bench", ES, EB, "train"))
    eng.init(jax.random.PRNGKey(0))

    def loader(n_nodes):
        docs = synthetic_corpus(n_nodes * 64, ES, ecfg.vocab_size, seed=1)
        for hb in VirtualBatchLoader(shard_corpus(docs, n_nodes), EB, seed=0):
            time.sleep(INGEST_S)                  # simulated IO-bound ingest
            yield hb

    eng.run(loader(2), steps=8)                   # compile + warmup
    clocks = {}
    for n in (2, 4, 8):
        serial, piped = [], []
        for _ in range(3):                        # min-of-3: dodge host noise
            eng.pipeline = False
            serial.append(eng.run(loader(n), steps=STEPS).wall_s)
            eng.pipeline = True
            piped.append(eng.run(loader(n), steps=STEPS).wall_s)
        serial, piped = min(serial), min(piped)
        clocks[str(n)] = {
            "ingest_s_per_batch": INGEST_S,
            "serial_wall_s": round(serial, 4),
            "pipelined_wall_s": round(piped, 4),
            "overlap_gain": round(serial / piped, 3)}

    print("RESULT", json.dumps({"production_dryrun": dryrun,
                                "engine_clock": clocks}))
""")

# The elastic-recovery measurement re-jits the step across shrinking
# meshes; it runs in a process of its own so its forced devices and
# re-jits never share a jax runtime with the columns above.
_ELASTIC_SCRIPT = textwrap.dedent("""
    import dataclasses, json, os, tempfile, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.data.pipeline import (VirtualBatchLoader, shard_corpus,
                                     synthetic_corpus)
    from repro.launch.elastic import KILL, DeviceFaultSpec, Drill
    from repro.launch.engine import Engine
    from repro.models import build_model
    from repro.optim import adamw

    # ---- elastic recovery: detect -> reshrink -> restore -> replay ------
    # A scripted chip kill at step 3 on (1,2)/(2,2)/(4,2) meshes over the
    # first 2/4/8 forced host devices; the engine's RecoveryReport is the
    # measurement.  ckpt_every=2 puts a checkpoint at step 2 (rollback
    # depth 1); ckpt_every=4 leaves only the step-0 anchor (depth 3) — the
    # depth axis of runtime_model.recovery_cost.
    cfg = get_config("deepseek-7b", reduced=True)
    ecfg = dataclasses.replace(cfg, name="engine-clock", n_layers=1,
                               d_model=128, n_heads=2, n_kv_heads=2,
                               d_ff=256, vocab_size=256)
    emodel = build_model(ecfg)
    EB, ES = 8, 32
    devs = jax.devices()
    docs_e = synthetic_corpus(2 * 64, ES, ecfg.vocab_size, seed=1)
    vbl = VirtualBatchLoader(shard_corpus(docs_e, 2), EB, seed=0)
    elastic = {}
    for n in (2, 4, 8):
        mesh_n = jax.sharding.Mesh(
            np.array(devs[:n]).reshape(n // 2, 2), ("data", "model"))
        per_cadence = {}
        for ckpt_every in (2, 4):
            eng_e = Engine(
                emodel, ecfg, adamw(3e-4, clip_norm=1.0), mesh_n,
                InputShape("bench", ES, EB, "train"),
                ckpt_dir=tempfile.mkdtemp(), ckpt_every=ckpt_every,
                elastic=True, watchdog_s=300.0,
                device_faults=DeviceFaultSpec(
                    drills=(Drill(KILL, 3, devs[0].id),)))
            eng_e.init(jax.random.PRNGKey(0))
            res = eng_e.run(vbl, steps=5)
            rec = res.recovery[0].as_dict()
            rec["n_devices"] = n
            per_cadence[f"ckpt_every_{ckpt_every}"] = rec
        elastic[str(n)] = per_cadence

    print("RESULT", json.dumps({"elastic_recovery": elastic}))
""")


def _run_result_script(script: str, what: str, timeout_s: int) -> dict:
    """Run one CPU-only measurement subprocess and parse its RESULT line.
    The child pins ``JAX_PLATFORMS=cpu`` before jax loads, so it never
    contends for an accelerator; a child that fails or times out fails the
    benchmark."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO_ROOT, "src"),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script],
                          env=env, capture_output=True, text=True,
                          timeout=timeout_s)
    if proc.returncode != 0:
        raise RuntimeError(f"{what} subprocess failed "
                           f"(exit {proc.returncode}):\n{proc.stderr[-2000:]}")
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line.split("RESULT ")[1])


def _production_columns() -> dict:
    """Run the production-path measurements in subprocesses (the forced
    8-device count must never leak into this process's jax)."""
    out = _run_result_script(_PRODUCTION_SCRIPT, "production", 1500)
    out.update(_run_result_script(_ELASTIC_SCRIPT, "elastic", 900))
    d = out["production_dryrun"]
    print(f"bench_tl_step/production_dryrun,"
          f"{d['step_time_s_cpu'] * 1e6:.0f},"
          f"roofline_v5e={d['roofline_step_s_v5e']:.2e}s")
    for n, c in out["engine_clock"].items():
        print(f"bench_tl_step/engine_nodes={n},"
              f"{c['pipelined_wall_s'] * 1e6:.0f},"
              f"overlap_gain={c['overlap_gain']}x")
    for n, cad in out.get("elastic_recovery", {}).items():
        for name, rec in cad.items():
            print(f"bench_tl_step/elastic_devices={n}/{name},"
                  f"{rec['total_s'] * 1e6:.0f},"
                  f"depth={rec['rollback_depth']},"
                  f"rejit={rec['rejit_s']:.2f}s")
    return out


def _load_runs(out_path: str) -> list:
    """Existing trajectory; a legacy single-run dict is migrated in place
    as the trajectory's first entry (for the root artifact that's PR 1's
    fused-vs-eager baseline, whose rev is known)."""
    if not os.path.exists(out_path):
        return []
    with open(out_path) as f:
        data = json.load(f)
    if isinstance(data, dict):                             # legacy format
        legacy_rev = ("822cfe8" if os.path.abspath(out_path) == OUT_PATH
                      else "unknown")
        data.setdefault("git_rev", legacy_rev)
        data.setdefault("legacy", True)     # never displaced by re-runs
        return [data]
    return data


def run(node_counts=(2, 4, 8), epochs: int = 3,
        out_path: Optional[str] = OUT_PATH,
        production: bool = True, hierarchy: bool = True) -> dict:
    """One benchmark entry.  ``out_path=None`` skips the trajectory write
    (smoke mode: ``benchmarks/run.py`` wraps the returned entry in its
    standard ``BENCH_<name>.json`` artifact instead)."""
    results = {}
    for n in node_counts:
        eager = _measure(_build_orchestrator(n, fused=False), epochs)
        fused = _measure(_build_orchestrator(n, fused=True), epochs)
        pallas = _measure(_build_orchestrator(n, fused=True,
                                              reassembly="pallas"), epochs)
        piped = _measure(_build_orchestrator(n, fused=True, pipelined=True),
                         epochs)
        clock_serial = _simulated_clock(n, pipelined=False)
        clock_piped = _simulated_clock(n, pipelined=True)
        wire = _wire_compression(n, epochs)
        results[str(n)] = {
            "eager_steps_per_s": round(eager, 2),
            "fused_steps_per_s": round(fused, 2),
            "pipelined_steps_per_s": round(piped, 2),
            "speedup": round(fused / eager, 2),
            "reassembly": {
                "xla_steps_per_s": round(fused, 2),
                "pallas_steps_per_s": round(pallas, 2),
            },
            "serial_clock_s": round(clock_serial, 4),
            "pipelined_clock_s": round(clock_piped, 4),
            "clock_speedup": round(clock_serial / clock_piped, 3),
            "wire_compression": wire,
        }
        print(f"bench_tl_step/nodes={n},"
              f"{1e6 / fused:.0f},speedup={fused / eager:.2f}x,"
              f"reassembly_pallas={pallas:.2f}steps/s,"
              f"clock={clock_serial:.3f}s->{clock_piped:.3f}s,"
              f"wire_int8={wire['int8']['bytes_ratio']}x,"
              f"wire_fp8_ef={wire['fp8_ef']['bytes_ratio']}x")
    entry = {
        "git_rev": _git_rev(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "benchmark": "tl_step",
        "model": "datret-mlp",
        "batch_size": BATCH_SIZE,
        "total_samples": TOTAL_SAMPLES,
        "epochs_measured": epochs,
        "backend": jax.default_backend(),
        "nodes": results,
    }
    if hierarchy:
        # clock vs node count far beyond the flat sweep: flat serial vs
        # two-tier, measured and eq.-19-predicted, at 64/256/1024 nodes
        entry["hierarchy"] = _hierarchy_clock()
    if production:
        entry.update(_production_columns())
    if out_path is not None:
        # one entry per git rev: a re-run at the same checkout replaces its
        # own earlier entry instead of duplicating it (the trajectory is
        # per-PR).  Migrated legacy baselines are immune — a dirty tree
        # sitting on the baseline's rev must not displace the baseline it
        # is compared against.
        runs = [r for r in _load_runs(out_path)
                if r.get("legacy") or r.get("git_rev") != entry["git_rev"]]
        runs.append(entry)
        with open(out_path, "w") as f:
            json.dump(runs, f, indent=1)
        print(f"bench_tl_step/artifact,{out_path} ({len(runs)} runs)")
    return entry


def main(smoke: bool = False) -> dict:
    if smoke:
        # fast per-PR regression signal: 2 nodes, one measured epoch, same
        # entry shape, no production subprocess and no hierarchy sweep (the
        # hierarchy smoke is its own run.py entry, ``hierarchy_smoke``).
        # The smoke artifact is written by benchmarks/run.py's standard
        # wrapper (BENCH_tl_step_smoke.json), not by this module — the
        # trajectory file stays full-sweep-only.
        return run(node_counts=(2,), epochs=1, out_path=None,
                   production=False, hierarchy=False)
    return run()


if __name__ == "__main__":
    import sys
    art = main(smoke="--smoke" in sys.argv)
    worst = min(v["speedup"] for v in art["nodes"].values())
    print(f"bench_tl_step/min_speedup,{worst}")
