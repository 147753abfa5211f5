"""End-to-end TL training CLI — a thin shim over ``repro.launch.engine``.

    PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b --reduced \
        --steps 50 --nodes 4 --batch 8 --seq 64 --mesh debug --pipeline

Wires together: synthetic corpus -> node shards -> virtual-batch loader
(Algorithm 1) -> ``Engine`` -> checkpointing.  The engine owns everything
the old driver got wrong: the step is jitted once with ``train_shardings``
in/out shardings and donated params/opt_state on a real mesh (``--mesh
{debug,host,production}``), batches prefetch host->device through a 2-deep
queue while the previous step runs (``--pipeline``, default; ``--no-
pipeline`` is the strictly batch-serial oracle), losses stay
device-resident until log boundaries — no per-step host sync — and the
virtual batch is reassembled into shuffled order inside the compiled step
(``--reassembly {xla,pallas}``: generic scatter vs the fused Pallas
vb_scatter kernel, shard-local perms under shard_map).

The three execution modes and their equivalence guarantees are documented
in ``repro.launch.engine``; the pipelined and serial paths produce
float32-ULP-identical parameters (``tests/test_engine.py``).

Fault tolerance: ``--ckpt-every N`` writes a step-boundary checkpoint into
``--ckpt`` every N steps (``--ckpt-keep N`` bounds the directory to the N
newest valid steps), and ``--resume`` restores the latest one — the loader
is a pure function of its seed, so the resumed run replays exactly the
killed run's remaining batches and finishes ULP-identical to an
uninterrupted run (``tests/test_faults.py``).

Elastic production engine: ``--elastic`` arms the device-loss supervision
loop (``repro.launch.elastic`` + ``Engine``): a lost chip or hung
collective (watchdog deadline ``--watchdog-s``) triggers mesh reshrink +
checkpoint rollback + deterministic replay instead of a crash.
``--drill kill-device:STEP[:DEV]`` / ``hang-device:STEP[:DEV]`` injects a
scripted fault for recovery drills; with ``--elastic`` the CLI then
*verifies the recovery guarantee* — it re-runs fresh from the rollback
checkpoint on the shrunken mesh and asserts the final parameters are
bit-equal, printing ``RECOVERY_DRILL bit_equal=true`` (the CI
``recovery-drill`` job greps exactly this).  A drill without ``--elastic``
fails loudly with the ``DeviceLost`` diagnostic — never a silent hang.

Compressed traversal wire: ``--mode sim --wire {int8,fp8} [--wire-ef]``
runs the protocol simulator with the visit-payload lane quantized
(per-row absmax, ``repro.kernels.act_compress``) and prints the measured
per-tag raw-vs-wire byte ratio from the transport; ``--wire-ef`` adds the
error-feedback accumulator (lossless-in-the-limit).  Model parameters
never quantize in any configuration.
"""
from __future__ import annotations

import argparse
import sys
import tempfile

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.data.pipeline import VirtualBatchLoader, shard_corpus, synthetic_corpus
from repro.launch.engine import Engine
from repro.launch.mesh import resolve_mesh
from repro.models import build_model
from repro.optim import adamw, warmup_cosine


def _run_sim(args):
    """Protocol-simulator run (``--mode sim``): DATRET on the
    TLOrchestrator via the Engine facade, with the wire-compression lane
    live — prints the per-tag raw-vs-wire byte accounting from the
    transport so ``--wire int8 --wire-ef`` shows the measured bandwidth
    win (model parameters always ship exact)."""
    from repro.configs.paper_models import DATRET
    from repro.core.baselines import ShardData
    from repro.models.small import SmallModel
    from repro.optim import sgd

    r = np.random.default_rng(5)
    shards = [ShardData(
        r.normal(size=(64,) + DATRET.in_shape).astype(np.float32),
        r.integers(0, DATRET.n_classes, 64)) for _ in range(args.nodes)]
    engine = Engine(SmallModel(DATRET), DATRET, sgd(0.05), mode="sim",
                    pipeline=args.pipeline and not args.hierarchy,
                    batch_size=32, seed=0, hierarchy=args.hierarchy,
                    wire=args.wire, wire_ef=args.wire_ef)
    result = engine.run(shards, epochs=args.epochs)
    tr = engine.orchestrator.transport
    print(f"mode=sim arch=datret nodes={args.nodes} epochs={args.epochs} "
          f"hierarchy={args.hierarchy} wire={args.wire} ef={args.wire_ef}")
    for tag in sorted(tr.bytes_sent):
        raw, wire = tr.raw_bytes.get(tag, 0), tr.bytes_sent[tag]
        print(f"wire[{tag}]: raw={raw} wire={wire} "
              f"ratio={raw / max(wire, 1):.2f}x")
    losses = result.losses.tolist()
    print(f"final loss {np.mean(losses[-5:]):.4f} "
          f"(start {np.mean(losses[:5]):.4f})")
    return losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--remat", default="tl", choices=["tl", "none", "dots"])
    ap.add_argument("--reassembly", default="xla",
                    choices=["xla", "pallas"],
                    help="virtual-batch reassembly on the hot path: XLA's "
                         "generic scatter or the fused Pallas vb_scatter "
                         "kernel (shard-local perms under shard_map)")
    ap.add_argument("--mesh", default="debug",
                    choices=["debug", "host", "production"])
    ap.add_argument("--multi-pod", action="store_true",
                    help="with --mesh production: the 2x16x16 "
                         "(pod, data, model) mesh")
    ap.add_argument("--pipeline", action="store_true", default=True,
                    help="2-deep host->device batch prefetch (default)")
    ap.add_argument("--no-pipeline", dest="pipeline", action="store_false",
                    help="strictly batch-serial loading (the equivalence "
                         "oracle)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save a step-boundary checkpoint into --ckpt every "
                         "N steps (0: only the final checkpoint)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt; the "
                         "run replays the loader tail and finishes "
                         "ULP-identical to an uninterrupted run")
    ap.add_argument("--ckpt-keep", type=int, default=0,
                    help="retain only the N newest valid checkpoints after "
                         "every save (0: keep everything); the step a live "
                         "resume/rollback depends on is never collected")
    ap.add_argument("--elastic", action="store_true",
                    help="device-loss supervision: watchdog detection, mesh "
                         "reshrink over the survivors, checkpoint rollback, "
                         "deterministic replay (see repro.launch.elastic)")
    ap.add_argument("--drill", default=None,
                    help="scripted fault injection: kill-device:STEP[:DEV] "
                         "or hang-device:STEP[:DEV]; with --elastic the run "
                         "recovers and the CLI verifies bit-equality against "
                         "a fresh run from the rollback checkpoint")
    ap.add_argument("--watchdog-s", type=float, default=60.0,
                    help="per-step watchdog deadline (seconds): a step that "
                         "exceeds it is classified as a lost device")
    ap.add_argument("--halt-at", type=int, default=0,
                    help="crash drill: stop after this many global steps "
                         "without finishing the --steps budget (the LR "
                         "schedule and checkpoints stay those of the full "
                         "budget, exactly like a real mid-run kill)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--mode", default="production",
                    choices=["production", "sim"],
                    help="production: pjit engine on a device mesh; sim: "
                         "the protocol simulator (TLOrchestrator), where "
                         "the wire-compression lane is live")
    ap.add_argument("--epochs", type=int, default=3,
                    help="sim mode: orchestrator epochs")
    ap.add_argument("--hierarchy", type=int, default=0,
                    help="sim mode: two-tier orchestration fan-out — "
                         "number of subtrees (0: flat). Implies "
                         "--no-pipeline: the subtree lanes are the overlap")
    ap.add_argument("--wire", default="off", choices=["off", "int8", "fp8"],
                    help="visit-payload wire codec in the sim transport "
                         "(X^(1)/δ^(L)/∂X^(1)/∂W^(1) quantize per-row; "
                         "model parameters never do)")
    ap.add_argument("--wire-ef", action="store_true",
                    help="error-feedback accumulator on the wire lane: "
                         "each send compresses x + residual and carries "
                         "the quantization error forward "
                         "(lossless-in-the-limit)")
    args = ap.parse_args(argv)
    if args.wire != "off" and args.mode != "sim":
        ap.error("--wire is simulator-only for now: pass --mode sim (the "
                 "production pjit path has no Transport wire)")
    if args.wire_ef and args.wire == "off":
        ap.error("--wire-ef needs --wire {int8,fp8}")
    if args.mode == "sim":
        return _run_sim(args)
    if args.resume and not args.ckpt:
        ap.error("--resume needs --ckpt")
    if args.ckpt_every and not args.ckpt:
        ap.error("--ckpt-every needs --ckpt")
    if args.ckpt_keep and not args.ckpt:
        ap.error("--ckpt-keep needs --ckpt")
    if args.elastic and not args.ckpt:
        # recovery needs a rollback anchor; a drill run doesn't need the
        # checkpoints to outlive the process
        args.ckpt = tempfile.mkdtemp(prefix="tl_elastic_ckpt_")
        print(f"--elastic without --ckpt: rollback anchors in {args.ckpt}")
    drill = None
    if args.drill:
        from repro.launch.elastic import DeviceFaultSpec, parse_drill
        try:
            drill = DeviceFaultSpec(drills=(parse_drill(args.drill),))
        except ValueError as e:
            ap.error(str(e))

    cfg = get_config(args.arch, reduced=args.reduced)
    model = build_model(cfg)
    mesh = resolve_mesh(args.mesh, multi_pod=args.multi_pod)
    shape = InputShape("cli_train", args.seq, args.batch, "train")
    opt = adamw(warmup_cosine(args.lr, 10, args.steps), clip_norm=1.0)

    engine = Engine(model, cfg, opt, mesh, shape,
                    pipeline=args.pipeline, remat_mode=args.remat,
                    reassembly=args.reassembly, log_every=args.log_every,
                    ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every,
                    ckpt_keep=args.ckpt_keep, elastic=args.elastic,
                    device_faults=drill, watchdog_s=args.watchdog_s)
    # the LR schedule is a function of the run config (--steps fixes the
    # cosine horizon, --lr the peak): stamp it into every checkpoint so a
    # resume under a *different* config fails loudly instead of silently
    # replaying different arithmetic (bit-identity needs identical configs)
    # nodes/batch/seq shape the synthetic corpus and loader stream, so they
    # are part of the resume contract too
    engine.ckpt_meta = {"arch": cfg.name, "steps": args.steps,
                        "lr": args.lr, "seed": 0, "nodes": args.nodes,
                        "batch": args.batch, "seq": args.seq}
    if args.resume:
        at = engine.restore()
        got = engine.restored_meta or {}
        for key, want in engine.ckpt_meta.items():
            if key in got and got[key] != want:
                ap.error(
                    f"--resume config mismatch: checkpoint was written by a "
                    f"run with {key}={got[key]!r}, this run has {key}="
                    f"{want!r} — the LR schedule/data order would diverge "
                    "from the killed run (pass the original flags)")
        if at >= args.steps:
            ap.error(f"checkpoint is already at step {at} of the --steps "
                     f"{args.steps} budget: nothing to resume")
        print(f"resumed from step {at}")
    else:
        at = 0
        engine.init(jax.random.PRNGKey(0))
    print(f"arch={cfg.name} params={engine.n_params()/1e6:.1f}M "
          f"nodes={args.nodes} mesh={args.mesh}{mesh.devices.shape} "
          f"pipeline={args.pipeline} reassembly={args.reassembly}")

    docs = synthetic_corpus(args.nodes * 64, args.seq, cfg.vocab_size, seed=1)
    shards = shard_corpus(docs, args.nodes)
    loader = VirtualBatchLoader(shards, args.batch, seed=0)

    budget = min(args.halt_at, args.steps) if args.halt_at else args.steps
    try:
        result = engine.run(loader, steps=budget)
    except Exception as e:
        from repro.launch.elastic import DeviceLost
        if isinstance(e, DeviceLost):
            # un-recovered device loss (no --elastic): fail loudly with the
            # diagnostic instead of a hang or a bare traceback
            print(f"FATAL: {e}\n       rerun with --elastic to recover "
                  "(reshrink + rollback + replay)", file=sys.stderr)
            raise SystemExit(2)
        raise
    for rec in result.recovery or ():
        print("recovery:", rec.as_dict())
    losses = result.losses.tolist()
    print(f"final loss {np.mean(losses[-5:]):.4f} "
          f"(start {np.mean(losses[:5]):.4f}) "
          f"{result.steps_per_s:.2f} steps/s, "
          f"input wait {result.input_wait_s:.3f} s of {result.wall_s:.3f} s")
    if args.ckpt:
        # same layout as the engine's step-boundary checkpoints, so a
        # --halt-at (or crashed-after-save) run's final checkpoint is
        # --resume-able under the same flags; a *completed* budget cannot
        # be extended — the config guard above refuses a changed --steps
        path = engine.save_ckpt(result.params, result.opt_state,
                                at + result.steps)
        print("checkpoint:", path)

    if args.elastic and args.drill and result.recovery:
        # verify the recovery guarantee end-to-end: a *fresh* engine on the
        # final (shrunken) mesh, restored from the rollback checkpoint and
        # run over the same loader, must produce bit-equal parameters —
        # post-recovery training is indistinguishable from a clean launch
        rollback = result.recovery[-1].rollback_step
        oracle = Engine(model, cfg, opt, engine.mesh, shape,
                        pipeline=args.pipeline, remat_mode=args.remat,
                        reassembly=args.reassembly, ckpt_dir=args.ckpt)
        oracle.restore(step=rollback)
        fresh = oracle.run(loader, steps=budget)
        bit_equal = all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(jax.tree.leaves(result.params),
                            jax.tree.leaves(fresh.params)))
        print(f"RECOVERY_DRILL bit_equal={str(bit_equal).lower()} "
              f"rollback_step={rollback} "
              f"mesh={tuple(int(s) for s in engine.mesh.devices.shape)}")
        if not bit_equal:
            print("FATAL: post-recovery parameters diverge from a fresh run "
                  "off the rollback checkpoint — the recovery guarantee is "
                  "broken", file=sys.stderr)
            raise SystemExit(3)
    return losses


if __name__ == "__main__":
    main()
