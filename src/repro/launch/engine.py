"""Unified TL training engine: one driver for all three execution modes.

Before this module the repo ran a TL step three disjoint ways:

* **simulator serial** — ``TLOrchestrator.train_batch``: the protocol
  simulator's per-virtual-batch round (node visits -> centralized BP);
* **simulator pipelined** — ``repro.core.pipeline``: the double-buffered
  visit-producer / BP-consumer epoch engine over the same orchestrator;
* **production jit** — ``launch/train.py``'s bare ``jax.jit`` loop with no
  mesh, no shardings, no donation, and a host sync every step.

``Engine`` unifies them behind one API::

    Engine(model, cfg, opt, mesh, shape).run(loader, steps)

**Production mode** (``mode="production"``, the default) drives the pjit TL
step (``repro.core.tl_step``) the way the 512-chip dry-run lowers it:

* the step is jitted once with :func:`train_shardings` in/out shardings on
  the given mesh and the params/opt_state buffers donated — identical on
  the (1,1)/(2,2) debug meshes, the forced-8-device CPU host mesh, and the
  multi-pod (pod, data, model) production mesh;
* ``pipeline=True`` ports the simulator's producer/consumer split to the
  device path: while step k's update runs, a background producer thread
  already assembles virtual batch k+1 from the loader and
  ``jax.device_put``\\ s its node-major shards with the ``tokens_pspec``
  NamedSharding — a 2-deep host->device prefetch queue (the double buffer:
  the batch being consumed plus the batch in flight), bounded by a slot
  semaphore so at most ``PREFETCH_DEPTH`` batches ever materialize ahead.
  ``pipeline=False`` reproduces the historical
  strictly batch-serial driver (dispatch, wait for the step, only then
  touch the loader) as the equivalence oracle and benchmark baseline;
* losses stay device-resident for the whole run; the host materializes a
  value only at ``log_every`` boundaries and at the end, so logging never
  blocks the prefetch queue;
* ``reassembly`` ("none" | "xla" | "pallas") puts the orchestrator's
  virtual-batch reassembly on the pjit hot path (``repro.core.tl_step``):
  the loader's ``positions`` (global batch positions of the node-major
  rows) are converted — per data shard — into shard-local rank perms, so
  the in-loss scatter runs under a ``shard_map`` over the (pod, data) axes
  with zero collective traffic; ``"pallas"`` lowers it through the fused
  ``repro.kernels.vb_scatter`` kernel instead of XLA's generic scatter.

**Simulator mode** (``mode="sim"``) wraps ``TLOrchestrator`` and routes
``pipeline=True`` through ``repro.core.pipeline`` — the engine is then a
thin facade so quickstart-style scripts and the production driver share one
entrypoint.

Equivalence guarantees (enforced by ``tests/test_engine.py``):

* production ``pipeline=True`` and ``pipeline=False`` run the *same* jitted
  step over the *same* batches in the same order — prefetch moves only
  host/transfer timing, so final params match to float32 ULP (in practice
  bit-for-bit) on every mesh;
* simulator ``pipeline=True`` is the lossless reordering proven by
  ``tests/test_pipelined_equivalence.py``.

**Elastic mode** (``elastic=True``, production only) wraps the pjit path in
a supervision loop that turns a lost chip from a fatal crash into a
bounded-cost recovery: ``device_faults`` (a
``repro.launch.elastic.DeviceFaultInjector``) injects seeded/scripted chip
kills and hung collectives at the host boundary, every step is issued under
a ``watchdog_s`` deadline (a hung collective is *classified* as a lost
device instead of stalling forever), and on detection the engine

1. re-factorizes the mesh over the surviving devices
   (``launch.mesh.plan_reshrink`` — data axis degrades first, validated
   against ``param_specs`` divisibility),
2. rolls back to the newest valid checkpoint (``ckpt_dir`` is therefore
   required; a step-0 anchor is written before the first step),
3. re-shards params/opt_state onto the new mesh's ``NamedSharding``\\ s and
   re-jits the step,
4. replays the loader deterministically to the rollback step and resumes.

The recovery guarantee is exact: post-recovery training on the shrunken
mesh is **bit-equal** to a fresh run launched from that checkpoint on that
mesh (``tests/test_elastic.py``) — the loader is a pure function of its
seed and every replayed batch flows through the same re-jitted step.
Without ``elastic=True`` an armed injector still detects (kill raises,
the watchdog still fires within its deadline) but the ``DeviceLost``
propagates as a loud failure instead of recovering.  Each recovery's
detect/plan/restore/rejit/replay cost lands in ``Engine.recovery_log``
(the ``elastic_recovery`` benchmark column and the
``runtime_model.recovery_cost`` term measure exactly this).
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.core.tl_step import make_train_step, train_shardings
from repro.dist.sharding import tokens_pspec
from repro.launch.elastic import (HANG, DeviceFaultInjector, DeviceFaultSpec,
                                  DeviceLost, RecoveryReport, WatchdogTimeout,
                                  call_with_deadline, simulate_hang)


@dataclass
class EngineResult:
    """What one ``Engine.run`` produced.  ``losses`` is host-materialized
    exactly once, at the end of the run.

    ``input_wait_s`` (production mode) is the host seconds the step loop
    spent waiting for its next device batch, the ``tl_input_wait`` spans of
    the run: blocked on the prefetch queue with ``pipeline=True``, loading
    and transferring the batch itself with ``pipeline=False``.  In elastic
    mode it is the last pass's; in sim mode it stays 0.  ``launch.train``
    prints it beside the run's wall time: a wait near the wall time means
    the step is input-bound."""
    losses: np.ndarray
    steps: int
    wall_s: float
    params: Any
    opt_state: Any = None
    stats: Optional[List] = None          # sim mode: flat StepStats list
    epoch_stats: Optional[List[List]] = None
    recovery: Optional[List] = None       # elastic mode: RecoveryReports
    input_wait_s: float = 0.0

    @property
    def steps_per_s(self) -> float:
        return self.steps / self.wall_s if self.wall_s else float("inf")


class Engine:
    """Unified TL training driver (see module docstring).

    Production-mode knobs: ``pipeline`` (2-deep device prefetch vs strictly
    batch-serial), ``remat_mode``, ``donate``, ``log_every``,
    ``reassembly`` ("none" | "xla" | "pallas" — in-loss virtual-batch
    reassembly with shard-local perms).

    Sim-mode knobs (forwarded to ``TLOrchestrator``): ``batch_size``,
    ``transport``, ``fused``, ``cache_model_per_epoch``, ``seed``; the
    shared ``pipeline`` flag selects the double-buffered epoch engine and
    ``reassembly`` the orchestrator's scatter strategy.  ``wire``
    ("off" | "int8" | "fp8") + ``wire_ef`` build a visit-payload
    :class:`~repro.core.transport.WirePolicy` transport (sim-only; model
    parameters never quantize; mutually exclusive with ``transport``).
    """

    PREFETCH_DEPTH = 2          # double buffer: consumed batch + in-flight

    def __init__(self, model, cfg: ModelConfig, opt, mesh=None,
                 shape: Optional[InputShape] = None, *,
                 mode: str = "production", pipeline: bool = True,
                 remat_mode: str = "tl", donate: bool = True,
                 microbatch: int = 1, log_every: int = 0,
                 reassembly: str = "none",
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
                 ckpt_keep: int = 0, elastic: bool = False,
                 device_faults=None, watchdog_s: float = 60.0,
                 batch_size: int = 64, transport=None, fused: bool = True,
                 cache_model_per_epoch: bool = False, seed: int = 0,
                 wire: str = "off", wire_ef: bool = False,
                 hierarchy: int = 0):
        if mode not in ("production", "sim"):
            raise ValueError(f"unknown engine mode: {mode!r}")
        if mode == "production" and (mesh is None or shape is None):
            raise ValueError("production mode needs a mesh and an InputShape")
        if wire != "off" and mode != "sim":
            raise ValueError(
                "wire compression is simulator-only for now: the production "
                "pjit path has no Transport to carry the WirePolicy")
        if wire != "off" and transport is not None:
            raise ValueError("pass either wire=... or a pre-built transport, "
                             "not both")
        if reassembly not in ("none", "xla", "pallas"):
            raise ValueError(f"unknown reassembly strategy: {reassembly!r}")
        if hierarchy < 0:
            raise ValueError(f"hierarchy must be >= 0, got {hierarchy}")
        if hierarchy and mode != "sim":
            raise ValueError(
                "hierarchy= (two-tier orchestration fan-out) is "
                "simulator-only: the production pjit path shards one flat "
                "step instead of nesting orchestrators")
        if hierarchy and pipeline:
            raise ValueError(
                "hierarchy= needs pipeline=False: the subtree lanes are "
                "the overlap; the double-buffered epoch engine on top "
                "would double-book the clock")
        if elastic and mode != "production":
            raise ValueError("elastic mode is production-only")
        if elastic and not ckpt_dir:
            raise ValueError(
                "elastic mode needs a ckpt_dir: the newest checkpoint is the "
                "rollback anchor every recovery restores from")
        self.model = model
        self.cfg = cfg
        self.opt = opt
        self.mesh = mesh
        self.shape = shape
        self.mode = mode
        self.pipeline = pipeline
        self.remat_mode = remat_mode
        self.donate = donate
        self.microbatch = microbatch
        self.log_every = log_every
        # reassembly: "none" | "xla" | "pallas" — production mode scatters
        # the virtual batch into shuffled order inside the loss (see module
        # docstring); sim mode forwards the strategy to TLOrchestrator
        # ("none" keeps the orchestrator's default xla scatter)
        self.reassembly = reassembly
        # step-boundary checkpointing (repro.checkpoint): production mode
        # saves {params, opt_state} every ckpt_every steps; sim mode saves
        # the orchestrator's full resume state at every epoch boundary.
        # restore() + run() replays the remaining batches — the loader and
        # the orchestrator's plan are pure functions of their seeds, so a
        # killed run resumes ULP-identically (tests/test_faults.py)
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        # ckpt_keep > 0 bounds the checkpoint dir: after every save the GC
        # retains the `keep` newest valid steps (repro.checkpoint
        # .gc_checkpoints) — never the step a live resume/rollback depends on
        self.ckpt_keep = ckpt_keep
        # ----- elastic supervision (see module docstring + launch.elastic)
        self.elastic = elastic
        if isinstance(device_faults, DeviceFaultSpec):
            device_faults = DeviceFaultInjector(device_faults)
        self.device_faults = device_faults
        self.watchdog_s = watchdog_s
        self.recovery_log: List[RecoveryReport] = []
        # a deterministic drill would re-fire every time the replay revisits
        # its step — fire each (step, device, kind) verdict at most once so
        # every recovery makes monotone progress
        self._fired_faults = set()
        self._protect_steps = set()
        # the watchdog deadline models the *steady-state* step clock; the
        # first step after every (re-)jit also pays an unbounded compile, so
        # it runs unsupervised and the deadline arms from the next step
        self._jit_warm = False
        self._loss_acc = {}            # step -> device loss (replays overwrite)
        self._pending_report = None    # RecoveryReport awaiting rejit/replay timings
        # caller-supplied run metadata stamped into every checkpoint's
        # extra dict (e.g. the CLI's total-step budget, which fixes the LR
        # schedule); surfaced back on restore() as .restored_meta so the
        # caller can refuse a resume whose run config would silently change
        # the arithmetic (bit-identity holds only for identical configs)
        self.ckpt_meta: Optional[dict] = None
        self.restored_meta: Optional[dict] = None
        self._start_step = 0
        self._sim_resume = None
        # sim-mode state
        self.batch_size = batch_size
        if wire != "off":
            from repro.core.transport import Transport, WirePolicy
            transport = Transport(
                wire=WirePolicy.visits(wire, error_feedback=wire_ef))
        self.wire = wire
        self.wire_ef = wire_ef
        self.transport = transport
        self.fused = fused
        self.cache_model_per_epoch = cache_model_per_epoch
        self.seed = seed
        # hierarchy > 0: sim mode builds a HierarchicalOrchestrator with
        # that many subtrees (0 = flat single orchestrator)
        self.hierarchy = hierarchy
        self.orchestrator = None
        self._sim_shards = None
        # production-mode state
        self.params = None
        self.opt_state = None
        self._step_fn = None
        self._state_shardings = None
        self._batch_shardings = None
        self._zero_embeds = None
        self._n_perm_shards = 1

    # ------------------------------------------------------------ lifecycle
    def init(self, key) -> "Engine":
        """Initialize params (+ optimizer state in production mode)."""
        self.params = self.model.init(key)
        if self.mode == "production":
            self.opt_state = self.opt.init(self.params)
        self._initialized = True
        return self

    def n_params(self) -> int:
        assert self.params is not None, "call init(key) first"
        return sum(p.size for p in jax.tree.leaves(self.params))

    # ------------------------------------------------- checkpoint / resume
    def save_ckpt(self, params, opt_state, step: int) -> str:
        from repro.checkpoint import gc_checkpoints, save_checkpoint
        extra = {"step": step}
        extra.update(self.ckpt_meta or {})
        path = save_checkpoint(self.ckpt_dir, step,
                               {"params": params, "opt_state": opt_state},
                               extra=extra)
        if self.ckpt_keep:
            gc_checkpoints(self.ckpt_dir, self.ckpt_keep,
                           protect=self._protect_steps)
        return path

    def restore(self, ckpt_dir: Optional[str] = None,
                step: Optional[int] = None) -> int:
        """Load a step-boundary checkpoint and arm the next ``run`` to
        resume from it.  Returns the global step the run will continue at.

        Production mode: params/opt_state are restored bit-exactly (npz is
        lossless for every dtype the checkpointer handles) and ``run``
        skips the already-consumed loader batches — the loader is a pure
        function of its seed, so the replayed tail is exactly the killed
        run's remainder and the final state is ULP-identical to an
        uninterrupted run.  Sim mode: the orchestrator's full resume state
        (including the mid-epoch traversal cursor) is loaded lazily at the
        next ``run``."""
        from repro.checkpoint import latest_step, load_checkpoint
        ckpt_dir = ckpt_dir or self.ckpt_dir
        if ckpt_dir is None:
            raise ValueError("no ckpt_dir configured or given")
        if self.mode == "sim":
            self._sim_resume = (ckpt_dir, step)
            got = step if step is not None else latest_step(ckpt_dir)
            if got is None:
                raise FileNotFoundError(f"no checkpoints under {ckpt_dir}")
            return int(got)
        if self.params is None:
            self.init(jax.random.PRNGKey(0))       # structure template
        tree = {"params": self.params, "opt_state": self.opt_state}
        arrays, meta = load_checkpoint(ckpt_dir, tree, step)
        self.params = arrays["params"]
        self.opt_state = arrays["opt_state"]
        self.restored_meta = dict(meta["extra"])
        self._start_step = int(meta["extra"]["step"])
        # the live resume replays from this step: the GC must never take it
        self._protect_steps.add(self._start_step)
        return self._start_step

    # ------------------------------------------------- production: jit once
    def _build_step(self):
        """jit the TL step with train_shardings in/out + donated state."""
        if self._step_fn is not None:
            return self._step_fn
        cfg, mesh, shape = self.cfg, self.mesh, self.shape
        reassemble = self.reassembly != "none"
        step = make_train_step(self.model, cfg, self.opt,
                               remat_mode=self.remat_mode,
                               microbatch=self.microbatch,
                               reassembly=self.reassembly, mesh=mesh)
        with mesh:
            in_sh, out_sh = train_shardings(
                self.params, self.opt_state, cfg, mesh, shape,
                with_embeds=bool(cfg.frontend), with_perm=reassemble)
        donate = (0, 1) if self.donate else ()
        self._state_shardings = in_sh[:2]
        self._step_fn = jax.jit(step, in_shardings=in_sh,
                                out_shardings=out_sh, donate_argnums=donate)
        tok = tokens_pspec(mesh, shape.global_batch)
        sh = {"tokens": NamedSharding(mesh, tok),
              "targets": NamedSharding(mesh, tok)}
        if reassemble:
            sh["perm"] = NamedSharding(mesh, P(tok[0]))
            # perms must be local to each of the n_dp batch shards (a
            # permutation of the shard's own row block) so the shard_map'd
            # scatter in the loss never crosses a chip boundary
            self._n_perm_shards = 1
            if tok[0] is not None:
                for a in (tok[0] if isinstance(tok[0], tuple) else (tok[0],)):
                    self._n_perm_shards *= mesh.shape[a]
        if cfg.frontend:
            sh["embeds"] = NamedSharding(mesh, P(tok[0], None, None))
            # frontend stubs are constant zeros: materialize the sharded
            # device array once, not one host alloc + transfer per batch
            self._zero_embeds = jax.device_put(
                jnp.zeros((shape.global_batch, cfg.frontend_tokens,
                           cfg.d_model)), sh["embeds"])
        self._batch_shardings = sh
        return self._step_fn

    def _local_perm(self, positions):
        """Global batch positions -> shard-local rank perm.

        Block j (one data shard's rows) gets the ranks of its rows' global
        positions: scattering by them orders each shard's slice by global
        batch position — the orchestrator's reassembly restricted to the
        shard, with no cross-shard movement."""
        pos = np.asarray(positions)
        blocks = pos.reshape(self._n_perm_shards, -1)
        return np.argsort(np.argsort(blocks, axis=1),
                          axis=1).reshape(-1).astype(np.int32)

    @partial(jax.profiler.annotate_function, name="tl_put_batch")
    def _put_batch(self, host_batch):
        """host batch -> node-major device shards under tokens_pspec."""
        cfg, sh = self.cfg, self._batch_shardings
        host_batch = dict(host_batch)
        # the loader's global row positions only matter when reassembling;
        # they become the shard-local perm (and never ship to the device
        # themselves)
        positions = host_batch.pop("positions", None)
        if self.reassembly != "none":
            if positions is None:
                raise ValueError(
                    "reassembly needs the loader to emit 'positions' "
                    "(global batch positions of the node-major rows); "
                    "VirtualBatchLoader does so by default")
            host_batch["perm"] = self._local_perm(positions)
        out = {k: jax.device_put(np.asarray(v), sh[k])
               for k, v in host_batch.items()}
        if cfg.frontend and "embeds" not in out:
            out["embeds"] = self._zero_embeds
        return out

    def _device_batches(self, host_batches: Iterable):
        """The producer half: a 2-deep host->device prefetch queue.

        A background producer thread assembles batch k+1 from the loader and
        ``device_put``\\ s its shards while the main thread drives step k —
        so the ingest+transfer cost rides in the shadow of device compute
        even on backends whose chained dispatch is effectively synchronous
        (XLA:CPU).  ``PREFETCH_DEPTH`` slots bound the batches materialized
        ahead of the consumer (the double buffer: the batch being consumed
        plus the batch being prefetched) — the producer blocks on the slot
        semaphore *before* assembling, so memory stays bounded.  Order is a
        FIFO queue and every batch flows through the same jitted step, so
        the arithmetic is exactly the serial path's.
        """
        import queue
        import threading

        q: queue.Queue = queue.Queue()
        slots = threading.Semaphore(self.PREFETCH_DEPTH)
        stop = threading.Event()

        def produce():
            try:
                for hb in host_batches:
                    slots.acquire()
                    if stop.is_set():       # consumer died: don't keep
                        return              # materializing device batches
                    q.put(("item", self._put_batch(hb)))
                q.put(("done", None))
            except BaseException as e:  # noqa: BLE001 — re-raised by consumer
                q.put(("error", e))

        threading.Thread(target=produce, daemon=True,
                         name="tl-engine-prefetch").start()
        try:
            while True:
                kind, val = q.get()
                if kind == "done":
                    return
                if kind == "error":
                    raise val
                yield val
                slots.release()
        finally:
            # consumer abandoned mid-run (step raised, generator closed):
            # wake a slot-parked producer so the thread exits instead of
            # leaking with up to PREFETCH_DEPTH device batches pinned
            stop.set()
            slots.release()

    def _run_production(self, loader, steps: int) -> EngineResult:
        if self.params is None:
            if getattr(self, "_initialized", False):
                # a previous run failed after handing its buffers to the
                # donated step; silently restarting from PRNGKey(0) would
                # discard all prior progress without a trace
                raise RuntimeError(
                    "engine state was lost by a failed run; call "
                    "init(key) (or assign params/opt_state) before rerunning")
            self.init(jax.random.PRNGKey(0))
        self._loss_acc = {}
        if self.elastic:
            return self._run_production_elastic(loader, steps)
        return self._production_pass(loader, steps)

    # --------------------------------------------- elastic fault detection
    def _maybe_inject(self, step: int):
        """Consult the fault injector for this step over the *current*
        mesh's device ids; a non-OK verdict raises :class:`DeviceLost`.

        A kill raises before the step is issued (the state is not donated
        for that step — exactly a runtime device error surfacing at
        dispatch).  A hang is only observable through the watchdog: the
        simulated never-completing collective runs under
        :func:`call_with_deadline` and the resulting timeout is classified
        as a lost device.  Each verdict fires at most once (the fired-set),
        so the post-recovery replay makes progress past the drill step."""
        inj = self.device_faults
        if inj is None:
            return
        for d in self.mesh.devices.flatten():
            kind = inj.decide(step, d.id)
            if kind is None or (step, d.id, kind) in self._fired_faults:
                continue
            self._fired_faults.add((step, d.id, kind))
            t0 = time.perf_counter()
            if kind == HANG:
                if not self.watchdog_s or self.watchdog_s <= 0:
                    raise RuntimeError(
                        f"hang injected at step {step} on device {d.id} but "
                        "no watchdog is armed (watchdog_s <= 0): the run "
                        "would stall forever inside the collective")
                try:
                    call_with_deadline(
                        simulate_hang, (self.watchdog_s,),
                        deadline_s=self.watchdog_s,
                        what=f"step {step} (injected hang)")
                except WatchdogTimeout:
                    pass                      # classified: fall through
            err = DeviceLost(step, d.id, kind)
            err.detect_s = time.perf_counter() - t0
            raise err

    def _run_production_elastic(self, loader, steps: int) -> EngineResult:
        from repro.checkpoint import latest_step
        if iter(loader) is loader:
            raise ValueError(
                "elastic mode needs a re-iterable loader (got a bare "
                "iterator): recovery replays the stream from the rollback "
                "step, which requires restarting iteration")
        # step-0 anchor: a device lost before the first periodic checkpoint
        # must still have a rollback point
        if latest_step(self.ckpt_dir) is None:
            self.save_ckpt(self.params, self.opt_state, self._start_step)
            self._protect_steps.add(self._start_step)
        t_wall = time.perf_counter()
        while True:
            try:
                res = self._production_pass(loader, steps)
            except DeviceLost as e:
                self.recovery_log.append(self._recover(e))
                continue
            res.wall_s = time.perf_counter() - t_wall   # includes recoveries
            res.recovery = list(self.recovery_log)
            return res

    def _recover(self, e: DeviceLost) -> RecoveryReport:
        """One detect→reshrink→rollback→re-shard→re-jit recovery.

        Bit-equality contract: everything that defines the arithmetic after
        recovery — the checkpoint state, the reshrunk mesh's shardings, the
        re-jitted step, the replayed batches — is exactly what a fresh run
        launched from that checkpoint on that mesh would use, so the two are
        indistinguishable (``tests/test_elastic.py`` asserts bit-equal)."""
        from repro.checkpoint import latest_step, load_checkpoint
        from repro.launch.mesh import plan_reshrink
        t0 = time.perf_counter()
        lost = e.device
        if lost < 0:
            # the watchdog classified a stall but nothing identified the
            # chip (a real un-injected hang): drop the highest-id device —
            # a real deployment would health-probe first, but shrinking by
            # one guarantees forward progress either way
            lost = max(d.id for d in self.mesh.devices.flatten())
        # params may be donated-deleted buffers here; shapes/dtypes survive
        # deletion, which is all the divisibility validation needs
        template = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), self.params)
        plan = plan_reshrink(self.mesh, [lost],
                             global_batch=self.shape.global_batch,
                             params=template, cfg=self.cfg)
        t_plan = time.perf_counter()

        rollback = latest_step(self.ckpt_dir)
        if rollback is None:
            raise RuntimeError(
                "device lost but no valid checkpoint remains to roll back "
                f"to under {self.ckpt_dir}") from e
        self._protect_steps.add(rollback)
        old_shape = tuple(int(s) for s in self.mesh.devices.shape)
        self.mesh = plan.mesh
        # everything derived from the old mesh is now invalid
        self._step_fn = None
        self._batch_shardings = None
        self._zero_embeds = None
        self._jit_warm = False

        # restore + re-shard onto the shrunken mesh's NamedShardings
        names = jax.tree.map(lambda p: np.zeros((), np.float32),
                             {"params": self.params,
                              "opt_state": self.opt_state})
        arrays, _ = load_checkpoint(self.ckpt_dir, names, rollback)
        with self.mesh:
            in_sh, _ = train_shardings(
                arrays["params"], arrays["opt_state"], self.cfg, self.mesh,
                self.shape, with_embeds=bool(self.cfg.frontend),
                with_perm=self.reassembly != "none")
        self.params = jax.device_put(arrays["params"], in_sh[0])
        self.opt_state = jax.device_put(arrays["opt_state"], in_sh[1])
        jax.block_until_ready((self.params, self.opt_state))
        self._start_step = int(rollback)
        t_restore = time.perf_counter()

        report = RecoveryReport(
            step=e.step, device=e.device, cause=e.cause,
            rollback_step=int(rollback),
            rollback_depth=int(e.step - rollback),
            old_mesh_shape=old_shape, new_mesh_shape=plan.new_shape,
            detect_s=getattr(e, "detect_s", 0.0),
            plan_s=t_plan - t0, restore_s=t_restore - t_plan,
            extra={"degraded_axes": list(plan.degraded_axes),
                   "n_idle": plan.n_idle, "dropped_device": int(lost)})
        # rejit_s (first post-recovery step: recompile for the new mesh) and
        # replay_s (loader fast-forward) are filled in by the next pass
        self._pending_report = report
        return report

    @partial(jax.profiler.annotate_function, name="tl_run")
    def _production_pass(self, loader, steps: int) -> EngineResult:
        """One pass of the pjit step over ``loader``.  Host spans for the
        profiler: ``tl_run`` (the pass), ``tl_step`` (each dispatch, with
        its step number), ``tl_input_wait`` (waiting for the next batch),
        ``tl_put_batch`` (one batch's perm and ``device_put``, on the
        prefetch thread when pipelined) and ``tl_sync`` (the final wait
        and the losses' transfer)."""
        step_fn = self._build_step()
        start = self._start_step
        if start >= steps:
            # keep the resume cursor armed: disarming before raising would
            # turn a caught-and-retried run into a silent from-step-0
            # replay on top of the restored parameters
            raise ValueError(
                f"resume step {start} is past the requested budget "
                f"steps={steps}: nothing to run")
        self._start_step = 0

        # deterministic loader replay: skip the already-consumed prefix
        # eagerly (and time it — this is the recovery model's replay term)
        it = iter(loader)
        t_replay = time.perf_counter()
        try:
            for _ in range(start):
                next(it)
        except StopIteration:
            pass
        if self._pending_report is not None:
            self._pending_report.replay_s = time.perf_counter() - t_replay

        def host_batches():
            # steps is the *global* budget: a resumed run replays (skips)
            # the first `start` loader batches, then runs the rest
            for i, hb in enumerate(it, start=start):
                if i >= steps:
                    return
                yield hb

        if self.pipeline:
            batches = self._device_batches(host_batches())
        else:
            # strictly batch-serial oracle: the loader is not touched while
            # a step is in flight (the consumer blocks below)
            batches = map(self._put_batch, host_batches())
        batches = iter(batches)

        # device scalars keyed by global step, one host sync at the end;
        # a replayed step simply overwrites its pre-rollback entry
        losses = self._loss_acc
        params, opt_state = self.params, self.opt_state
        self.params = self.opt_state = None    # donated: drop stale refs
        # commit the state to the step's shardings before the first step:
        # fed fresh uncommitted arrays and then its own committed outputs,
        # the step would compile twice
        params, opt_state = jax.device_put((params, opt_state),
                                           self._state_shardings)
        armed = self.device_faults is not None or self.elastic
        deadline = self.watchdog_s if (armed and self.watchdog_s
                                       and self.watchdog_s > 0) else None
        t0 = time.perf_counter()
        input_wait = 0.0
        k = start
        try:
            for k in itertools.count(start):
                t_wait = time.perf_counter()
                with jax.profiler.TraceAnnotation("tl_input_wait"):
                    batch = next(batches, None)
                input_wait += time.perf_counter() - t_wait
                if batch is None:
                    break
                self._maybe_inject(k)          # raises DeviceLost on verdict
                t_step = time.perf_counter()
                with jax.profiler.StepTraceAnnotation("tl_step", step_num=k):
                    if deadline is not None and self._jit_warm:
                        # supervised dispatch: a hung collective surfaces as
                        # a WatchdogTimeout instead of stalling the run
                        # forever.  The warmup step (fresh jit: unbounded
                        # compile time) runs unsupervised so a slow compile
                        # is never misclassified as a hang.
                        params, opt_state, loss = call_with_deadline(
                            step_fn, (params, opt_state, batch),
                            deadline_s=deadline, what=f"step {k}")
                    else:
                        params, opt_state, loss = step_fn(params, opt_state,
                                                          batch)
                self._jit_warm = True
                if self._pending_report is not None:
                    # first post-recovery step: its wall time is the re-jit
                    # cost (recompile for the reshrunk mesh)
                    jax.block_until_ready(loss)
                    self._pending_report.rejit_s = (time.perf_counter()
                                                    - t_step)
                    self._pending_report = None
                losses[k] = loss
                if not self.pipeline:
                    jax.block_until_ready(loss)
                if self.log_every and k % self.log_every == 0:
                    # the only mid-run host sync, at the caller's cadence
                    print(f"step {k:4d} loss {float(loss):.4f} "
                          f"({time.perf_counter() - t0:.1f}s)")
                if (self.ckpt_dir and self.ckpt_every
                        and (k + 1) % self.ckpt_every == 0):
                    # step-boundary checkpoint: forces a host sync of the
                    # state at the caller's chosen cadence (the prefetch
                    # queue keeps producing meanwhile)
                    self.save_ckpt(params, opt_state, k + 1)
        except WatchdogTimeout as t:
            # a real (un-injected) stall: classify as a lost device with no
            # identified chip; the elastic loop (or the caller) decides what
            # to drop.  The worker thread still holds the donated buffers,
            # so the engine state is gone either way — exactly a real hang.
            err = DeviceLost(k, -1, HANG)
            err.detect_s = deadline or 0.0
            raise err from t
        finally:
            # on failure these may point at donated (deleted) buffers — a
            # later use then raises loudly instead of silently restarting
            self.params, self.opt_state = params, opt_state
        with jax.profiler.TraceAnnotation("tl_sync"):
            jax.block_until_ready(params)
            wall = time.perf_counter() - t0
            order = sorted(losses)
            loss_arr = (np.asarray(jax.device_get([losses[i] for i in order]),
                                   np.float32)
                        if order else np.zeros((0,), np.float32))
        return EngineResult(losses=loss_arr, steps=len(order), wall_s=wall,
                            params=params, opt_state=opt_state,
                            input_wait_s=input_wait)

    # ---------------------------------------------------------- sim facade
    def _run_sim(self, shards, epochs: int) -> EngineResult:
        from repro.core.node import TLNode
        from repro.core.orchestrator import TLOrchestrator
        from repro.core.plan import PlanSpec
        from repro.core.transport import Transport

        if self.orchestrator is not None and shards is not self._sim_shards:
            # the cached orchestrator's TLNodes were built from the first
            # run's shards; silently training on those while the caller
            # hands in different data would fit the wrong dataset
            raise ValueError(
                "sim-mode engine is bound to the shards of its first run; "
                "pass the same shards object to continue training, or build "
                "a fresh Engine for a different dataset")
        if self.orchestrator is None:
            self._sim_shards = shards
            nodes = [TLNode(i, self.model, s.x, s.y, jit_visits=self.fused)
                     for i, s in enumerate(shards)]
            common = dict(
                plan=PlanSpec(seed=self.seed, batch_size=self.batch_size),
                fused=self.fused, donate=False,
                cache_model_per_epoch=self.cache_model_per_epoch,
                reassembly=("xla" if self.reassembly == "none"
                            else self.reassembly))
            if self.hierarchy:
                from repro.core.hierarchy import HierarchicalOrchestrator
                self.orchestrator = HierarchicalOrchestrator(
                    self.model, nodes, self.opt,
                    self.transport or Transport(),
                    n_subtrees=self.hierarchy, **common)
            else:
                self.orchestrator = TLOrchestrator(
                    self.model, nodes, self.opt,
                    self.transport or Transport(),
                    pipelined=self.pipeline, **common)
            if self.params is not None:       # caller-provided init (eq. 13)
                self.orchestrator.params = self.params
                self.orchestrator.opt_state = self.opt.init(self.params)
            else:
                self.orchestrator.initialize(jax.random.PRNGKey(self.seed))
        orch = self.orchestrator

        start_batch = 0
        if self._sim_resume is not None:
            ckpt_dir, step = self._sim_resume
            self._sim_resume = None
            start_batch = orch.restore(ckpt_dir, step)

        epoch_stats, t0 = [], time.perf_counter()
        for e in range(epochs):
            # first (possibly partial) epoch resumes at the checkpoint's
            # mid-epoch traversal cursor; later epochs run in full
            epoch_stats.append(orch.train_epoch(
                start_batch=start_batch if e == 0 else 0))
            if self.ckpt_dir:
                orch.save(self.ckpt_dir)     # epoch-boundary checkpoint
        wall = time.perf_counter() - t0
        flat = [s for ep in epoch_stats for s in ep]
        self.params = orch.params
        return EngineResult(
            losses=np.asarray([s.loss for s in flat], np.float32),
            steps=len(flat), wall_s=wall, params=orch.params,
            opt_state=orch.opt_state, stats=flat, epoch_stats=epoch_stats)

    # ----------------------------------------------------------------- run
    def run(self, loader, steps: Optional[int] = None, *,
            epochs: Optional[int] = None) -> EngineResult:
        """Drive training.

        Production mode: ``loader`` yields host batch dicts (e.g. a
        ``VirtualBatchLoader``); ``steps`` bounds the run.  Sim mode:
        ``loader`` is a sequence of per-node shards (anything with ``.x`` /
        ``.y``) and ``epochs`` counts orchestrator epochs.
        """
        if self.mode == "production":
            if steps is None:
                raise ValueError("production mode needs steps=")
            if epochs is not None:
                raise ValueError("production mode counts steps, not epochs")
            return self._run_production(loader, steps)
        if steps is not None:
            raise ValueError("sim mode counts epochs, not steps")
        return self._run_sim(loader, epochs if epochs is not None else 1)
