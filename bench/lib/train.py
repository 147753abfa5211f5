"""The training driver: TL production steps through ``Engine.run``.

Set-up builds one ``Engine`` with the benchmark's weights, drives it
through the cell's first three steps (the output check reads their losses,
the first gradient from the optimizer's state, and the parameters' change),
times a warm-up call, and hands the same engine to the window: one
``Engine.run`` call over the cell's loader, its step count sized from the
warm-up so that it lasts about ``--seconds``.  The reference runs the same
three steps once the window has closed, on the benchmark's own corpus rows.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.lib import reference as ref
from bench.lib.clock import CompileClock
from bench.lib.common import memory_peak, norms_fn, traced, worst_gap
from bench.lib.weights import make_weights, model_config

CHECK_STEPS = 3


def make_corpus(seed: int, n_docs: int, seq: int, vocab: int,
                styles: int, zipf_a: float) -> np.ndarray:
    """Documents of ``seq + 1`` tokens: each draws Zipf-distributed ranks
    through one of ``styles`` random orderings of the vocabulary, so that
    rows differ in their statistics as well as in their tokens."""
    rng = np.random.default_rng(int(seed))
    perms = np.stack([rng.permutation(vocab) for _ in range(styles)])
    style = rng.integers(0, styles, n_docs)
    ranks = np.minimum(rng.zipf(zipf_a, (n_docs, seq + 1)) - 1, vocab - 1)
    return perms[style[:, None], ranks].astype(np.int32)


def make_mesh(shape):
    n = int(np.prod(shape))
    if n == 1:
        return Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"))
    from repro.launch.mesh import make_debug_mesh
    return make_debug_mesh(*shape)


class Trainer:
    """The program under test, built once for a cell."""

    def __init__(self, cell):
        from repro.configs.base import InputShape
        from repro.data.pipeline import VirtualBatchLoader, shard_corpus
        from repro.launch.engine import Engine
        from repro.models import build_model
        from repro.optim import adamw

        self.c, self.t = cell.config, cell.traffic
        t, o = self.t, self.t["optimizer"]
        self.cfg = model_config(self.c, cell.config_name)
        self.model = build_model(self.cfg)
        self.opt = adamw(o["lr"], weight_decay=o["weight_decay"], b1=o["b1"],
                         b2=o["b2"], eps=o["eps"], clip_norm=o.get("clip_norm"))
        self.mesh = make_mesh(t["mesh"])
        self.devices = list(self.mesh.devices.flat)
        self.engine = Engine(self.model, self.cfg, self.opt, self.mesh,
                             InputShape(cell.name, t["seq"], t["global_batch"],
                                        "train"),
                             pipeline=t["pipeline"], reassembly=t["reassembly"])
        self._loader_cls, self._shard = VirtualBatchLoader, shard_corpus
        with self.mesh:
            from repro.dist.sharding import param_specs
            shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
            specs = param_specs(shapes, self.cfg, self.mesh)
        self.param_sh = jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                                     specs, is_leaf=lambda x: isinstance(x, P))
        self.opt_sh = {"step": NamedSharding(self.mesh, P()),
                       "m": self.param_sh, "v": self.param_sh}
        self._opt_init = jax.jit(self.opt.init, out_shardings=self.opt_sh)
        self.norms = norms_fn()
        self._delta_norms = jax.jit(
            lambda a, b: self.norms(jax.tree.map(jnp.subtract, a, b)))
        mine = jax.eval_shape(lambda: make_weights(self.c, 0))
        if (jax.tree.structure(mine) != jax.tree.structure(shapes)
                or jax.tree.leaves(jax.tree.map(
                    lambda a, b: a.shape != b.shape, mine, shapes)).count(True)):
            raise SystemExit("the program's parameter tree is not the layout "
                             "bench/lib/weights.py makes")

    def corpus(self, seed: int) -> np.ndarray:
        t = self.t
        return make_corpus(seed, t["docs"], t["seq"], self.c["vocab_size"],
                           t["corpus"]["styles"], t["corpus"]["zipf_a"])

    def loader(self, docs: np.ndarray, seed: int):
        t = self.t
        return self._loader_cls(self._shard(docs, t["nodes"], seed=seed),
                                t["global_batch"], seed=seed)

    def reset(self, seed: int):
        """Fresh weights from ``seed`` and a fresh optimizer state."""
        eng = self.engine
        eng.params = eng.opt_state = None
        gc.collect()
        eng.params = make_weights(self.c, seed, self.param_sh)
        eng.opt_state = self._opt_init(eng.params)

    def first_steps(self, seed: int, batches) -> dict:
        """Steps 1..3 through ``Engine.run`` on ``batches``: each loss, the
        per-layer norm of the first gradient (from Adam's first moment
        after one step) and of the parameters' change after three."""
        eng, b1 = self.engine, self.t["optimizer"]["b1"]
        r1 = eng.run(batches[:1], steps=1)
        g1 = np.asarray(self.norms(eng.opt_state["m"])) / (1.0 - b1)
        t0 = time.perf_counter()
        r2 = eng.run(batches[1:CHECK_STEPS], steps=CHECK_STEPS - 1)
        step_s = (time.perf_counter() - t0) / (CHECK_STEPS - 1)
        p0 = make_weights(self.c, seed, self.param_sh)
        delta = np.asarray(self._delta_norms(eng.params, p0))
        del p0
        return {"losses": np.concatenate([r1.losses, r2.losses]).tolist(),
                "grad": g1, "delta": delta, "step_s": step_s}

    def free(self):
        self.engine.params = self.engine.opt_state = None
        gc.collect()


def corpus_rows(docs: np.ndarray, batches) -> tuple:
    """The reference's own batches for the program's: each row that the
    program's loader fed is looked up in the benchmark's corpus, and the
    reference takes that document from the corpus.  Returns those batches
    and the number of rows that are not distinct documents of the corpus
    (altered, or fed twice); such rows are left out of the reference."""
    index = {d.tobytes(): i for i, d in enumerate(docs)}
    seen, off, out = set(), 0, []
    for b in batches:
        rows = []
        for x, y in zip(np.asarray(b["tokens"]), np.asarray(b["targets"])):
            doc = np.concatenate([x[:1], y]).astype(docs.dtype)
            i = index.get(doc.tobytes())
            if i is None or i in seen or not np.array_equal(x[1:], y[:-1]):
                off += 1
                continue
            seen.add(i)
            rows.append(i)
        d = docs[rows]
        out.append({"tokens": d[:, :-1], "targets": d[:, 1:]})
    return out, off


def reference_steps(c: dict, t: dict, seed: int, batches,
                    precision: str = "highest", shardings=None,
                    half_batch: bool = False) -> dict:
    """The plain reference through the same three steps, at ``highest``
    precision, or at ``fp8`` for the control.  ``half_batch`` plants the
    fault of a step that leaves out half of its rows."""
    w = make_weights(c, seed, shardings)
    grad_fn = ref.make_grad_fn(c, precision)
    update = ref.make_adamw_update(t["optimizer"])
    norms = norms_fn()
    st = ref.adamw_init(w)
    m, v = st["m"], st["v"]
    losses, g1 = [], None
    rows = t["reference_rows_per_block"]
    for k, b in enumerate(batches[:CHECK_STEPS]):
        tok, tgt = b["tokens"], b["targets"]
        if half_batch:
            tok, tgt = tok[: len(tok) // 2], tgt[: len(tgt) // 2]
        loss, g = ref.loss_and_grad(grad_fn, w, tok, tgt, rows)
        losses.append(loss)
        w, m, v, g = update(w, g, m, v, k + 1)
        if k == 0:
            g1 = np.asarray(norms(g))
        del g
    del m, v
    gc.collect()
    w0 = make_weights(c, seed, shardings)
    delta = np.asarray(jax.jit(lambda a, b: norms(
        jax.tree.map(jnp.subtract, a, b)))(w, w0))
    del w, w0
    gc.collect()
    return {"losses": losses, "grad": g1, "delta": delta}


def compare(prog: dict, refr: dict) -> dict:
    """The loss, gradient and change numbers of the output check."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(refr["losses"])
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    grad_gap = worst_gap(prog["grad"], refr["grad"])
    # leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: left out of the change by this rule, not by name
    g = np.asarray(refr["grad"])
    moved = g >= 1e-3 * np.median(g)
    delta_gap = worst_gap(prog["delta"], refr["delta"], moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "delta_gap": delta_gap}


def run(cell, seed: int, seconds: float, trace: bool, t_start: float):
    t = cell.traffic
    marks = [("start", time.perf_counter())]
    clock = CompileClock()
    tr = Trainer(cell)
    docs = tr.corpus(seed)
    loader = tr.loader(docs, seed)
    it = iter(loader)
    batches = [next(it) for _ in range(CHECK_STEPS)]
    marks.append(("build", time.perf_counter()))
    tr.reset(seed)
    marks.append(("weights", time.perf_counter()))
    prog = tr.first_steps(seed, batches)
    marks.append(("first_steps", time.perf_counter()))
    eng = tr.engine
    warm_steps = 2
    t0 = time.perf_counter()
    eng.run(loader, steps=warm_steps)
    # a call's own start (first batch, dispatch) lengthens a short call now
    # and then: the quicker of two two-step calls sizes the window
    step_s = min(prog["step_s"], (time.perf_counter() - t0) / warm_steps)
    n_steps = max(4, math.ceil(seconds / step_s))
    setup_compile_s, setup_compiles, setup_loads = clock.take()
    marks.append(("warmup", time.perf_counter()))
    phases = {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}

    setup_s = time.perf_counter() - t_start
    with traced(trace) as tb:
        t0 = time.perf_counter()
        res = eng.run(loader, steps=n_steps)
        wall = time.perf_counter() - t0
    window_compile_s, window_compiles, window_loads = clock.take()
    peak = memory_peak(tr.devices)
    losses = np.asarray(res.losses)
    del res
    tr.free()

    ref_batches, off = corpus_rows(docs, batches)
    refr = reference_steps(tr.c, t, seed, ref_batches, "highest",
                           tr.param_sh if len(tr.devices) > 1 else None)
    readings = {**compare(prog, refr), "rows_off_corpus": off}
    tokens = n_steps * t["global_batch"] * t["seq"]
    return {
        "attempted": n_steps,
        "failed": int(np.sum(~np.isfinite(losses))),
        "readings": readings,
        "e2e": {"train_tokens_per_s": tokens / wall, "setup_s": setup_s},
        "devices": tr.devices,
        "memory_peak_bytes": peak,
        "compact_trace": tb.get("compact"),
        "host": {"window_s": wall, "steps": n_steps,
                 "tokens_per_s": tokens / wall,
                 "mesh": list(t["mesh"]),
                 "setup_phases_s": phases,
                 "setup_compiles": setup_compiles,
                 "setup_cache_loads": setup_loads,
                 "setup_compile_s": setup_compile_s,
                 "window_compiles": window_compiles,
                 "window_cache_loads": window_loads,
                 "window_compile_s": window_compile_s,
                 "losses_first": prog["losses"],
                 "losses_ref": refr["losses"]},
    }
