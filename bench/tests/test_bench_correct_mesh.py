"""The training driver's output check on a (2, 2) data x model mesh: the
sharded TL step passes it, agrees with the one-device step, and the two
exchanges the mesh adds are each needed: without the data axis's gradient
exchange, or without the sum of the tensor-parallel partial products, the
check fails.  The four-chip cell's readers, once loaded, give the
reference's Adam state its weights' shardings.

Four virtual CPU devices in one subprocess per case (the forced device
count never reaches the other tests); the training cell's files cut to
tiny widths, with 16 query heads over 2 KV heads, so that each ``model``
shard holds one KV head and its 8 query heads, as each chip holds 4 KV
heads and their 32 query heads of DeepSeek LLM 67B on such a mesh."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = textwrap.dedent("""
    import json
    import os
    import sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import repro.core.tl_step as tl_step
    import repro.launch.engine as engine
    from repro.models import attention, blocks, layers
    from bench.lib import train
    from bench.tests import tiny

    def cell(mesh=(2, 2)):
        c = tiny.train_cell()
        c.config = dict(c.config, num_attention_heads=16,
                        num_key_value_heads=2)
        c.traffic = dict(c.traffic, mesh=list(mesh))
        return c

    def first_steps(c):
        tr = train.Trainer(c)
        docs = tr.corpus(tiny.SEED)
        it = iter(tr.loader(docs, tiny.SEED))
        batches = [next(it) for _ in range(train.CHECK_STEPS)]
        tr.reset(tiny.SEED)
        return tr.first_steps(tiny.SEED, batches)

    def no_data_exchange(model, cfg, opt, *, mesh, reassembly="none",
                         remat_mode="tl", **_):
        # each data shard's rows give the gradient for the slices of the
        # parameters that shard holds, as if the reduce-scatter were gone
        from repro.dist.sharding import param_specs
        loss_fn = tl_step.tl_loss_fn(model, cfg, remat_mode,
                                     reassembly=reassembly)
        n = mesh.shape["data"]

        def step(params, opt_state, batch):
            rows = batch["tokens"].shape[0] // n
            own = [jax.value_and_grad(loss_fn)(params, {
                k: v[i * rows:(i + 1) * rows] for k, v in batch.items()})
                for i in range(n)]

            def pick(spec, *grads):
                axes = [a for a, e in enumerate(spec) if e == "data"]
                if not axes:
                    return grads[0]
                a, w = axes[0], grads[0].shape[axes[0]] // n
                return jnp.concatenate([jax.lax.slice_in_dim(
                    g, i * w, (i + 1) * w, axis=a)
                    for i, g in enumerate(grads)], axis=a)
            grads = jax.tree.map(pick, param_specs(params, cfg, mesh),
                                 *[g for _, g in own],
                                 is_leaf=lambda x: isinstance(x, P))
            params, opt_state = opt.update(params, grads, opt_state)
            return params, opt_state, sum(l for l, _ in own) / n
        return step

    def partial_sums(mesh):
        # the row-parallel product of each model shard, never summed
        def local(x, w):
            return jnp.einsum("...k,kn->...n", x, w)

        def mm(x, w):
            xs = P(*([None] * (x.ndim - 1)), "model")
            return jax.shard_map(local, mesh=mesh, in_specs=(xs, P("model")),
                                 out_specs=P(), axis_names={"model"},
                                 check_vma=False)(x, w)

        def gqa(params, cfg, x, **_):
            B, S, _ = x.shape
            pos = jnp.arange(S, dtype=jnp.int32)
            q, k, v = attention.gqa_project(params, cfg, x,
                                            jnp.broadcast_to(pos, (B, S)))
            hd = cfg.resolved_head_dim
            out = attention.attend(q, k, v, pos, pos, cfg.sliding_window,
                                   hd ** -0.5)
            return mm(out.reshape(B, S, -1), params["w_o"]), None

        def swiglu(params, x):
            g = jnp.einsum("...d,df->...f", x, params["w_gate"])
            u = jnp.einsum("...d,df->...f", x, params["w_up"])
            return mm(layers.swiglu_act(g, u), params["w_down"])
        return gqa, swiglu

    def result(name, **kw):
        print("RESULT", json.dumps({"case": name, **kw}), flush=True)

    def load_four_chip_readers():
        # as bench/run.py loads the four-chip cell's readers before its
        # driver runs: they place the reference's Adam state as its weights
        from bench.lib import spec
        cell67 = spec.resolve(spec.load_benchmark(), "train.ds67b.tp4")
        for m in cell67.per_layer:
            spec.load_module(m["name"])

    for case in sys.argv[1:]:
        if case == "program":
            load_four_chip_readers()
            out = train.run(cell(), tiny.SEED, 0.5, False, tiny.now())
            result(case, readings=out["readings"],
                   window_compiles=out["host"]["window_compiles"],
                   setup_compiles=out["host"]["setup_compiles"])
        elif case == "moments":
            from bench.lib import reference
            from bench.lib.weights import make_weights
            tr = train.Trainer(cell())
            w = make_weights(tr.c, tiny.SEED, tr.param_sh)
            placed = {}
            for when in ("before", "after"):
                if when == "after":
                    load_four_chip_readers()
                st = reference.adamw_init(w)
                placed[when] = all(
                    x.sharding == a.sharding and x.dtype == jnp.float32
                    and not bool(jnp.any(x))
                    for mo in (st["m"], st["v"])
                    for x, a in zip(jax.tree.leaves(mo), jax.tree.leaves(w)))
            result(case, **placed)
        elif case == "one_device":
            mine, one = first_steps(cell()), first_steps(cell((1, 1)))
            result(case, readings={**train.compare(mine, one),
                                   "rows_off_corpus": 0})
        elif case == "no_data_exchange":
            tl_step.make_train_step = engine.make_train_step = \\
                no_data_exchange
            out = train.run(cell(), tiny.SEED, 0.5, False, tiny.now())
            result(case, readings=out["readings"])
        elif case == "partial_sums":
            from repro.launch.mesh import make_debug_mesh
            gqa, swiglu = partial_sums(make_debug_mesh(2, 2))
            attention.gqa_apply, blocks.swiglu = gqa, swiglu
            out = train.run(cell(), tiny.SEED, 0.5, False, tiny.now())
            result(case, readings=out["readings"])
""")

CASES = ("program", "moments", "one_device", "no_data_exchange",
         "partial_sums")


@pytest.fixture(scope="module")
def results():
    """Each case in a subprocess of its own (the faults patch the
    program), all of them started at once."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    procs = {c: subprocess.Popen([sys.executable, "-c", SCRIPT, c], env=env,
                                 cwd=ROOT, stdout=subprocess.PIPE,
                                 stderr=subprocess.PIPE, text=True)
             for c in CASES}
    out = {}
    for c, p in procs.items():
        stdout, stderr = p.communicate(timeout=600)
        assert p.returncode == 0, stderr[-3000:]
        line = [ln for ln in stdout.splitlines() if ln.startswith("RESULT ")]
        out[c] = json.loads(line[-1][len("RESULT "):])
    return out


def _correct(readings):
    from bench.lib.common import report_checks
    from bench.tests import tiny
    return report_checks(tiny.checks(readings))


def test_sharded_step_passes(results):
    r = results["program"]
    assert r["window_compiles"] == 0
    assert _correct(r["readings"])


def test_reference_moments_take_their_weights_sharding(results):
    """Loading the four-chip cell's readers, as the harness does, gives the
    reference's Adam moments their weights' shardings (on the four-chip
    host each moment whole on chip 0 would not fit)."""
    r = results["moments"]
    assert (r["before"], r["after"]) == (False, True)


def test_sharded_step_equals_one_device(results):
    assert _correct(results["one_device"]["readings"])


def test_gradient_exchange_over_data_left_out_fails(results):
    assert not _correct(results["no_data_exchange"]["readings"])


def test_tensor_parallel_partial_sums_left_unreduced_fails(results):
    assert not _correct(results["partial_sums"]["readings"])
