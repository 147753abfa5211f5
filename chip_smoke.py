"""Drive the TL trainer and the paged server once on one TPU chip.

    python chip_smoke.py                # one chip: trainer + server phases
    python chip_smoke.py --four-chips   # trainer only: (2, 2) mesh over all
                                        # four chips vs (1, 1) over device 0

Both phases run deepseek-7b at its published widths (d_model 4096, 32 MHA
heads of 128, d_ff 11008) with the depth cut to 2 layers and random
weights from ``--seed``, through the entry points a user calls:

* trainer — ``repro.launch.engine.Engine(mode="production")`` on the
  ``resolve_mesh("debug")`` mesh, vocabulary sliced to 1/8 (12 800 ids;
  the synthetic corpus draws from the slice), seq 1024, global batch 8
  over 4 TL nodes.  One warm-up step (compiles), then 5 timed steps, once
  with ``reassembly="xla"`` and once with ``"pallas"`` from the same init;
  every loss must be finite and the two runs' losses equal to f32 ULP.
* server — ``repro.serve.ServeEngine(attention="paged")`` with the full
  102 400 vocabulary, page size 16 and a 2 GiB page pool; two rounds of 8
  requests (prompt 512, 32 new tokens each), the first of which compiles.
  Every request must finish on length with all its tokens, and one
  ``paged_decode_attention`` call at these shapes must match the dense
  reference.

Everything runs in this one process, which holds the chip throughout.  The
script refuses to run anywhere but a TPU and never interprets a kernel.
Timings it prints are smoke numbers, not benchmark results.  The last line
of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import InputShape  # noqa: E402
from repro.data.pipeline import (VirtualBatchLoader, shard_corpus,  # noqa: E402
                                 synthetic_corpus)
from repro.kernels import resolve_interpret  # noqa: E402
from repro.kernels.paged_attention import (paged_decode_attention,  # noqa: E402
                                           paged_decode_attention_ref)
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.engine import Engine  # noqa: E402
from repro.launch.mesh import resolve_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.serve import Request, ServeEngine  # noqa: E402

EPS32 = float(np.finfo(np.float32).eps)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileClock:
    """Counts XLA backend compiles and sums their seconds (a persistent
    cache hit is not a backend compile)."""

    def __init__(self):
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def take(self) -> tuple:
        taken = (self.seconds, self.count)
        self.seconds, self.count = 0.0, 0
        return taken


# ----------------------------------------------------------------- trainer

def trainer_config():
    base = get_config("deepseek-7b")
    return dataclasses.replace(base, name="deepseek-7b-2l-vocab8",
                               n_layers=2, vocab_size=base.vocab_size // 8)


def train_once(cfg, mesh, reassembly, *, seq, batch, nodes, steps, seed,
               clock):
    """One warm-up step (compiles) then ``steps`` timed steps through
    ``Engine.run``; the serial loader path blocks on every loss, so the
    timed run's wall over ``steps`` is the per-step wall time."""
    docs = synthetic_corpus(nodes * 32, seq, cfg.vocab_size, seed=seed + 1)
    loader = VirtualBatchLoader(shard_corpus(docs, nodes), batch, seed=seed)
    eng = Engine(build_model(cfg), cfg, adamw(3e-4, clip_norm=1.0), mesh,
                 InputShape("chip_smoke", seq, batch, "train"),
                 pipeline=False, reassembly=reassembly)
    eng.init(jax.random.PRNGKey(seed))
    clock.take()
    warm = eng.run(loader, steps=1)
    compile_s, _ = clock.take()
    timed = eng.run(loader, steps=steps)
    timed_compile_s, timed_compiles = clock.take()
    losses = np.concatenate([warm.losses, timed.losses]).astype(np.float64)
    out = {"reassembly": reassembly,
           "mesh": list(mesh.devices.shape),
           "compile_s": compile_s,
           "first_step_s": warm.wall_s,
           "step_s": timed.wall_s / steps,
           "compiles_in_timed_steps": timed_compiles,
           "compile_s_in_timed_steps": timed_compile_s,
           "losses": losses.tolist()}
    return out, timed.params


def losses_ulp_equal(a, b) -> bool:
    """The test_engine criterion: within 16 f32 eps of the loss scale."""
    a, b = np.asarray(a), np.asarray(b)
    return bool(np.all(np.abs(a - b) <= 16 * EPS32 * np.maximum(1.0,
                                                                np.abs(a))))


def trainer_phase(cfg, mesh, *, seq, batch, nodes, steps, seed, clock):
    runs = {}
    for reassembly in ("xla", "pallas"):
        out, params = train_once(cfg, mesh, reassembly, seq=seq, batch=batch,
                                 nodes=nodes, steps=steps, seed=seed,
                                 clock=clock)
        del params
        gc.collect()
        log("trainer", **out)
        runs[reassembly] = out
    for reassembly, out in runs.items():
        check(bool(np.all(np.isfinite(out["losses"]))),
              f"non-finite loss under reassembly={reassembly}")
    check(losses_ulp_equal(runs["xla"]["losses"], runs["pallas"]["losses"]),
          "xla and pallas reassembly losses differ beyond f32 ULP: "
          f"{runs['xla']['losses']} vs {runs['pallas']['losses']}")
    return runs


# ------------------------------------------------------------------ server

def server_config():
    return dataclasses.replace(get_config("deepseek-7b"),
                               name="deepseek-7b-2l", n_layers=2)


def paged_kernel_vs_ref(cfg, *, batch, page, max_len, seed):
    """One decode-attention call at the server's shapes against the dense
    reference (f32 pools, random block tables and ragged lengths)."""
    r = np.random.default_rng(seed)
    H, KV, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    maxp = -(-max_len // page)
    P = batch * maxp + 1
    k = jnp.asarray(r.normal(size=(P, KV, page, d)), jnp.float32)
    v = jnp.asarray(r.normal(size=(P, KV, page, d)), jnp.float32)
    q = jnp.asarray(r.normal(size=(batch, H, d)), jnp.float32)
    bt = jnp.asarray(r.permutation(np.arange(1, P)).reshape(batch, maxp),
                     jnp.int32)
    lens = jnp.asarray(r.integers(1, max_len + 1, batch), jnp.int32)
    out = paged_decode_attention(q, k, v, bt, lens, scale=d ** -0.5,
                                 interpret=False)
    with jax.default_matmul_precision("highest"):
        ref = paged_decode_attention_ref(q, k, v, bt, lens, scale=d ** -0.5)
    err = float(jnp.max(jnp.abs(out - ref)))
    # Both sides are f32 on the MXU (the kernel's dots run at HIGHEST, the
    # reference under highest matmul precision).  What differs is the
    # order of the softmax: the kernel rescales page by page (34 pages
    # online) while the reference normalizes once, and Mosaic's exp is not
    # XLA's.  Each costs a few f32 ulps of the O(1) outputs; 1e-4 bounds
    # that with room, while a wrong page, head or mask moves outputs by O(1).
    tol = 1e-4
    check(bool(jnp.allclose(out, ref, atol=tol, rtol=tol)),
          f"paged_decode_attention differs from the reference: max |err| "
          f"{err:.3e} > {tol}")
    return err


def serve_round(eng, prompts, rid0, gen):
    t0 = time.perf_counter()
    now = eng.clock()
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=rid0 + i, prompt=p, max_new_tokens=gen,
                           arrival=now))
    steps0 = eng.n_steps
    results = eng.run()
    wall = time.perf_counter() - t0
    mine = [results[rid0 + i] for i in range(len(prompts))]
    for res in mine:
        check(res.finish_reason == "length" and len(res.tokens) == gen,
              f"request {res.rid} ended {res.finish_reason!r} with "
              f"{len(res.tokens)}/{gen} tokens")
    ttft = [res.token_times[0] - res.arrival for res in mine]
    gaps = np.concatenate([np.diff(res.token_times) for res in mine])
    return {"requests": len(mine), "engine_steps": eng.n_steps - steps0,
            "wall_s": wall, "ttft_mean_s": float(np.mean(ttft)),
            "ttft_max_s": float(np.max(ttft)),
            "decode_step_mean_s": float(np.mean(gaps)),
            "tokens_per_s": len(mine) * gen / wall}


def server_phase(cfg, *, requests, prompt_len, gen, page, num_pages, seed,
                 clock):
    max_len = prompt_len + gen
    err = paged_kernel_vs_ref(cfg, batch=requests, page=page,
                              max_len=max_len, seed=seed)
    log("paged_attention_vs_ref", max_abs_err=err)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    eng = ServeEngine(model, cfg, params, num_pages=num_pages,
                      page_size=page, max_slots=requests, max_len=max_len,
                      attention="paged", seed=seed, interpret=False)
    pool_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.pages))
    r = np.random.default_rng(seed)
    prompts = r.integers(0, cfg.vocab_size, (2 * requests, prompt_len),
                         dtype=np.int32)
    clock.take()
    cold = serve_round(eng, prompts[:requests], 0, gen)
    cold["compile_s"], cold["compiles"] = clock.take()
    warm = serve_round(eng, prompts[requests:], requests, gen)
    warm["compile_s"], warm["compiles"] = clock.take()
    for name, rnd in (("cold", cold), ("warm", warm)):
        log("server", round=name, pool_gib=pool_bytes / 2 ** 30,
            page_size=page, prompt_len=prompt_len, new_tokens=gen,
            note="smoke numbers, not benchmark results", **rnd)
    return {"cold": cold, "warm": warm}


# ------------------------------------------------------------- four chips

def check_param_sharding(params, mesh):
    """Every leaf whose spec names a mesh axis is split across the mesh's
    devices; at least one leaf is."""
    n_dev = mesh.devices.size
    sharded = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        sh = leaf.sharding
        name = jax.tree_util.keystr(path)
        check(len(sh.device_set) == n_dev,
              f"{name} lives on {len(sh.device_set)} devices, not {n_dev}")
        if any(ax is not None for ax in sh.spec):
            check(sh.shard_shape(leaf.shape) != leaf.shape,
                  f"{name} has spec {sh.spec} but is not split")
            check(len({s.device for s in leaf.addressable_shards}) == n_dev,
                  f"{name} has shards on fewer than {n_dev} devices")
            sharded += 1
    check(sharded > 0, "no parameter is sharded on the (2, 2) mesh")
    return sharded


def four_chip_phase(cfg, *, seq, batch, nodes, steps, seed, clock):
    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    one = jax.sharding.Mesh(np.array(devs[:1]).reshape(1, 1),
                            ("data", "model"))
    four = resolve_mesh("debug")
    check(four.devices.shape == (2, 2),
          f"debug mesh is {four.devices.shape}, not (2, 2)")
    kw = dict(seq=seq, batch=batch, nodes=nodes, steps=steps, seed=seed,
              clock=clock)
    runs = {}
    for name, mesh in (("1x1", one), ("2x2", four)):
        runs[name] = {}
        for reassembly in ("xla", "pallas"):
            out, params = train_once(cfg, mesh, reassembly, **kw)
            if name == "2x2":
                out["sharded_leaves"] = check_param_sharding(params, mesh)
            del params
            gc.collect()
            log("trainer", **out)
            runs[name][reassembly] = out["losses"]
    # The (2, 2) step splits matmuls over the model axis and sums gradients
    # over the data axis, so its f32 sums run in another order than on one
    # chip, and the matmuls take bf16 passes (the TPU's default precision).
    # The first loss differs by a few ulps; Adam then carries the gradient
    # differences into the weights.  1e-3 of the loss bounds that drift
    # over 6 steps; wrong rows, a missing reduction or a mis-sharded weight
    # move the loss by orders more.
    a = np.asarray(runs["1x1"]["xla"])
    b = np.asarray(runs["2x2"]["xla"])
    rel = float(np.max(np.abs(a - b) / np.abs(a)))
    log("four_chips", loss_max_rel_diff_2x2_vs_1x1=rel, tol=1e-3)
    for name, by_reassembly in runs.items():
        for reassembly, losses in by_reassembly.items():
            check(bool(np.all(np.isfinite(losses))),
                  f"non-finite loss on {name} {reassembly}")
        check(losses_ulp_equal(by_reassembly["xla"], by_reassembly["pallas"]),
              f"{name}: xla and pallas losses differ beyond f32 ULP")
    check(rel <= 1e-3, f"(2, 2) losses differ from (1, 1) by {rel:.3e}")
    return runs


# -------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the trainer phase, on a (2, 2) mesh over "
                         "four chips against a (1, 1) mesh over device 0")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    if resolve_interpret() is not False:
        raise SystemExit("Pallas kernels would run interpreted "
                         "(REPRO_PALLAS_INTERPRET is set): refusing")
    cache_dir = use_compile_cache()
    log("device", platform=dev.platform, kind=dev.device_kind,
        count=jax.device_count(), compile_cache=cache_dir)
    clock = CompileClock()
    train = dict(seq=1024, batch=8, nodes=4, steps=5, seed=args.seed,
                 clock=clock)
    if args.four_chips:
        phases = {"four_chips": lambda: four_chip_phase(trainer_config(),
                                                        **train)}
    else:
        phases = {
            "trainer": lambda: trainer_phase(trainer_config(),
                                             resolve_mesh("debug"), **train),
            "server": lambda: server_phase(
                server_config(), requests=8, prompt_len=512, gen=32, page=16,
                num_pages=2048, seed=args.seed, clock=clock),
        }
    # every phase runs even when an earlier one failed, so one run on the
    # chip reports on all of them; any failure fails the script
    failed = []
    for name, phase in phases.items():
        try:
            phase()
        except Exception:                # noqa: BLE001 — reported, then fatal
            traceback.print_exc()
            failed.append(name)
        gc.collect()
    stats = dev.memory_stats() or {}
    log("memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    if failed:
        raise SystemExit(f"chip_smoke.py: phase(s) failed: {failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
