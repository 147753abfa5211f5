import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any other import (jax locks the platform
# and the device count on first initialization): the dry run lowers for 512
# host devices and never takes an accelerator.  Do not move them.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) and emit
roofline artifacts.

    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b \
        --shape train_4k --mesh single [--remat tl] [--out artifacts/]

Exit code 0 and a JSON artifact mean the sharding config is coherent for the
production mesh: GSPMD found a partitioning, the collective schedule exists,
and memory/cost analyses were extracted.
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.analysis.roofline import Roofline, model_flops, summarize
from repro.configs import get_config, get_shape
from repro.core.tl_step import (make_serve_step, make_train_step,
                                serve_shardings, train_shardings)
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import abstract_cache, abstract_params, input_specs
from repro.models import build_model
from repro.optim import adafactor


def lower_one(arch: str, shape_name: str, mesh_kind: str, remat: str = "tl",
              dtype=jnp.bfloat16, extra_tags=None, microbatch: int = 1,
              cache_seq_shard: bool = False, activation_constraints: bool = False,
              serve_fsdp=None, moe_ep: bool = False):
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    if shape.kind == "decode" and shape.seq_len > 40_000 \
            and not cfg.supports_long_context:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped",
                "reason": "full-attention arch: long-context decode is "
                          "quadratic by design (DESIGN.md §4)"}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    model = build_model(cfg)
    params = abstract_params(model, dtype)
    t0 = time.time()

    from repro.dist.constraints import set_activation_mesh
    from repro.dist.sharding import batch_axes
    if activation_constraints:
        set_activation_mesh(batch_axes(mesh))
    if moe_ep:
        from repro.models.moe import set_expert_parallel_mesh
        set_expert_parallel_mesh(mesh)

    with mesh:
        if shape.kind == "train":
            opt = adafactor(1e-3)
            opt_state = jax.eval_shape(opt.init, params)
            step = make_train_step(model, cfg, opt, remat_mode=remat,
                                   microbatch=microbatch)
            in_sh, out_sh = train_shardings(
                params, opt_state, cfg, mesh, shape,
                with_embeds=bool(cfg.frontend))
            lowered = jax.jit(step, in_shardings=in_sh,
                              out_shardings=out_sh).lower(
                params, opt_state, input_specs(cfg, shape, dtype))
        elif shape.kind == "prefill":
            specs = input_specs(cfg, shape, dtype)
            cache = abstract_cache(model, shape.global_batch, shape.seq_len,
                                   dtype)
            in_sh, out_sh = serve_shardings(params, cache, cfg, mesh, shape,
                                            cache_seq_shard=cache_seq_shard,
                                            fsdp=serve_fsdp)
            pf = lambda p, c, tok, extra=None: model.prefill(p, c, tok, extra)
            args = (params, cache, specs["tokens"])
            in_shardings = (in_sh[0], in_sh[1], None)
            if "embeds" in specs:
                args = args + (specs["embeds"],)
                in_shardings = in_shardings + (None,)
            lowered = jax.jit(pf, in_shardings=in_shardings,
                              out_shardings=out_sh).lower(*args)
        else:  # decode
            specs = input_specs(cfg, shape, dtype)
            cache = abstract_cache(model, shape.global_batch, shape.seq_len,
                                   dtype)
            in_sh, out_sh = serve_shardings(params, cache, cfg, mesh, shape,
                                            cache_seq_shard=cache_seq_shard,
                                            fsdp=serve_fsdp)
            step = make_serve_step(model, cfg)
            lowered = jax.jit(step, in_shardings=(in_sh[0], in_sh[1],
                                                  in_sh[2], in_sh[3]),
                              out_shardings=out_sh).lower(
                params, cache, specs["token"], specs["cache_len"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()

    # cost_analysis counts scan (while) bodies once; the HLO analyzer
    # multiplies by trip counts — use it for the roofline, keep raw XLA
    # numbers as a cross-check
    from repro.analysis.hlo_flops import analyze
    costs = analyze(hlo)
    coll = {k: int(v) for k, v in costs.coll.items()}
    flops = float(costs.flops)
    bytes_acc = float(costs.hbm_bytes)
    raw_flops = float(cost.get("flops", 0.0)) if cost else 0.0
    raw_bytes = float(cost.get("bytes accessed", 0.0)) if cost else 0.0
    mem_fields = {}
    if mem is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(mem, f, None)
            if v is not None:
                mem_fields[f] = int(v)

    peak = (mem_fields.get("argument_size_in_bytes", 0)
            + mem_fields.get("temp_size_in_bytes", 0)
            + mem_fields.get("output_size_in_bytes", 0)
            - mem_fields.get("alias_size_in_bytes", 0))

    r = Roofline(
        arch=arch, shape=shape_name, mesh=mesh_kind, chips=chips,
        flops_per_chip=flops, bytes_per_chip=bytes_acc,
        coll_bytes_per_chip=float(sum(coll.values())),
        coll_breakdown=coll,
        model_flops_global=model_flops(cfg, shape),
        peak_memory_per_chip=float(peak),
    )
    out = r.to_dict()
    out.update(status="ok", remat=remat, microbatch=microbatch,
               cache_seq_shard=cache_seq_shard,
               activation_constraints=activation_constraints,
               memory_analysis=mem_fields,
               t_lower_s=t_lower, t_compile_s=t_compile,
               hlo_lines=hlo.count("\n"),
               xla_cost_analysis={"flops": raw_flops,
                                  "bytes_accessed": raw_bytes},
               extra_tags=extra_tags or {})
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--remat", default="tl", choices=["tl", "none", "dots"])
    ap.add_argument("--out", default="experiments/artifacts")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--cache-seq-shard", action="store_true")
    ap.add_argument("--act-constraints", action="store_true")
    ap.add_argument("--no-serve-fsdp", action="store_true")
    ap.add_argument("--moe-ep", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    try:
        art = lower_one(args.arch, args.shape, args.mesh, args.remat,
                        microbatch=args.microbatch,
                        cache_seq_shard=args.cache_seq_shard,
                        activation_constraints=args.act_constraints,
                        serve_fsdp=False if args.no_serve_fsdp else None,
                        moe_ep=args.moe_ep)
    except Exception as e:  # noqa: BLE001 — report compile failures as data
        art = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}

    os.makedirs(args.out, exist_ok=True)
    name = f"{args.arch}__{args.shape}__{args.mesh}__{args.tag}.json"
    path = os.path.join(args.out, name)
    with open(path, "w") as f:
        json.dump(art, f, indent=1)

    if art["status"] == "ok":
        print("memory_analysis:", art["memory_analysis"])
        print("cost_analysis: flops=%.3e bytes=%.3e" %
              (art["flops_per_chip"], art["bytes_per_chip"]))
        print(summarize(art))
    else:
        print(art["status"], art.get("reason", art.get("error", "")))
    print("artifact:", path)
    return 0 if art["status"] in ("ok", "skipped") else 1


if __name__ == "__main__":
    raise SystemExit(main())
