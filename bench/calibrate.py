"""Readings that the limits of the output check are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3]

In one process on the chip, at the cell's own sizes: for every seed the
program's numbers against the plain reference (the lower readings); for
the control seeds the reference with float8 matmul inputs in the program's
place (the upper readings); for the fault seeds the reference with half of
each batch left out in the program's place.  One JSON line per reading,
with ``correct`` as the cell's own limits judge it; each number beside its
limit goes to standard error.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def seeds(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def readings(cell, args):
    from bench.lib import train
    from bench.lib.common import report_checks
    tr = train.Trainer(cell)
    sh = tr.param_sh if len(tr.devices) > 1 else None
    limits = cell.traffic["limits"]
    for seed in sorted(set(args.seeds) | set(args.control_seeds)
                       | set(args.fault_seeds)):
        docs = tr.corpus(seed)
        it = iter(tr.loader(docs, seed))
        batches = [next(it) for _ in range(train.CHECK_STEPS)]
        ref_batches, off = train.corpus_rows(docs, batches)
        variants = []
        if seed in args.seeds:
            tr.reset(seed)
            variants.append(("program", tr.first_steps(seed, batches), off))
            tr.free()
        if seed in args.control_seeds:
            variants.append(("control_fp8", train.reference_steps(
                cell.config, cell.traffic, seed, ref_batches, "fp8", sh), 0))
        if seed in args.fault_seeds:
            variants.append(("fault_half_batch", train.reference_steps(
                cell.config, cell.traffic, seed, ref_batches, "highest", sh,
                half_batch=True), 0))
        ref32 = train.reference_steps(cell.config, cell.traffic, seed,
                                      ref_batches, "highest", sh)
        for name, got, rows_off in variants:
            got = {**train.compare(got, ref32), "rows_off_corpus": rows_off}
            print(f"{name} seed {seed}:", file=sys.stderr)
            ok = report_checks({k: {"value": v, "limit": limits[k]}
                                for k, v in got.items()})
            print(json.dumps({"cell": cell.name, "seed": seed,
                              "variant": name, "correct": ok, **got}),
                  flush=True)
        gc.collect()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)
    from bench.lib import spec
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    if cell.traffic["driver"] != "train":
        raise SystemExit("calibrate.py reads training cells")
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate.py needs a TPU")
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    readings(cell, args)


if __name__ == "__main__":
    main()
