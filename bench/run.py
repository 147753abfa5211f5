"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and per-layer metrics are found by
name from ``BENCHMARK.json`` (see ``bench/lib/spec.py``); the traffic file's
``driver`` names the module of ``bench/lib`` that runs it.  The run refuses
any device but a TPU whose peaks ``bench/lib/peaks.py`` lists, and any
interpreted kernel.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiled window.  Every compared
number is printed beside its limit, last on standard error and as the last
key of the result line, the last line of standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(cell, out, peak, readers) -> tuple:
    """Reduce the window's trace and read each per-layer metric."""
    from bench.lib import trace as tr
    kernels, modules = {}, set()
    for mod in readers.values():
        kernels.update(getattr(mod, "KERNELS", {}))
        modules.update(getattr(mod, "MODULES", ()))
    red = tr.reduce(out["compact_trace"], kernels, tuple(sorted(modules)))
    run = {"cell": cell.name, "config": cell.config, "traffic": cell.traffic,
           "chips": cell.chips, "peak": peak, "host": out["host"],
           "e2e": out["e2e"], "trace": red}
    metrics = {}
    for m in cell.per_layer:
        value = readers[m["name"]].read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, red


def main(argv=None) -> int:
    args = parse(argv)
    from bench.lib import spec
    bench = spec.load_benchmark()
    cell = spec.resolve(bench, args.workload)
    readers = {m["name"]: spec.load_module(m["name"]) for m in cell.per_layer}
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))

    import jax
    from bench.lib.peaks import peak_for
    marks = [("imports", time.perf_counter())]
    devs = jax.devices()
    marks.append(("tpu_start", time.perf_counter()))
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 3
    if len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 3
    peak = peak_for(devs[0].device_kind)
    from repro.kernels import resolve_interpret
    if resolve_interpret() is not False:
        print("bench: Pallas kernels would run interpreted", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from bench.lib.common import device_stamp, report_checks
    driver = importlib.import_module(f"bench.lib.{cell.traffic['driver']}")
    marks.append(("program_import", time.perf_counter()))
    out = driver.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    before = {b[0]: b[1] - a[1]
              for a, b in zip([("", T_START)] + marks, marks)}
    out["host"]["setup_phases_s"] = {**before,
                                     **out["host"]["setup_phases_s"]}

    limits = cell.traffic["limits"]
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in out["readings"].items()}
    device = dict(device_stamp(out["devices"]),
                  memory_peak_bytes=out["memory_peak_bytes"])
    result = {"attempted": out["attempted"], "failed": out["failed"]}
    if args.trace:
        metrics, red = per_layer(cell, out, peak, readers)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        metrics = {m["name"]: {"value": out["e2e"][m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    host = out["host"]
    print(json.dumps({"host": {k: v for k, v in host.items()
                               if not isinstance(v, list)}}),
          file=sys.stderr)
    correct = report_checks(checks)
    line = {"correct": correct, **result, "metrics": metrics,
            "device": device,
            "window_compiles": host["window_compiles"],
            "checks": checks}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
