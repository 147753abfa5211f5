"""What every driver shares: the device stamp, the memory peak, tracing a
window, per-layer norms and the gap measure of the output check."""
from __future__ import annotations

import contextlib
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import trace as tr


def device_stamp(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def memory_peak(devices):
    peaks = [(dv.memory_stats() or {}).get("peak_bytes_in_use")
             for dv in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@contextlib.contextmanager
def traced(enabled: bool):
    """Profile the block when ``enabled``; yields a dict that holds the
    compact trace once the block has ended."""
    out = {}
    if not enabled:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield out
        return
    d = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield out
    finally:
        jax.profiler.stop_trace()
        try:
            out["compact"] = tr.extract(d)
        finally:
            shutil.rmtree(d, ignore_errors=True)


def norms_fn():
    """jitted tree -> per-layer L2 norms (one value per ``leaf_items``)."""
    def f(tree):
        out = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            x = leaf.astype(jnp.float32)
            if "['cycles']" in jax.tree_util.keystr(path):
                out.append(jnp.sqrt(jnp.sum(x * x, axis=tuple(
                    range(1, x.ndim)))))
            else:
                out.append(jnp.sqrt(jnp.sum(x * x)).reshape(1))
        return jnp.concatenate(out)
    return jax.jit(f)


def worst_gap(prog, ref, include=None) -> float:
    """The widest gap between the program's and the reference's norm of a
    leaf, over the larger of that leaf's reference norm and the median
    leaf's."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    idx = np.arange(len(ref)) if include is None else np.flatnonzero(include)
    med = float(np.median(ref[idx]))
    return float(np.max(np.abs(prog[idx] - ref[idx])
                        / np.maximum(ref[idx], med)))


def report_checks(checks: dict) -> bool:
    """Print each compared number beside its limit, last on stderr;
    True when every one is within its limit."""
    ok = True
    for name, c in checks.items():
        within = (c["value"] is not None and c["limit"] is not None
                  and c["value"] <= c["limit"])
        ok &= bool(within)
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if within else 'FAILED'}", file=sys.stderr)
    return ok
