"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, kept apart so that the second can be checked on a small
recorded trace:

1. :func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
   keeps, per device, the op events and the module (whole jitted program)
   events, and the host's spans: the benchmark's own (``bench.*``
   annotations) and any other of a millisecond or more, as plain lists:
   the *compact* trace.
2. :func:`reduce` turns a compact trace into busy time, idle gaps by host
   activity, per-module and per-kernel device time, and the collective
   time with no compute under it.
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
HOST_MIN_NS = 1_000_000
COLLECTIVE_RE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|allgather|allreduce|reducescatter", re.I)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def op_name(hlo_text: str) -> str:
    """``%fusion.80 = (...) fusion(...)`` -> ``fusion.80``; a custom call
    keeps its target: ``jvp__.1 tpu_custom_call``."""
    m = re.match(r"%?([\w.\-]+)", hlo_text)
    short = m.group(1) if m else hlo_text[:64]
    tgt = re.search(r'custom_call_target="([^"]+)"', hlo_text)
    return f"{short} {tgt.group(1)}" if tgt else short


def extract(trace_dir: str) -> dict:
    """The compact trace: ``{"devices": {plane: {"ops": [[name, start_ns,
    dur_ns], ...], "modules": [[name, start_ns, dur_ns], ...]}},
    "host": [[name, start_ns, dur_ns], ...]}``; op names by
    :func:`op_name`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(trace_dir))
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        ops.append([op_name(e.name), e.start_ns,
                                    e.duration_ns])
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        mods.append([e.name, e.start_ns, e.duration_ns])
            if ops or mods:
                devices[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if (e.name.startswith(HOST_PREFIX)
                            or e.duration_ns >= HOST_MIN_NS):
                        host.append([e.name, e.start_ns, e.duration_ns])
    return {"devices": devices, "host": host}


# ------------------------------------------------------------- reduction

def union(intervals, lo=None, hi=None) -> list:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    iv = []
    for s, e in intervals:
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e > s:
            iv.append((s, e))
    iv.sort()
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def measure(iv) -> float:
    return float(sum(e - s for s, e in iv))


def subtract(a, b) -> list:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window_of(compact: dict, span: str = "bench.window"):
    """[start, end) of the benchmark's window span (host and device events
    share one clock in the profiler's trace), else of all device ops."""
    spans = [(s, s + d) for n, s, d in compact["host"] if n == span]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    evs = [(s, s + d) for dev in compact["devices"].values()
           for _, s, d, *_ in dev["ops"]]
    if not evs:
        return 0, 1
    return min(s for s, _ in evs), max(e for _, e in evs)


def _op_group(name: str) -> str:
    return re.sub(r"\.\d+", "", name)


def reduce(compact: dict, kernels: dict = None, modules: tuple = ()) -> dict:
    """Busy, idle, module, kernel and collective time over the window.

    ``kernels`` maps a kernel's name to ``(regex, module)``: its op events
    match the regex and, unless ``module`` is None, run inside a call of a
    jitted program whose name starts with ``module``; ``modules`` are
    jitted-program name prefixes whose
    per-call device times are kept.  Times are in seconds; per-device
    numbers are averaged over the devices.
    """
    kernels = kernels or {}
    lo, hi = window_of(compact)
    devs = compact["devices"]
    n = max(1, len(devs))
    busy, coll_exposed, ops_time = 0.0, 0.0, {}
    kern = {k: {"seconds": 0.0, "calls": 0} for k in kernels}
    mods = {m: [] for m in modules}
    gaps_by_host = {}
    host = sorted(([s, s + d, nm] for nm, s, d in compact["host"]
                   if nm != "bench.window"), key=lambda h: h[1] - h[0])
    for dname, dev in sorted(devs.items()):
        ops = [(s, s + d, nm) for nm, s, d, *_ in dev["ops"]
               if s + d > lo and s < hi]
        calls = {}
        for nm, s, d in dev["modules"]:
            calls.setdefault(nm.split("(")[0], []).append((s, s + d))
        allu = union([(s, e) for s, e, _ in ops], lo, hi)
        busy += measure(allu)
        coll = union([(s, e) for s, e, nm in ops if COLLECTIVE_RE.search(nm)],
                     lo, hi)
        comp = union([(s, e) for s, e, nm in ops
                      if not COLLECTIVE_RE.search(nm)], lo, hi)
        coll_exposed += measure(subtract(coll, comp))
        for s, e, nm in ops:
            g = _op_group(nm)
            ops_time[g] = ops_time.get(g, 0.0) + (min(e, hi) - max(s, lo))
            for k, (pat, mod) in kernels.items():
                if re.search(pat, nm) and (mod is None or any(
                        a <= s < b for a, b in calls.get(mod, ()))):
                    kern[k]["seconds"] += (min(e, hi) - max(s, lo)) * 1e-9
                    kern[k]["calls"] += 1
        for nm, s, d in dev["modules"]:
            if s + d > lo and s < hi:
                for m in modules:
                    if nm.startswith(m):
                        mods[m].append(d * 1e-9)
        if dname == sorted(devs)[0]:
            # idle time on the first device, split over the innermost
            # host span that covers each part of it
            left = subtract([(lo, hi)], allu)
            for s, e, nm in host:
                part = subtract(left, subtract(left, [(s, e)]))
                if part:
                    gaps_by_host[nm] = gaps_by_host.get(nm, 0.0) \
                        + measure(part) * 1e-9
                    left = subtract(left, [(s, e)])
            if left:
                gaps_by_host["no bench span"] = measure(left) * 1e-9
    for k in kern:
        kern[k]["seconds"] /= n
        kern[k]["calls"] /= n
    window_s = (hi - lo) * 1e-9
    top = sorted(ops_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_s,
        "busy_s": busy / n * 1e-9,
        "collective_exposed_s": coll_exposed / n * 1e-9,
        "kernels": kern,
        "modules": mods,
        "device_ops": [[k, v / n * 1e-9] for k, v in top],
        "idle_gaps": sorted(([k, v] for k, v in gaps_by_host.items()),
                            key=lambda kv: -kv[1])[:10],
        "n_devices": len(devs),
    }
