"""The TL step's phases and the engine's host spans, from the same trace.

The training program names its phases with ``jax.named_scope``
(``tl_node``, ``tl_reassembly``, ``tl_tail``, ``tl_loss``, ``tl_optimizer``
in ``repro.core.tl_step``) and its host work with profiler annotations
(``tl_run``, ``tl_step``, ``tl_input_wait``, ``tl_put_batch``, ``tl_sync``
in ``repro.launch.engine``).  This module extends the two steps of
:mod:`bench.lib.trace` without changing what they already give:

1. :func:`extract` is :func:`bench.lib.trace.extract`, and appends to each
   op event its ``op_name`` path as a 4th element, and keeps the program's
   host spans (``tl_*``) under a millisecond too.
2. :func:`reduce` is :func:`bench.lib.trace.reduce`, plus ``scopes``,
   ``idle_by_span`` and ``idle_within_span`` (:func:`phase_split`).

The harness calls ``bench.lib.trace``'s two functions; :func:`install`,
which the readers of these numbers call when they load, points them here.
Every key that ``bench.lib.trace.reduce`` returns keeps its value, since
that reduction unpacks ops as ``name, start, duration, *_``.
"""
from __future__ import annotations

import bisect
import re

from bench.lib import trace as tr

PROGRAM_PREFIX = "tl_"
# the step program, whose ops are split by the TL step's named scopes: the
# phase is the first scope on an op's ``op_name`` path, the direction what
# autodiff wrapped around it.  The program's own tests (tests/test_engine.py,
# tests/test_tpu_compile.py) read these names and ``extract``: renaming them
# breaks those tests too
STEP_MODULE = "jit_step"
PATH_STAT = "tf_op"
PHASE_RE = re.compile(r"(?<![A-Za-z0-9])tl_[a-z]+")
TL_PHASES = ("tl_node", "tl_reassembly", "tl_tail", "tl_loss")

_base_extract, _base_reduce = tr.extract, tr.reduce


def _varint(buf, i: int) -> tuple:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf):
    """``(field number, value)`` of each field of one protobuf message:
    an int for a varint, a memoryview for a length-delimited field, None
    for a fixed-width one."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} in the profile")
        yield key >> 3, value


def op_paths(raw: bytes) -> dict:
    """``{TPU plane name: {op event name: op_name path}}`` from the raw
    XSpace.  xprof keeps an op's ``op_name`` as the ``tf_op`` stat
    (``path:type``) of the op's event metadata, which ``ProfileData``'s
    events do not expose.  A name that two ops share with different paths
    gets none."""
    out = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1:                                   # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:                            # event_metadata map
                metas.append(dict(_fields(v)).get(2, b""))
            elif pf == 5:                            # stat_metadata map
                sm = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[sm.get(1, 0)] = bytes(sm.get(2, b"")).decode()
        if not (name.startswith("/device:") and "TPU" in name):
            continue
        paths = out.setdefault(name, {})
        for meta in metas:
            ev_name, path = None, ""
            for mf, v in _fields(meta):
                if mf == 2:
                    ev_name = bytes(v).decode()
                elif mf == 5:                        # an XStat
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1, 0)) == PATH_STAT:
                        val = (bytes(st[5]).decode() if 5 in st
                               else stat_names.get(st.get(7), ""))
                        path = val[:-1] if val.endswith(":") else val
            if ev_name is not None:
                paths[ev_name] = (path if paths.get(ev_name, path) == path
                                  else "")
    return out


def extract(trace_dir: str) -> dict:
    """:func:`bench.lib.trace.extract`'s compact trace, each op as
    ``[name, start_ns, dur_ns, path]`` (path by :func:`op_paths`, "" where
    there is none), and the host's ``tl_*`` spans of any length."""
    from jax.profiler import ProfileData
    compact = _base_extract(trace_dir)
    with open(tr.find_xplane(trace_dir), "rb") as f:
        raw = f.read()
    paths = op_paths(raw)
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name in compact["devices"]:
            ops = compact["devices"][plane.name]["ops"]
            names = [e.name for line in plane.lines
                     if line.name == tr.OPS_LINE for e in line.events]
            on_path = paths.get(plane.name, {})
            # the base extraction kept this plane's op events in this order
            for op, nm in zip(ops, names, strict=True):
                op.append(on_path.get(nm, ""))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    # the longer ones the base extraction kept already
                    if (e.name.startswith(PROGRAM_PREFIX)
                            and e.duration_ns < tr.HOST_MIN_NS):
                        compact["host"].append(
                            [e.name, e.start_ns, e.duration_ns])
    return compact


def phase_of(path: str) -> tuple:
    """``(phase, direction)`` of a step-program op from its ``op_name``
    path: the first ``tl_*`` scope on it (``other`` if none), and
    ``recompute`` under ``rematted_computation``, ``bwd`` under a
    ``transpose(``, else ``fwd``."""
    m = PHASE_RE.search(path)
    direction = ("recompute" if "rematted_computation" in path
                 else "bwd" if "transpose(" in path else "fwd")
    return (m.group(0) if m else "other"), direction


def phase_split(compact: dict) -> dict:
    """The step program's device time by phase, and idle time by span.

    ``scopes`` is the device time of the ops inside calls of
    :data:`STEP_MODULE` in the window, by :func:`phase_of`: ``{phase:
    {direction: seconds}}``, averaged over the devices.  ``idle_by_span``
    holds the idle time of the first device under each host span in the
    window (0 where a span covers none), each idle interval given to the
    innermost span over it: the dict of which ``idle_gaps`` is the ten
    largest non-zero entries.  ``idle_within_span`` instead holds, per
    program span name (``tl_*``), the idle time inside the union of that
    name's spans, whatever other spans lie inside them (the prefetch
    thread's ``tl_put_batch`` within ``tl_input_wait``)."""
    lo, hi = tr.window_of(compact)
    devs = compact["devices"]
    n = max(1, len(devs))
    scopes, gaps_by_host, idle_within = {}, {}, {}
    host = sorted(([s, s + d, nm] for nm, s, d in compact["host"]
                   if nm != "bench.window"), key=lambda h: h[1] - h[0])
    for dname, dev in sorted(devs.items()):
        steps = sorted((s, s + d) for nm, s, d in dev["modules"]
                       if nm.split("(")[0] == STEP_MODULE)
        starts = [a for a, _ in steps]
        for nm, s, d, *path in dev["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            if s + d <= lo or s >= hi or i < 0 or s >= steps[i][1]:
                continue
            phase, direction = phase_of(path[0] if path else "")
            by = scopes.setdefault(phase, {})
            by[direction] = by.get(direction, 0.0) \
                + (min(s + d, hi) - max(s, lo)) * 1e-9 / n
        if dname != sorted(devs)[0]:
            continue
        allu = tr.union([(s, s + d) for _, s, d, *_ in dev["ops"]
                         if s + d > lo and s < hi], lo, hi)
        left = tr.subtract([(lo, hi)], allu)
        gaps_by_host = {nm: 0.0 for s, e, nm in host if e > lo and s < hi}
        for s, e, nm in host:
            part = tr.subtract(left, tr.subtract(left, [(s, e)]))
            if part:
                gaps_by_host[nm] += tr.measure(part) * 1e-9
                left = tr.subtract(left, [(s, e)])
        if left:
            gaps_by_host["no bench span"] = tr.measure(left) * 1e-9
        spans = {}
        for s, e, nm in host:
            if nm.startswith(PROGRAM_PREFIX) and e > lo and s < hi:
                spans.setdefault(nm, []).append((s, e))
        idle_within = {nm: tr.measure(tr.subtract(tr.union(iv, lo, hi),
                                                  allu)) * 1e-9
                       for nm, iv in spans.items()}
    return {"scopes": scopes, "idle_by_span": gaps_by_host,
            "idle_within_span": idle_within}


def reduce(compact: dict, kernels: dict = None, modules: tuple = ()) -> dict:
    """:func:`bench.lib.trace.reduce` and :func:`phase_split`."""
    return {**_base_reduce(compact, kernels, modules), **phase_split(compact)}


def install():
    """Have the harness's calls of ``bench.lib.trace.extract`` and
    ``reduce`` come here."""
    tr.extract, tr.reduce = extract, reduce


def scope_ms_per_step(run: dict, phases, directions):
    """Device milliseconds per window step of the step program's ops in
    ``phases`` and ``directions`` (``scopes``); None for a cell that does
    not train or a trace that holds none of ``phases``."""
    scopes = run["trace"].get("scopes") or {}
    steps = run["host"].get("steps")
    if run["traffic"]["driver"] != "train" or not steps \
            or not any(p in scopes for p in phases):
        return None
    s = sum(scopes.get(p, {}).get(d, 0.0) for p in phases for d in directions)
    return 1e3 * s / steps
