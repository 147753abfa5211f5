"""The step program's exchanges between chips, from the same trace.

On a mesh of several chips the partitioner adds collectives to the TL step:
the FSDP gathers of the weights, the gradients' reduce-scatters, the
tensor-parallel all-reduces and the vocabulary-parallel loss's reductions.
The TPU's compiler runs most of them as fusions of its own: an async
collective (``async-collective-start`` / ``-done``) or a fusion that calls
an ``all-reduce-scatter`` computation, whose short names say nothing of a
collective.  So an op counts as a collective by its HLO text, which is its
event's name in the profile, and not by its short name alone.

This module extends :mod:`bench.lib.scopes` (and through it
:mod:`bench.lib.trace`) without changing what they give:

1. :func:`extract` is :func:`bench.lib.scopes.extract`, and appends to
   each op event a 5th element, true for a collective.
2. :func:`reduce` is :func:`bench.lib.scopes.reduce`, plus ``collectives``
   (:func:`collective_split`).

:func:`install`, which the readers of these numbers call when they load,
points the harness's calls of ``bench.lib.trace`` here.
"""
from __future__ import annotations

import bisect
import re

from bench.lib import scopes as sc
from bench.lib import trace as tr

COLLECTIVE_TEXT = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|async-collective|AsyncCollective|allgather|allreduce|reducescatter",
    re.I)


def is_collective(hlo_text: str) -> bool:
    """Whether an op event, by its name (the op's HLO text), exchanges data
    between chips: a collective instruction, or a fusion that calls one.
    Only the op's own name, its opcode and the computations it calls are
    read, never its operands (an op that consumes a collective's result is
    no collective)."""
    name, _, rest = hlo_text.partition(" = ")
    opcode = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rest)
    own = [name, opcode.group(1) if opcode else ""]
    own += re.findall(r"calls=%?([\w.\-]+)", rest)
    return any(COLLECTIVE_TEXT.search(t) for t in own)


def extract(trace_dir: str) -> dict:
    """:func:`bench.lib.scopes.extract`'s compact trace, each op as
    ``[name, start_ns, dur_ns, path, collective]``."""
    from jax.profiler import ProfileData
    compact = sc.extract(trace_dir)
    pd = ProfileData.from_file(tr.find_xplane(trace_dir))
    for plane in pd.planes:
        if plane.name in compact["devices"]:
            ops = compact["devices"][plane.name]["ops"]
            names = [e.name for line in plane.lines
                     if line.name == tr.OPS_LINE for e in line.events]
            # the base extraction kept this plane's op events in this order
            for op, nm in zip(ops, names, strict=True):
                op.append(is_collective(nm))
    return compact


def collective_split(compact: dict) -> dict:
    """``collectives``: over the ops inside calls of the step program
    (:data:`bench.lib.scopes.STEP_MODULE`) in the window, averaged over the
    devices, ``seconds`` is the union of the collectives' intervals,
    ``exposed_s`` the part of it under which no other op runs, and
    ``scopes`` the collectives' time by TL phase and direction
    (:func:`bench.lib.scopes.phase_of`; ``other`` holds those the compiler
    added with no ``op_name``)."""
    lo, hi = tr.window_of(compact)
    devs = compact["devices"]
    n = max(1, len(devs))
    total = exposed = 0.0
    by = {}
    for dev in devs.values():
        steps = sorted((s, s + d) for nm, s, d in dev["modules"]
                       if nm.split("(")[0] == sc.STEP_MODULE)
        starts = [a for a, _ in steps]
        coll, comp = [], []
        for nm, s, d, *rest in dev["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            if s + d <= lo or s >= hi or i < 0 or s >= steps[i][1]:
                continue
            if len(rest) < 2 or not rest[1]:
                comp.append((s, s + d))
                continue
            coll.append((s, s + d))
            phase, direction = sc.phase_of(rest[0])
            per = by.setdefault(phase, {})
            per[direction] = per.get(direction, 0.0) \
                + (min(s + d, hi) - max(s, lo)) * 1e-9 / n
        cu = tr.union(coll, lo, hi)
        total += tr.measure(cu)
        exposed += tr.measure(tr.subtract(cu, tr.union(comp, lo, hi)))
    return {"collectives": {"seconds": total * 1e-9 / n,
                            "exposed_s": exposed * 1e-9 / n, "scopes": by}}


def reduce(compact: dict, kernels: dict = None, modules: tuple = ()) -> dict:
    """:func:`bench.lib.scopes.reduce` and :func:`collective_split`."""
    return {**sc.reduce(compact, kernels, modules),
            **collective_split(compact)}


def install():
    """Have the harness's calls of ``bench.lib.trace.extract`` and
    ``reduce`` come here (which includes what ``scopes.install`` gives)."""
    tr.extract, tr.reduce = extract, reduce


def ms_per_step(run: dict, key: str):
    """Milliseconds per window step of ``collectives[key]``; None for a
    cell that does not train or a trace reduced without this module."""
    coll = run["trace"].get("collectives")
    steps = run["host"].get("steps")
    if run["traffic"]["driver"] != "train" or not steps or coll is None:
        return None
    return 1e3 * coll[key] / steps
