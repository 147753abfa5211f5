"""Backward time of the TL step: device time per step of the step
program's transposed ops (``transpose(`` on their ``op_name`` path) under
the node, reassembly, tail and loss scopes, ms."""
from bench.lib.scopes import TL_PHASES, install, scope_ms_per_step

install()


def read(run):
    return scope_ms_per_step(run, TL_PHASES, ("bwd",))
