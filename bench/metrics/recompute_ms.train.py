"""Recompute time of the TL step, the cost TL adds over plain backprop:
device time per step of the step program's ops that the backward pass
rematerializes from X^(1) (``rematted_computation`` on their ``op_name``
path), ms."""
from bench.lib.scopes import TL_PHASES, install, scope_ms_per_step

install()


def read(run):
    return scope_ms_per_step(run, TL_PHASES, ("recompute",))
