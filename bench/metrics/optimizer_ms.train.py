"""Optimizer time of the TL step: device time per step of the step
program's ops under the ``tl_optimizer`` scope (the AdamW update), ms."""
from bench.lib.scopes import install, scope_ms_per_step

install()


def read(run):
    return scope_ms_per_step(run, ("tl_optimizer",),
                             ("fwd", "recompute", "bwd"))
