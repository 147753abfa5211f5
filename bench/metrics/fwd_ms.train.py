"""Forward time of the TL step: device time per step of the step
program's ops under the ``tl_node``, ``tl_reassembly``, ``tl_tail`` and
``tl_loss`` scopes that autodiff neither transposed nor rematerialized,
ms."""
from bench.lib.scopes import TL_PHASES, install, scope_ms_per_step

install()


def read(run):
    return scope_ms_per_step(run, TL_PHASES, ("fwd",))
