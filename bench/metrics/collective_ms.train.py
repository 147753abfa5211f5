"""Time the TL step spends in exchanges between chips: device time per
step, per chip, of the step program's collectives (the FSDP gathers, the
gradients' reduce-scatters, the tensor-parallel all-reduces and the
vocabulary-parallel loss's reductions, as the TPU runs them), the union of
their intervals, ms."""
from bench.lib import sharded_reference
from bench.lib.collectives import install, ms_per_step

install()
# the reference of the cell that reads these keeps its Adam state
# sharded as its weights (bench/lib/sharded_reference.py)
sharded_reference.install()


def read(run):
    return ms_per_step(run, "seconds")
