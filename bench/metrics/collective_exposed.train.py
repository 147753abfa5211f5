"""The part of ``collective_ms.train`` under which no other op runs on the
chip: device time per step, per chip, in which the step program only
waits on an exchange between chips, ms."""
from bench.lib import sharded_reference
from bench.lib.collectives import install, ms_per_step

install()
# the reference of the cell that reads these keeps its Adam state
# sharded as its weights (bench/lib/sharded_reference.py)
sharded_reference.install()


def read(run):
    return ms_per_step(run, "exposed_s")
