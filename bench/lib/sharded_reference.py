"""The plain reference's Adam state placed as its weights are, for a cell
on several chips.

:func:`bench.lib.reference.adamw_init` makes each moment with
``jnp.zeros(shape)``, which lands whole on the default device.  On one chip
that is where the weights are.  On a mesh the weights are sharded (the
training driver hands the reference the program's parameter shardings), and
the moments of a 67B stage, 12.75 GB, would all sit on chip 0.  Here each
moment is ``jnp.zeros_like`` its weight: the same zeros, in float32, with
the weight's sharding.  Nothing else of the reference changes.

:func:`install` points ``bench.lib.reference.adamw_init`` here.  The readers
of the four-chip cell's own metrics call it when they load, which
``bench/run.py`` does before the driver runs, traced or not.  To set that
cell's limits, install it before ``bench/calibrate.py`` runs::

    python3 -c "from bench.lib import sharded_reference as s; s.install();
    from bench import calibrate; calibrate.main()" --workload <cell> ...
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib import reference


def adamw_init(w):
    z = lambda a: jnp.zeros_like(a, dtype=jnp.float32)   # noqa: E731
    return {"step": 0, "m": jax.tree.map(z, w), "v": jax.tree.map(z, w)}


def install():
    """Have the training driver's calls of ``reference.adamw_init`` come
    here."""
    reference.adamw_init = adamw_init
