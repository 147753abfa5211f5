"""Production mesh construction + elastic reshrink planning.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run process forces
512 host devices while tests/benches must see 1.

:func:`plan_reshrink` is the elastic engine's mesh half: given a mesh and a
set of lost device ids it re-factorizes the ``(pod, data, model)`` shape
over the survivors — degrading the **data** axis first (pod second, model
only as a last resort: a model-axis change re-lays-out every weight and
grows per-chip parameter memory) — and validates the result against
``repro.dist.sharding.param_specs`` divisibility before the engine commits
to re-sharding onto it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Tuple

import jax
import numpy as np


def make_mesh(shape, axes):
    """``jax.make_mesh`` over the visible devices with Auto (GSPMD-sharded)
    axes on every mesh axis."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU multi-device tests (host platform device count)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def make_host_mesh(n_model: int = None):
    """(data, model) mesh over every visible device.  ``n_model`` defaults to
    2 when the device count is even (so TP paths are exercised), else 1.
    With ``--xla_force_host_platform_device_count=8`` this is the forced-8
    CPU mesh the engine equivalence tests run on."""
    n = jax.device_count()
    if n_model is None:
        n_model = 2 if n % 2 == 0 and n >= 2 else 1
    return make_mesh((n // n_model, n_model), ("data", "model"))


def make_multipod_debug_mesh(pod: int = 2, data: int = 2, model: int = 2):
    """Smallest mesh carrying the full multi-pod axis set (pod, data, model);
    runnable on 8 forced host devices.  Exercises the composite (pod, data)
    batch axes of :func:`repro.dist.sharding.batch_axes` without 512 chips."""
    return make_mesh((pod, data, model), ("pod", "data", "model"))


def resolve_mesh(kind: str, *, multi_pod: bool = False):
    """CLI-facing mesh selection for the training engine.

    * ``debug``      — the largest of (2,2) / (2,1) / (1,1) the host's device
      count supports.  On a plain single-device CPU this degenerates to a
      (1,1) mesh: the same jit path, shardings and donation as at scale,
      with every collective a no-op.
    * ``host``       — all visible devices as (data, model); combined with a
      forced ``--xla_force_host_platform_device_count`` this is the CPU
      stand-in for a real slice.
    * ``production`` — the 16x16 pod mesh (``multi_pod=True``: 2x16x16 with
      the (pod, data, model) axes); lower/compile-only on a laptop, the real
      thing on the actual slice.
    """
    if kind == "debug":
        n = jax.device_count()
        if n >= 4:
            return make_debug_mesh(2, 2)
        if n >= 2:
            return make_debug_mesh(2, 1)
        return make_debug_mesh(1, 1)
    if kind == "host":
        return make_host_mesh()
    if kind == "production":
        return make_production_mesh(multi_pod=multi_pod)
    raise ValueError(f"unknown mesh kind: {kind!r}")


# --------------------------------------------------------- elastic reshrink

class ReshrinkError(RuntimeError):
    """No valid mesh factorization exists over the surviving devices."""


@dataclass(frozen=True)
class ReshrinkPlan:
    """Outcome of :func:`plan_reshrink`: the new mesh plus the audit trail
    the engine's recovery report carries."""

    mesh: object                       # jax.sharding.Mesh over the survivors
    old_shape: Tuple[int, ...]
    new_shape: Tuple[int, ...]
    axis_names: Tuple[str, ...]
    lost_ids: Tuple[int, ...]
    n_idle: int                        # survivors the new shape leaves unused
    degraded_axes: Tuple[str, ...]     # axes that shrank, major-to-minor


def validate_param_divisibility(params, cfg, mesh) -> None:
    """Assert every ``param_specs`` spec materializes on ``mesh``: each
    spec entry's mesh-axis product must divide its dim exactly.
    ``param_specs`` filters non-dividing axes by construction, so a failure
    here means the sharding layer's contract broke — the reshrink must not
    commit to the mesh."""
    from repro.dist.sharding import _mesh_sizes, param_pspec, spec_divisible
    sizes = _mesh_sizes(mesh)
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        spec = param_pspec(path, leaf, cfg, axis_sizes=sizes)
        if not spec_divisible(leaf.shape, spec, sizes):
            raise ReshrinkError(
                f"param {jax.tree_util.keystr(path)} shape {tuple(leaf.shape)} "
                f"does not divide over spec {spec} on the reshrunk mesh "
                f"{sizes} — refusing to re-shard")


def plan_reshrink(mesh, lost_device_ids: Iterable[int], *, global_batch: int,
                  params=None, cfg=None) -> ReshrinkPlan:
    """Re-factorize ``(pod, data, model)`` over the surviving devices.

    Degradation order (the cheapest semantic change first):

    1. **data** — shrinking data-parallel width only re-slices the batch;
       the candidate must keep ``global_batch`` divisible by the composite
       (pod, data) width so the batch stays sharded (``tokens_pspec``'s own
       criterion);
    2. **pod** — collapses cross-pod replication into the remaining pods;
    3. **model** — last resort: every weight re-lays-out and per-chip
       parameter memory grows.

    The survivors keep their original mesh-major order (a deterministic
    function of the lost set), so two processes that observe the same loss
    derive the same mesh.  When ``params``/``cfg`` are given the winning
    shape is validated against ``param_specs`` divisibility before being
    returned.
    """
    lost = frozenset(int(i) for i in lost_device_ids)
    survivors = [d for d in mesh.devices.flatten() if d.id not in lost]
    if not survivors:
        raise ReshrinkError("no surviving devices")
    axes = tuple(mesh.axis_names)
    old = tuple(int(s) for s in mesh.devices.shape)
    sizes = dict(zip(axes, old))
    pod0 = sizes.get("pod", 1)
    data0 = sizes.get("data", 1)
    model0 = sizes.get("model", 1)
    n = len(survivors)

    def batch_ok(p, d):
        ndp = p * d
        return global_batch % ndp == 0 and global_batch >= ndp

    candidates = []
    for m in range(model0, 0, -1):               # model degrades last ...
        for p in range(pod0, 0, -1):             # ... pod second ...
            for d in range(data0, 0, -1):        # ... data first
                if p * d * m <= n and batch_ok(p, d):
                    candidates.append((m, p, d))
    if not candidates:
        raise ReshrinkError(
            f"cannot re-factorize {dict(sizes)} over {n} survivors with "
            f"global_batch={global_batch}")
    # preference: max model, then max pod, then max data — exactly the
    # degradation order (the sort above already emits in that order)
    m, p, d = candidates[0]

    shape = []
    for a in axes:
        shape.append({"pod": p, "data": d, "model": m}.get(a, 1))
    shape = tuple(shape)
    count = int(np.prod(shape))
    devs = np.array(survivors[:count], dtype=object).reshape(shape)
    new_mesh = jax.sharding.Mesh(devs, axes)
    if params is not None and cfg is not None:
        validate_param_divisibility(params, cfg, new_mesh)
    degraded = tuple(a for a, o, s in zip(axes, old, shape) if s < o)
    return ReshrinkPlan(mesh=new_mesh, old_shape=old, new_shape=shape,
                        axis_names=axes, lost_ids=tuple(sorted(lost)),
                        n_idle=n - count, degraded_axes=degraded)
