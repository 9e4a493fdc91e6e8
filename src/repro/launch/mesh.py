"""Production mesh definitions.

make_production_mesh is a FUNCTION (never a module-level constant) so importing
this module does not touch jax device state — the dry-run must set XLA_FLAGS
before the first jax device query.
"""
from __future__ import annotations

import jax



def make_mesh(shape, axes):
    """Mesh over the local devices with every axis in Auto mode (the
    sharding rules place arrays; the partitioner fills in the rest)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_small_mesh():
    """4x2 = 8 placeholder chips (data, model): the --small dry-run mesh the
    roofline benchmark self-generates records on (REPRO_DRYRUN_DEVICES=8)."""
    return make_mesh((4, 2), ("data", "model"))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over real local devices (tests / CPU examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(1, n // data))
    return make_mesh((data, model), ("data", "model"))
