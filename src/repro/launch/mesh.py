"""Production meshes (defined as functions so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS first)."""

from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods."""
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    assert len(devices) >= n, (
        f"need {n} devices, have {len(devices)} — the dry-run entry point "
        "must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
        "before any jax import")
    from jax.sharding import Mesh
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_engine_mesh(num_workers: int):
    """1-D worker mesh for the query engine (one worker per device).

    The ``workers`` axis is ``AxisType.Auto``: the engine states its
    placement with sharding constraints and lets the partitioner carry it
    through gathers and sorts. ``jax.make_mesh`` defaults to Explicit axes,
    under which those operations raise ``ShardingTypeError``.
    """
    import jax
    from jax.sharding import AxisType

    devices = jax.devices()
    if len(devices) < num_workers:
        raise ValueError(f"a {num_workers}-worker mesh needs {num_workers} "
                         f"devices; {len(devices)} available")
    return jax.make_mesh((num_workers,), ("workers",),
                         axis_types=(AxisType.Auto,),
                         devices=devices[:num_workers])


def axes_of(mesh):
    """Sharding-policy Axes from a production mesh."""
    from ..models.sharding import Axes

    names = mesh.axis_names
    if "pod" in names:
        dp = ("pod", "data")
    else:
        dp = ("data",)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    return Axes(dp=dp, tp="model", dp_size=dp_size,
                tp_size=int(mesh.shape["model"]))
