"""The engine's worker mesh (built in a function, so importing this module
never touches jax device state)."""

from __future__ import annotations


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

