"""Compile-only checks of every Pallas kernel on the engine's main path for a
TPU v5e chip, at the sizes a real deployment runs them.

Nothing executes: each kernel is lowered with shapes placed on one chip of
a described (not attached) ``v5e:2x2`` topology and compiled by the TPU's
own compiler, which refuses what interpret mode accepts (per-lane VMEM
gathers, int32 MXU matmuls, stacked i1 vectors, scoped-VMEM overflow).
Each compile must yield a Mosaic ``tpu_custom_call``. The exchange's mesh
programs are compiled the same way for all four chips of the topology, at
the sizes TPC-H Q3 gives them at SF 1, and must lower to their ICI
collectives. Where no v5e topology can be described, the fixture skips
these tests.
"""

from __future__ import annotations

import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.core import dtypes as dt
from repro.core import exchange, fused
from repro.core.batch import ParamRef, apply_batched_stages
from repro.core.expr import col
from repro.core.table import DeviceTable

# the kernel modules (the package re-exports same-named functions)
segagg = importlib.import_module("repro.kernels.segmented_agg")
probe = importlib.import_module("repro.kernels.hash_probe")
radix = importlib.import_module("repro.kernels.radix_histogram")
prefix = importlib.import_module("repro.kernels.block_prefix_sum")

N_ROWS = 1 << 20          # rows per aggregation / histogram / compaction call
N_GROUPS = 1 << 16        # the engine's inclusive pallas group bound
TABLE_SLOTS = 1 << 18     # operators.MAX_HASH_TABLE_SLOTS
MORSEL = 8192             # the engine's default batch_rows
I32, F32 = jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 topology, with the persistent compile cache off
    (an entry compiled for a described chip cannot be read back here)."""
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the described topology."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The engine's 1-D ``workers`` mesh over the topology's four chips."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    return Mesh(np.asarray(topo.devices).reshape(4), ("workers",),
                axis_types=(AxisType.Auto,))


def _q6_table(shape):
    """A scan morsel of Q6's columns plus a bytes column (l_shipmode,
    uint8[rows, 10]) that rides through the kernel."""
    schema = {"l_shipdate": dt.DATE32, "l_discount": dt.FLOAT32,
              "l_quantity": dt.FLOAT32, "l_extendedprice": dt.FLOAT32,
              "l_orderkey": dt.INT32, "l_shipmode": dt.bytes_(10)}
    cols = {n: shape(d.storage_shape(MORSEL), d.jnp_dtype())
            for n, d in schema.items()}
    return DeviceTable(cols, shape((MORSEL,), jnp.bool_), schema)


_Q6_FILTER = (col("l_shipdate").between(8766, 9130)
              & col("l_discount").between(0.05, 0.07)
              & (col("l_quantity") < 24.0))
_Q6_STAGES = ((_Q6_FILTER, None),
              (None, (("revenue", col("l_extendedprice") * col("l_discount")),
                      ("l_orderkey", col("l_orderkey")),
                      ("l_shipmode", col("l_shipmode")))))


def _fused_probe(t, tk, tv):
    spec = dict(tk=tk, tv=tv, probe_keys=("l_orderkey",), pack=None,
                empty_key=-1, max_probes=64)
    return fused.fused_morsel_program(t, _Q6_STAGES, probe=spec,
                                      interpret=False)


_BATCH_MEMBERS = 16
# a stacked small query: one quantity literal per member
_BATCH_STAGES = ((col("l_quantity") < ParamRef(0, dt.FLOAT32), None),
                 (None, (("revenue",
                          col("l_extendedprice") * col("l_discount")),)))


def _fused_batch(t, quantity):
    return fused.fused_batch_program(
        t, (quantity,),
        lambda tb, pr: apply_batched_stages(tb, _BATCH_STAGES, pr,
                                            _BATCH_MEMBERS),
        _BATCH_MEMBERS, interpret=False)


# name -> (function, argument shapes as (shape, dtype) or a table builder)
KERNELS = {
    "segmented_sum": (
        lambda g, v: segagg.segmented_sum(g, v, N_GROUPS, interpret=False),
        [((N_ROWS,), I32), ((N_ROWS,), F32)]),
    "segmented_int_sum": (
        lambda g, v: segagg.segmented_int_sum(g, v, N_GROUPS,
                                              interpret=False),
        [((N_ROWS,), I32), ((N_ROWS,), I32)]),
    "segmented_min": (
        lambda g, v: segagg.segmented_minmax(g, v, N_GROUPS, "min",
                                             interpret=False),
        [((N_ROWS,), I32), ((N_ROWS,), F32)]),
    "segmented_max_int": (
        lambda g, v: segagg.segmented_minmax(g, v, N_GROUPS, "max",
                                             interpret=False),
        [((N_ROWS,), I32), ((N_ROWS,), I32)]),
    "hash_probe": (
        lambda tk, tv, k: probe.hash_probe(tk, tv, k, max_probes=64,
                                           interpret=False),
        [((TABLE_SLOTS,), I32), ((TABLE_SLOTS,), I32), ((MORSEL,), I32)]),
    "hash_probe_multi": (
        lambda tk, tv, k: probe.hash_probe_multi(tk, tv, k, 4, max_probes=64,
                                                 interpret=False),
        [((TABLE_SLOTS,), I32), ((TABLE_SLOTS,), I32), ((MORSEL,), I32)]),
    "radix_histogram": (
        lambda p: radix.radix_histogram(p, 4, interpret=False),
        [((N_ROWS,), I32)]),
    "block_prefix_sum": (
        lambda m: prefix.block_prefix_sum(m, interpret=False),
        [((N_ROWS,), jnp.bool_)]),
    "fused_morsel": (
        lambda t: fused.fused_morsel_program(t, _Q6_STAGES,
                                             interpret=False)[0],
        [_q6_table]),
    "fused_morsel_probe": (
        _fused_probe,
        [_q6_table, ((TABLE_SLOTS,), I32), ((TABLE_SLOTS,), I32)]),
    "fused_batch": (
        _fused_batch, [_q6_table, ((_BATCH_MEMBERS,), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = KERNELS[name]

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=one_chip)

    args = [spec(shape) if callable(spec) else shape(*spec)
            for spec in specs]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, name


# Q3 at SF 1 on four workers: the largest repartition sends lineitem's
# (l_orderkey, revenue) rows in [W_src, W_dst, 2^19] buffers; the largest
# broadcast replicates a 2^16-row build side
MESH_PROGRAMS = {
    "exchange_data": ("all-to-all", lambda mesh, sh: exchange._exchange_data
                      .lower(_mesh_table(sh, (4, 4, 1 << 19)), mesh,
                             "workers", 4, 1 << 19)),
    "broadcast_data": ("all-gather", lambda mesh, sh: exchange
                       ._broadcast_data.lower(_mesh_table(sh, (4, 1 << 16)),
                                              mesh, "workers", 4)),
}


def _mesh_table(sharding, shape):
    def leaf(d):
        return jax.ShapeDtypeStruct(shape, d, sharding=sharding)

    schema = {"l_orderkey": dt.INT32, "revenue": dt.FLOAT32}
    return DeviceTable({"l_orderkey": leaf(I32), "revenue": leaf(F32)},
                       leaf(jnp.bool_), schema)


@pytest.mark.parametrize("name", sorted(MESH_PROGRAMS))
def test_mesh_exchange_compiles_for_four_v5e_chips(four_chips, name):
    from jax.sharding import NamedSharding, PartitionSpec

    collective, lower = MESH_PROGRAMS[name]
    sharding = NamedSharding(four_chips, PartitionSpec("workers"))
    hlo = lower(four_chips, sharding).compile().as_text()
    assert collective in hlo, name
