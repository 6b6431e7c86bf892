"""Tiered-memory spill subsystem tests (core.spill + spill-aware operators).

Three layers of coverage:

* ``SpillManager`` unit/property tests — reservation accounting,
  largest-first victim selection, and *bit-exact* tier round-trips through
  host buffers and the paged disk format (extreme int64 values included:
  the disk codec must not rely on the paged format's delta encoding).
* Forced-spill differentials — a device budget far below the working set
  makes every memory-hungry operator (grace join, flushing aggregation)
  take its spill path; results must stay oracle-identical and the spill
  counters must show real tier crossings.
* The full 22-query out-of-core sweep at ~1/4 of the estimated footprint,
  across the streaming/distributed/pallas paths (``out_of_core`` marker:
  slow, runs as its own CI job).
"""

import os
import tempfile

import numpy as np
import pytest

from repro.core import ICIExchange, Session, dtypes as dt
from repro.core.spill import (HostMemoryBudget, SpillCapacityError,
                              SpillManager)
from repro.core.table import DeviceTable
from repro.tpch import dbgen, oracle, queries

from _hypothesis_compat import ints, sampled, seeded_given
from repro.tpch.compare import assert_results_match

SF = 0.002


@pytest.fixture(scope="module")
def data():
    return dbgen.generate(sf=SF)


@pytest.fixture(scope="module")
def catalog():
    return dbgen.load_catalog(sf=SF)


# ---------------------------------------------------------------------------
# SpillManager: reservations
# ---------------------------------------------------------------------------

def test_reservation_accounting():
    mgr = SpillManager(device_budget=1000)
    assert mgr.reserve("a", 600) == 600
    assert mgr.reserve("b", 600) == 400          # clipped to what's left
    assert mgr.stats.reserve_denials == 1
    assert mgr.device_reserved() == 1000
    assert mgr.device_available() == 0
    mgr.release("a")
    assert mgr.device_reserved() == 400
    assert mgr.reserved("a") == 0 and mgr.reserved("b") == 400
    mgr.release("b", 100)                        # partial release
    assert mgr.reserved("b") == 300
    assert mgr.stats.reserved_peak == 1000
    mgr.close()


def test_reserve_minimum_oversubscribes_for_progress():
    # a zero-available budget still grants the minimum: operators always
    # make progress, the budget just goes (accounted) negative
    mgr = SpillManager(device_budget=100)
    assert mgr.reserve("big", 100) == 100
    assert mgr.reserve("next", 500, minimum=64) == 64
    assert mgr.device_available() == -64
    assert mgr.stats.reserve_denials == 1
    mgr.close()


def test_should_stage_tracks_available_budget():
    mgr = SpillManager(device_budget=1000)
    assert not mgr.should_stage(800)
    mgr.reserve("op", 600)
    assert mgr.should_stage(800)
    assert not mgr.should_stage(400)
    mgr.close()


def test_host_budget_progress_guarantee():
    budget = HostMemoryBudget(100)
    # an oversize request is admitted when nothing is held
    assert budget.acquire(500)
    assert budget.in_use == 500
    assert not budget.try_acquire(1)             # full now
    budget.release(500)
    assert budget.try_acquire(80) and budget.try_acquire(20)
    assert not budget.try_acquire(1)
    budget.release(100)


# ---------------------------------------------------------------------------
# SpillManager: tiers and victim selection
# ---------------------------------------------------------------------------

def _part(n_rows: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    cols = {"k": rng.integers(-1 << 62, 1 << 62, n_rows, dtype=np.int64),
            "v": rng.standard_normal(n_rows).astype(np.float32)}
    validity = rng.random(n_rows) < 0.9
    schema = {"k": dt.INT64, "v": dt.FLOAT32}
    return cols, validity, schema


def test_largest_first_victim_selection(tmp_path):
    small = _part(10, seed=1)
    large = _part(1000, seed=2)
    mid = _part(100, seed=3)
    mgr = SpillManager(device_budget=0, host_budget=2000,
                       spill_dir=str(tmp_path))
    mgr.put_host("small", *small)
    mgr.put_host("large", *large)                # overflows the host tier
    mgr.put_host("mid", *mid)
    # the largest partition is the disk victim; the small ones stay hot
    assert mgr.tier_of("large") == "disk"
    assert mgr.tier_of("small") == "host"
    assert mgr.stats.disk.spills >= 1
    assert mgr.stats.host.spills == 3            # all passed through host
    # restores drain both tiers and delete the disk file
    for key, (cols, validity, _schema) in [("large", large), ("small", small),
                                           ("mid", mid)]:
        got_cols, got_validity, _ = mgr.restore_host(key)
        np.testing.assert_array_equal(got_validity, validity)
        for c in cols:
            np.testing.assert_array_equal(got_cols[c], cols[c])
    assert mgr.keys() == []
    assert not any(f.endswith(".paged") for f in os.listdir(tmp_path))
    mgr.close()


def test_disk_ceiling_raises(tmp_path):
    mgr = SpillManager(device_budget=0, host_budget=0,
                       spill_dir=str(tmp_path), disk_ceiling=64)
    with pytest.raises(SpillCapacityError, match="disk ceiling"):
        mgr.put_host("p", *_part(1000))
    mgr.close()


def test_close_removes_own_spill_dir():
    mgr = SpillManager(device_budget=0, host_budget=0)   # every put -> disk
    mgr.put_host("p", *_part(100))
    root = mgr._dir()
    assert os.path.isdir(root)
    mgr.close()
    assert not os.path.isdir(root)
    # counters survive close for executor_stats
    assert mgr.stats.disk.spills == 1


# ---------------------------------------------------------------------------
# tier round-trips are bit-exact (property)
# ---------------------------------------------------------------------------

@seeded_given(max_examples=15,
              dtype_name=sampled("int32", "int64", "float32", "float64",
                                 "bool", "bytes"),
              n_rows=ints(1, 300),
              stacked=sampled(False, True),
              force_disk=sampled(False, True),
              seed=ints(0, 1 << 30))
def test_tier_roundtrip_bit_exact(dtype_name, n_rows, stacked, force_disk,
                                  seed):
    rng = np.random.default_rng(seed)
    shape = (2, n_rows) if stacked else (n_rows,)
    if dtype_name == "bytes":
        d = dt.bytes_(7)
        arr = rng.integers(0, 256, shape + (7,), dtype=np.uint8)
    elif dtype_name == "bool":
        d = dt.BOOL
        arr = rng.random(shape) < 0.5
    elif dtype_name.startswith("int"):
        d = {"int32": dt.INT32, "int64": dt.INT64}[dtype_name]
        info = np.iinfo(d.np_dtype())
        # extremes included: the disk codec must not delta-encode
        arr = rng.integers(info.min, info.max, shape, dtype=d.np_dtype())
        arr.flat[0] = info.min
        arr.flat[-1] = info.max
    else:
        d = {"float32": dt.FLOAT32, "float64": dt.FLOAT64}[dtype_name]
        arr = rng.standard_normal(shape).astype(d.np_dtype())
    validity = rng.random(shape[:2] if stacked else shape) < 0.8
    # one directory per example: a function-scoped fixture would be shared
    # by every example (and hypothesis refuses one under @given)
    with tempfile.TemporaryDirectory() as spill_dir:
        mgr = SpillManager(device_budget=0,
                           host_budget=0 if force_disk else 1 << 30,
                           spill_dir=spill_dir)
        mgr.put_host("p", {"c": arr}, validity, {"c": d})
        assert mgr.tier_of("p") == ("disk" if force_disk else "host")
        cols, got_validity, schema = mgr.restore_host("p")
        assert schema["c"].name == d.name
        np.testing.assert_array_equal(got_validity, validity)
        np.testing.assert_array_equal(cols["c"], arr)   # bit-exact
        assert cols["c"].dtype == arr.dtype and cols["c"].shape == arr.shape
        mgr.close()


def test_spill_device_table_roundtrip():
    cols, validity, schema = _part(64, seed=7)
    table = DeviceTable.from_numpy(cols, schema)
    mgr = SpillManager(device_budget=0, host_budget=0)   # straight to disk
    nbytes = mgr.spill_table("t", table)
    assert nbytes == table.nbytes()
    back = mgr.restore("t")
    for c in cols:
        np.testing.assert_array_equal(np.asarray(back.columns[c]),
                                      np.asarray(table.columns[c]))
    np.testing.assert_array_equal(np.asarray(back.validity),
                                  np.asarray(table.validity))
    mgr.close()


# ---------------------------------------------------------------------------
# bytes-aware prefetcher shares the host budget
# ---------------------------------------------------------------------------

def test_prefetcher_is_bytes_aware(catalog):
    from repro.core.streaming import MorselPrefetcher

    src = catalog.get("lineitem")
    budget = HostMemoryBudget(1)     # every morsel oversubscribes alone
    pre = MorselPrefetcher(
        src._host_morsels(1, ["l_orderkey"], 1024, None),
        depth=2, host_budget=budget)
    rows = sum(int(t.num_valid()) for t in pre)
    assert rows == src.num_rows()
    # all acquired bytes were released as the consumer drained
    assert budget.in_use == 0


def test_scan_shares_spill_host_budget(catalog, data):
    # the driver hands the spill manager's host meter to every scan: with
    # a budget this small, each morsel proceeds only via the
    # empty-tier progress guarantee, and the query still completes
    session = Session(catalog, num_workers=1, batch_rows=2048,
                      device_budget=1 << 20, host_budget=1)
    res = session.execute(queries.build_query(6, catalog))
    assert_results_match(res, oracle.ORACLES[6](data), 6)


# ---------------------------------------------------------------------------
# forced-spill differentials (fast tier-1 slice)
# ---------------------------------------------------------------------------

# join-heavy (3, 18), aggregation-heavy (1, 13), scan+filter (6, 14)
_FAST_QUERIES = [1, 3, 6, 13, 14, 18]


@pytest.mark.parametrize("qnum", _FAST_QUERIES)
def test_tiny_budget_oracle_identical(qnum, data, catalog):
    session = Session(catalog, num_workers=1, batch_rows=4096,
                      device_budget=16 * 1024)
    res = session.execute(queries.build_query(qnum, catalog))
    assert_results_match(res, oracle.ORACLES[qnum](data), qnum)
    spill = session.executor_stats()["spill"]
    if qnum in (3, 13, 18):       # joins/high-cardinality aggs must spill
        assert spill["spilled_bytes"] > 0, spill


def test_tiny_budget_disk_tier_exercised(data, catalog):
    # host budget squeezed too: victims cascade to paged disk files
    session = Session(catalog, num_workers=1, batch_rows=4096,
                      device_budget=512, host_budget=4096)
    res = session.execute(queries.build_query(3, catalog))
    assert_results_match(res, oracle.ORACLES[3](data), 3)
    spill = session.executor_stats()["spill"]
    assert spill["disk"]["spills"] > 0 and spill["disk"]["restores"] > 0
    # partitions proven unmatchable are dropped, not restored
    assert spill["disk"]["restored_bytes"] <= spill["disk"]["spilled_bytes"]


def test_tiny_budget_distributed(data, catalog):
    session = Session(catalog, num_workers=4, exchange=ICIExchange(),
                      batch_rows=2048, device_budget=16 * 1024)
    res = session.execute(queries.build_query(3, catalog))
    assert_results_match(res, oracle.ORACLES[3](data), 3)
    assert session.executor_stats()["spill"]["spilled_bytes"] > 0


# ---------------------------------------------------------------------------
# full out-of-core sweep (own CI job)
# ---------------------------------------------------------------------------

def _quarter_budget(session, plan) -> int:
    from repro.core.optimizer import estimate_memory
    est = estimate_memory(session.optimize(plan), session.catalog,
                          num_workers=session.num_workers,
                          batch_rows=session.batch_rows,
                          prefetch_depth=session.prefetch_depth)
    return max(est // 4, 1024)


@pytest.mark.out_of_core
@pytest.mark.parametrize("qnum", sorted(queries.QUERIES))
def test_out_of_core_sweep_streaming(qnum, data, catalog):
    """All 22 queries, device budget = 1/4 of the estimated footprint."""
    plan = queries.build_query(qnum, catalog)
    probe = Session(catalog, num_workers=1, batch_rows=4096)
    session = Session(catalog, num_workers=1, batch_rows=4096,
                      device_budget=_quarter_budget(probe, plan))
    res = session.execute(plan)
    assert_results_match(res, oracle.ORACLES[qnum](data), qnum)


@pytest.mark.out_of_core
@pytest.mark.parametrize("qnum", [1, 3, 5, 9, 13, 18, 22])
def test_out_of_core_sweep_distributed(qnum, data, catalog):
    plan = queries.build_query(qnum, catalog)
    probe = Session(catalog, num_workers=4, batch_rows=2048)
    session = Session(catalog, num_workers=4, exchange=ICIExchange(),
                      batch_rows=2048,
                      device_budget=_quarter_budget(probe, plan))
    res = session.execute(plan)
    assert_results_match(res, oracle.ORACLES[qnum](data), qnum)


@pytest.mark.out_of_core
@pytest.mark.parametrize("qnum", [1, 3, 6, 13, 14, 18])
def test_out_of_core_sweep_pallas(qnum, data, catalog):
    plan = queries.build_query(qnum, catalog)
    probe = Session(catalog, num_workers=1, batch_rows=4096)
    session = Session(catalog, num_workers=1, batch_rows=4096,
                      kernel_backend="pallas",
                      device_budget=_quarter_budget(probe, plan))
    res = session.execute(plan)
    assert_results_match(res, oracle.ORACLES[qnum](data), qnum)
