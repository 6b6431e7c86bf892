"""Inter-query batching: eligibility, stacked-launch correctness, inertness.

Unmarked tests are tier-1 fast checks of the pure pieces: shape
extraction and program interning (``core.batch``), the stacked
group-capacity bound (``kernels.segmented_agg``), the scheduler's
per-program batch limit, and the disabled path's inertness contract
(``SchedulerConfig.batching=False`` must never touch batch state).

``@pytest.mark.batching`` tests are the runtime sweep (own CI job,
deselected from the default run via pyproject ``addopts``): the seeded
batched == serial property test, the incompatibility regressions
(snapshot versions, kernel backends, capacity overflow must degrade to
solo — never produce wrong results), and the batched small-query fuzz
corpus diffed against DuckDB (skips loudly without the ``[sql]`` extra).

Env knobs: ``BATCHING_SF`` (default 0.005), ``BATCHING_FUZZ_N``
(default 24), ``BATCHING_SEED`` (default 11).
"""

from __future__ import annotations

import functools
import os
import threading
import types

import numpy as np
import pytest

from repro.core import Session
from repro.core import batch as B
from repro.core import dtypes as dt
from repro.core import relational as rel
from repro.core.builder import QueryBuilder
from repro.core.expr import col
from repro.core.scheduler import SchedulerConfig
from repro.core.session import ExecutionOptions
from repro.kernels import segmented_agg as segagg
from repro.tpch import dbgen

from sql_oracle import (connect_with_catalog, diff_results,
                        fuzz_small_queries, require_duckdb, run_duckdb)

SF = float(os.environ.get("BATCHING_SF", "0.005"))
FUZZ_N = int(os.environ.get("BATCHING_FUZZ_N", "24"))
SEED = int(os.environ.get("BATCHING_SEED", "11"))


@functools.lru_cache(maxsize=1)
def dataset():
    return dbgen.generate(sf=SF), dbgen.load_catalog(sf=SF)


def _sched_config(**over) -> SchedulerConfig:
    base = dict(memory_budget=512 << 20, max_concurrency=4, max_queue=256,
                cache_results=False, batching=True, batch_window_ms=150.0,
                max_batch=32)
    base.update(over)
    return SchedulerConfig(**base)


def _workload(catalog, order_keys, n: int):
    """``n`` distinct-literal small queries cycling three batchable
    shapes (point lookup / filtered global agg / low-card group-by)."""
    out = []
    for i in range(n):
        kind = i % 3
        if kind == 0:
            key = int(order_keys[(i * 29) % len(order_keys)])
            out.append(QueryBuilder.scan(catalog, "orders")
                       .filter(col("o_orderkey") == key)
                       .project("o_orderkey", "o_totalprice"))
        elif kind == 1:
            out.append(QueryBuilder.scan(catalog, "lineitem")
                       .filter(col("l_quantity") < float(2 + (i % 47)))
                       .agg(total=("sum", "l_extendedprice"),
                            n=("count", None)))
        else:
            out.append(QueryBuilder.scan(catalog, "lineitem")
                       .filter(col("l_quantity") < float(3 + (i % 43)))
                       .group_by("l_returnflag")
                       .agg(total=("sum", "l_extendedprice"),
                            n=("count", None)))
    return out


def _submit_concurrently(session, builders, n_clients: int = 4):
    """Submit from client threads (so the batch window sees stragglers);
    returns handles in builder order."""
    handles: list = [None] * len(builders)
    errors: list = []

    def client(c: int):
        try:
            for i in range(c, len(builders), n_clients):
                handles[i] = session.submit(builders[i])
        except Exception as exc:  # noqa: BLE001 -- re-raised below
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    session.gather(*handles)
    return handles


def _assert_columns_equal(ref: dict, got: dict, label: str) -> None:
    """Exact row identity for ints/keys; allclose for floats (the stacked
    one-hot contraction reduces in a different order than solo)."""
    assert set(ref) == set(got), f"{label}: column sets differ"
    for c in ref:
        r, g = np.asarray(ref[c]), np.asarray(got[c])
        assert r.shape == g.shape, f"{label}.{c}: {r.shape} != {g.shape}"
        if np.issubdtype(r.dtype, np.floating):
            np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-6,
                                       err_msg=f"{label}.{c}")
        else:
            np.testing.assert_array_equal(g, r, err_msg=f"{label}.{c}")


# ---------------------------------------------------------------------------
# tier-1: stacked group capacity (kernels.segmented_agg)
# ---------------------------------------------------------------------------

def test_stacked_group_capacity_bound():
    limit = segagg.STACKED_GROUP_LIMIT
    for mg in [1, 2, 3, 7, 16, 100, 4096, limit // 2, limit, limit + 1,
               limit * 4]:
        cap = segagg.stacked_group_capacity(mg)
        assert cap >= 1
        assert cap & (cap - 1) == 0, f"capacity {cap} not a power of two"
        if cap > 1:
            # the stacked problem must fit the kernel dispatch bound,
            # and cap is the largest power of two that does
            assert cap * mg <= limit
            assert 2 * cap > limit // mg
    # a query whose max_groups alone exceeds the limit degrades to solo
    assert segagg.stacked_group_capacity(limit + 1) == 1
    assert segagg.stacked_group_capacity(limit * 8) == 1
    with pytest.raises(ValueError):
        segagg.stacked_group_capacity(0)


def test_stacked_capacity_matches_kernel_limit():
    # hand-synced constant (kernels must not import core): a drift would
    # let a stacked problem exceed what the pallas kernels accept
    assert segagg.STACKED_GROUP_LIMIT == rel.PALLAS_AGG_GROUP_LIMIT


# ---------------------------------------------------------------------------
# tier-1: shape extraction + program interning (core.batch)
# ---------------------------------------------------------------------------

def test_extract_shape_interns_literal_variants():
    _, catalog = dataset()
    a = B.extract_shape(
        QueryBuilder.scan(catalog, "orders")
        .filter(col("o_orderkey") == 7)
        .project("o_orderkey", "o_totalprice").optimized())
    b = B.extract_shape(
        QueryBuilder.scan(catalog, "orders")
        .filter(col("o_orderkey") == 1953)
        .project("o_orderkey", "o_totalprice").optimized())
    assert a is not None and b is not None
    # literal-only variants intern to ONE program (the stacked compile
    # cache key); the literals come back as per-member parameters
    assert a.program is b.program
    assert a.params != b.params
    assert len(a.params) == len(a.program.param_dtypes) == 1


def test_extract_shape_eligible_aggregates():
    _, catalog = dataset()
    keyed = B.extract_shape(
        QueryBuilder.scan(catalog, "lineitem")
        .filter(col("l_quantity") < 5.0)
        .group_by("l_returnflag")
        .agg(total=("sum", "l_extendedprice"), n=("count", None),
             m=("avg", "l_discount")).optimized())
    assert keyed is not None
    assert keyed.program.group_keys == ("l_returnflag",)
    assert keyed.program.max_groups >= 1
    glob = B.extract_shape(
        QueryBuilder.scan(catalog, "lineitem")
        .filter(col("l_quantity") < 5.0)
        .agg(lo=("min", "l_extendedprice"),
             hi=("max", "l_extendedprice")).optimized())
    assert glob is not None
    assert glob.program.group_keys == ()


def test_extract_shape_rejects_unsupported_plans():
    _, catalog = dataset()
    li = QueryBuilder.scan(catalog, "lineitem").filter(col("l_quantity") < 5.0)
    orders = QueryBuilder.scan(catalog, "orders")
    assert B.extract_shape(
        li.join(orders, ["l_orderkey"], ["o_orderkey"])
        .agg(n=("count", None)).optimized()) is None
    assert B.extract_shape(
        li.project("l_orderkey").order_by("l_orderkey").optimized()) is None
    assert B.extract_shape(
        li.project("l_orderkey").limit(5).optimized()) is None
    assert B.extract_shape(
        li.distinct("l_returnflag").optimized()) is None


def test_batch_limit_caps_keyed_programs():
    _, catalog = dataset()
    session = Session(catalog, num_workers=1, batch_rows=16384)
    session.scheduler_config = _sched_config()
    sch = session.scheduler()
    keyed = B.extract_shape(
        QueryBuilder.scan(catalog, "lineitem")
        .group_by("l_returnflag")
        .agg(n=("count", None)).optimized())
    assert sch._batch_limit(keyed.program) == min(
        sch.config.max_batch,
        segagg.stacked_group_capacity(keyed.program.max_groups))
    # keyless programs take the configured cap unmodified
    glob = types.SimpleNamespace(group_keys=(), max_groups=1)
    assert sch._batch_limit(glob) == sch.config.max_batch
    # capacity overflow (max_groups alone exceeds the kernel bound)
    # degrades to solo: a limit of 1 means no batch ever forms
    over = types.SimpleNamespace(group_keys=("k",),
                                 max_groups=rel.PALLAS_AGG_GROUP_LIMIT + 1)
    assert sch._batch_limit(over) == 1
    sch.close()


# ---------------------------------------------------------------------------
# tier-1: the disabled path is inert
# ---------------------------------------------------------------------------

def test_disabled_batching_is_inert():
    data, catalog = dataset()
    keys = np.asarray(data["orders"]["o_orderkey"])
    builders = _workload(catalog, keys, 6)
    refs = [Session(catalog, num_workers=1, batch_rows=16384).execute(
        b.optimized()) for b in builders]

    assert SchedulerConfig().batching is False   # opt-in by default
    session = Session(catalog, num_workers=1, batch_rows=16384)
    session.scheduler_config = _sched_config(batching=False)
    try:
        handles = _submit_concurrently(session, builders)
        stats = session.scheduler().stats()
        assert stats["batches"] == 0
        assert stats["batched_queries"] == 0
        for i, h in enumerate(handles):
            # the disabled path never inspects the plan for batchability
            assert h._batch_shape is None and h._batch_key is None
            assert "batch" not in h.executor_stats
            _assert_columns_equal(refs[i], h.result(), f"q{i}")
    finally:
        session.scheduler().close()


def test_stacked_failure_is_counted_and_solo_results_stay_correct(
        monkeypatch):
    """A stacked launch that raises re-runs its members solo: the results
    stay right, and ``stats()`` counts the failure and keeps its text."""
    from repro.core.driver import Driver

    def broken(self, shapes, lanes=None):
        raise RuntimeError("stacked program refused")

    monkeypatch.setattr(Driver, "collect_batch", broken)
    data, catalog = dataset()
    keys = np.asarray(data["orders"]["o_orderkey"])
    builders = [_workload(catalog, keys, 3)[1]] * 4 + \
        _workload(catalog, keys, 6)[3:]
    serial = Session(catalog, num_workers=1, batch_rows=16384)
    refs = [serial.execute(b.optimized()) for b in builders]

    session = Session(catalog, num_workers=1, batch_rows=16384)
    session.scheduler_config = _sched_config()
    try:
        assert session.scheduler().stats()["batch_failures"] == 0
        handles = _submit_concurrently(session, builders)
        stats = session.scheduler().stats()
        assert stats["batches"] == 0
        assert stats["batch_failures"] >= 1
        assert stats["first_batch_failure"] == (
            "RuntimeError: stacked program refused")
        for i, h in enumerate(handles):
            assert "batch" not in h.executor_stats
            _assert_columns_equal(refs[i], h.result(), f"q{i}")
    finally:
        session.scheduler().close()


# ---------------------------------------------------------------------------
# -m batching: batched == serial property test
# ---------------------------------------------------------------------------

@pytest.mark.batching
def test_batched_equals_serial_property():
    """Seeded concurrent small-query workload through the batching
    scheduler must return the same rows as scheduler-less serial
    execution, and must actually form stacked launches."""
    data, catalog = dataset()
    keys = np.asarray(data["orders"]["o_orderkey"])
    builders = _workload(catalog, keys, 24)
    serial = Session(catalog, num_workers=1, batch_rows=16384)
    refs = [serial.execute(b.optimized()) for b in builders]

    session = Session(catalog, num_workers=1, batch_rows=16384)
    session.scheduler_config = _sched_config()
    try:
        handles = _submit_concurrently(session, builders)
        stats = session.scheduler().stats()
        assert stats["batches"] >= 1, "no stacked launch formed"
        assert stats["batched_queries"] >= 2
        batched = [h for h in handles if "batch" in h.executor_stats]
        assert len(batched) == stats["batched_queries"]
        for h in batched:
            b = h.executor_stats["batch"]
            assert b["size"] >= 2 and b["queue_delay_s"] >= 0.0
        for i, h in enumerate(handles):
            _assert_columns_equal(refs[i], h.result(), f"q{i}")
    finally:
        session.scheduler().close()


# ---------------------------------------------------------------------------
# -m batching: incompatibility regressions
# ---------------------------------------------------------------------------

@pytest.mark.batching
def test_snapshot_version_gates_compatibility():
    """Re-registering a table bumps its version; queries admitted across
    the bump share a program but must never share a stacked launch."""
    data, catalog = dataset()
    keys = np.asarray(data["orders"]["o_orderkey"])
    session = Session(catalog, num_workers=1, batch_rows=16384)
    session.scheduler_config = _sched_config()
    try:
        q = _workload(catalog, keys, 1)[0]
        h1 = session.submit(q)
        r1 = h1.result()
        src = catalog.get("orders")
        catalog.register(src)          # same data, new version
        h2 = session.submit(q)
        r2 = h2.result()
        assert h1._batch_key == h2._batch_key       # same interned program
        assert h1._versions != h2._versions         # ...different snapshot
        _assert_columns_equal(r1, r2, "across-version")
    finally:
        session.scheduler().close()


@pytest.mark.batching
def test_backend_is_part_of_batch_key():
    data, catalog = dataset()
    keys = np.asarray(data["orders"]["o_orderkey"])
    session = Session(catalog, num_workers=1, batch_rows=16384)
    session.scheduler_config = _sched_config()
    try:
        q = _workload(catalog, keys, 3)[2]          # keyed group-by
        h_jnp = session.submit(q)
        h_pal = session.submit(
            q, options=ExecutionOptions(kernel_backend="pallas"))
        r_jnp, r_pal = h_jnp.result(), h_pal.result()
        assert h_jnp._batch_key is not None and h_pal._batch_key is not None
        assert h_jnp._batch_key[0] is h_pal._batch_key[0]   # same program
        assert h_jnp._batch_key != h_pal._batch_key         # different key
        _assert_columns_equal(r_jnp, r_pal, "across-backend")
    finally:
        session.scheduler().close()


@pytest.mark.batching
def test_capacity_overflow_degrades_to_solo():
    """A keyed program whose ``max_groups`` alone exceeds the stacked
    kernel bound must run solo (no batch ever forms) and stay correct."""
    data, catalog = dataset()
    n = rel.PALLAS_AGG_GROUP_LIMIT + 100         # row bound > kernel limit
    rng = np.random.default_rng(3)
    wide = {"k": rng.integers(0, n, n).astype(np.int32),
            "v": rng.random(n).astype(np.float32)}
    catalog.register_numpy("wide_groups", wide,
                           {"k": dt.INT32, "v": dt.FLOAT32})
    serial = Session(catalog, num_workers=1, batch_rows=16384)

    def q(lo: float):
        return (QueryBuilder.scan(catalog, "wide_groups")
                .filter(col("v") > lo)
                .group_by("k").agg(total=("sum", "v"), cnt=("count", None)))

    builders = [q(0.1 + 0.01 * i) for i in range(3)]
    refs = [serial.execute(b.optimized()) for b in builders]
    shape = B.extract_shape(builders[0].optimized())
    assert shape is not None
    assert shape.program.max_groups > rel.PALLAS_AGG_GROUP_LIMIT

    session = Session(catalog, num_workers=1, batch_rows=16384)
    session.scheduler_config = _sched_config()
    try:
        assert session.scheduler()._batch_limit(shape.program) == 1
        handles = _submit_concurrently(session, builders, n_clients=3)
        stats = session.scheduler().stats()
        assert stats["batches"] == 0             # degraded to solo...
        for i, h in enumerate(handles):
            assert "batch" not in h.executor_stats
            _assert_columns_equal(refs[i], h.result(), f"q{i}")   # ...never wrong
    finally:
        session.scheduler().close()


# ---------------------------------------------------------------------------
# -m batching: small-query fuzz corpus vs DuckDB through the batched path
# ---------------------------------------------------------------------------

@pytest.mark.batching
def test_batched_fuzz_vs_duckdb():
    require_duckdb()
    _, catalog = dataset()
    con = connect_with_catalog(catalog)
    session = Session(catalog, num_workers=1, batch_rows=16384)
    session.scheduler_config = _sched_config()
    try:
        # each text twice: duplicates are compatible by construction, so
        # the sweep exercises stacked launches even when the random
        # corpus spreads across templates
        texts = fuzz_small_queries(SEED, FUZZ_N, catalog) * 2
        qbs = [session.sql(t) for t in texts]
        handles: list = [None] * len(qbs)

        def client(c: int, n_clients: int = 4):
            for i in range(c, len(qbs), n_clients):
                handles[i] = qbs[i].submit()

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        session.gather(*handles)
        for text, qb, h in zip(texts, qbs, handles):
            diff_results(h.result(), run_duckdb(con, text),
                         qb.schema, sql=text)
    finally:
        session.scheduler().close()
        con.close()
