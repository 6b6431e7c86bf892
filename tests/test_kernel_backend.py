"""Backend-differential harness: the pallas kernel backend vs jnp + oracle.

The tentpole contract of the kernel-backend layer: for every TPC-H query,
``Session(kernel_backend="pallas")`` (Pallas kernels, interpret mode
off-TPU) must produce exactly the rows of the jnp backend (the sort-based
code, which doubles as the kernel oracle) and of the pure-numpy TPC-H
oracle — and ``executor_stats()['kernel_dispatch']`` must show the hot
spots actually ran on the kernels (probe/agg/compact/partition).

Layering mirrors the distributed-oracle suite:

* unmarked tests — fast smoke slice + dispatch/backend plumbing, tier-1;
* ``@pytest.mark.kernel_backend`` — the full 22-query × W∈{1,2} sweep and
  a randomized-config property pass, deselected from the default run
  (pyproject ``addopts``) and executed as its own CI job with
  ``REPRO_KERNEL_BACKEND=pallas``. ``KERNEL_BACKEND_SF`` shrinks it.
"""

from __future__ import annotations

import functools
import os

import pytest

from repro.core import Session
from repro.core import plan as P
from repro.kernels import ops as kernel_ops
from repro.tpch import dbgen, oracle, queries

from _hypothesis_compat import bools, sampled, seeded_given
from repro.tpch.compare import assert_results_match

SF = float(os.environ.get("KERNEL_BACKEND_SF", "0.002"))

# dispatch kinds specific queries must exercise under the pallas backend
# (W=2 adds 'partition' whenever the planner places a Repartition).
# "probe|fused" = the probe may run standalone or inside the fused
# per-morsel pipeline kernel, depending on whether it fused into the scan.
EXPECTED_KINDS = {
    1: {"agg"},                          # group-by aggregation
    3: {"probe|fused", "build", "agg"},  # unique-key joins + group-by
    14: {"probe|fused", "build"},        # lineitem x part join
    15: {"compact"},                     # scalar subquery -> compacted scalar
}


def _dispatched(kd: dict, kind: str) -> bool:
    """True when any of the '|'-separated alternative kinds ran."""
    return any(kd.get(k, 0) > 0 for k in kind.split("|"))


@functools.lru_cache(maxsize=2)
def dataset(sf: float):
    """(raw numpy tables, catalog) for one scale factor, cached."""
    return dbgen.generate(sf=sf), dbgen.load_catalog(sf=sf)


def run_backend(catalog, qnum: int, num_workers: int, backend: str,
                batch_rows: int = 8192, streaming: bool = True):
    """Execute ``qnum`` under ``backend``; returns (result, stats)."""
    plan = queries.build_query(qnum, catalog, num_workers=num_workers)
    session = Session(catalog, num_workers=num_workers,
                      kernel_backend=backend, batch_rows=batch_rows,
                      streaming=streaming)
    res = session.execute(plan)
    return res, session.executor_stats()


# ---------------------------------------------------------------------------
# tier-1: dispatch plumbing
# ---------------------------------------------------------------------------

def test_backend_selection_api():
    """use_backend/use_pallas scope the thread; bad names are rejected."""
    assert kernel_ops.current_backend() in kernel_ops.BACKENDS
    with kernel_ops.use_pallas():
        assert kernel_ops.current_backend() == "pallas"
        with kernel_ops.use_backend("jnp"):
            assert kernel_ops.current_backend() == "jnp"
        assert kernel_ops.current_backend() == "pallas"
    with pytest.raises(ValueError):
        with kernel_ops.use_backend("cuda"):
            pass
    with pytest.raises(ValueError):
        kernel_ops.set_default_backend("velox")


def test_session_threads_backend_into_stats():
    """Session(kernel_backend=...) reaches the driver and executor stats."""
    _, catalog = dataset(SF)
    for backend in kernel_ops.BACKENDS:
        _, stats = run_backend(catalog, 6, 1, backend)
        assert stats["kernel_backend"] == backend
    # jnp sessions never count pallas dispatches
    _, stats = run_backend(catalog, 1, 1, "jnp")
    assert stats["kernel_dispatch"] == {}


@pytest.mark.parametrize("qnum", [1, 3, 14])
def test_smoke_slice_matches_oracle_and_jnp(qnum):
    """Q1/Q3/Q14 × W∈{1,2}: pallas rows == jnp rows == oracle rows, and
    the expected kernel kinds dispatched (plus 'partition' at W=2)."""
    data, catalog = dataset(SF)
    ref = oracle.ORACLES[qnum](data)
    for w in (1, 2):
        res_j, _ = run_backend(catalog, qnum, w, "jnp")
        res_p, stats = run_backend(catalog, qnum, w, "pallas")
        assert_results_match(res_p, ref, qnum)
        assert_results_match(res_p, res_j, qnum)
        kd = stats["kernel_dispatch"]
        for kind in EXPECTED_KINDS[qnum]:
            assert _dispatched(kd, kind), (qnum, w, kind, kd)
        if w == 2 and qnum in (1, 3):
            # Q1/Q3 shuffle on group keys at W=2 (Q14's global agg
            # broadcasts instead, which has no metadata histogram)
            assert kd.get("partition", 0) > 0, (qnum, kd)


def test_fused_morsel_dispatch_smoke():
    """The streaming scan's filter->project->probe chain collapses into
    the fused per-morsel kernel under pallas: Q3 and Q6 must report
    'fused' dispatches (and Q3's unique-key joins must not fall back),
    with rows still matching the oracle."""
    data, catalog = dataset(SF)
    for qnum in (3, 6):
        res, stats = run_backend(catalog, qnum, 1, "pallas")
        assert_results_match(res, oracle.ORACLES[qnum](data), qnum)
        kd = stats["kernel_dispatch"]
        assert kd.get("fused", 0) > 0, (qnum, kd)
    assert kd.get("fallback_probe", 0) == 0, kd   # Q3: all joins on-kernel


def test_fused_pipeline_never_dispatches_under_jnp():
    """The morsel-pipeline collapse is pallas-only: a jnp session must
    show no 'fused' (or any other) dispatches."""
    _, catalog = dataset(SF)
    _, stats = run_backend(catalog, 3, 1, "jnp")
    assert stats["kernel_dispatch"] == {}


def test_unkernelable_stages_stay_unfused():
    """A filter->project run holding an expression no compiled kernel can
    evaluate (a LIKE pattern) keeps its standalone XLA operators, counted
    once as ``fallback_fused``; a kernel-safe run still fuses, and both
    give the same rows."""
    import numpy as np

    from repro.core import dtypes as dt
    from repro.core import operators as ops
    from repro.core.expr import col, in_kernel
    from repro.core.table import DeviceTable

    like = col("p_type").startswith("PROMO")
    assert not in_kernel(like)
    assert in_kernel((col("p_size") > 5) & col("p_size").isin((1, 7)))
    rng = np.random.default_rng(0)
    types = ["PROMO BRUSHED", "LARGE PLATED", "PROMO ANODIZED"]
    table = DeviceTable.from_numpy(
        {"p_size": rng.integers(1, 50, 300).astype(np.int32),
         "p_type": dt.encode_bytes(rng.choice(types, 300), 25)},
        {"p_size": dt.INT32, "p_type": dt.bytes_(25)}, capacity=512)

    def run(first_filter):
        pipe = ops.Pipeline([
            ops.FilterProject(first_filter),
            ops.FilterProject(col("p_size") > 5,
                              [("s2", col("p_size") * 2)])])
        counts: dict = {}
        with kernel_ops.use_pallas(), kernel_ops.collect_dispatches(counts):
            ops.fuse_morsel_pipeline(pipe)
            pipe.open()
            out = pipe.add_input(table)[0]
        kinds = [type(op).__name__ for op in pipe.ops]
        return np.sort(np.asarray(out.columns["s2"])[
            np.asarray(out.validity)]), kinds, counts

    got, kinds, counts = run(like)
    assert kinds == ["FilterProject", "FilterProject"]
    assert counts.get("fallback_fused") == 1, counts
    sizes = np.asarray(table.columns["p_size"])[:300]
    promo = np.char.startswith(
        np.array([bytes(r).decode() for r in np.asarray(
            table.columns["p_type"])[:300]]), "PROMO")
    np.testing.assert_array_equal(got, np.sort(2 * sizes[promo & (sizes > 5)]))

    _, kinds, counts = run(col("p_size") < 40)
    assert kinds == ["FusedMorsel"] and "fallback_fused" not in counts


def test_compact_dispatches_on_scalar_subquery():
    """Q15's scalar-subquery broadcast stream-compacts under the kernel
    backend (block_prefix_sum addresses)."""
    data, catalog = dataset(SF)
    res, stats = run_backend(catalog, 15, 1, "pallas")
    assert_results_match(res, oracle.ORACLES[15](data), 15)
    assert stats["kernel_dispatch"].get("compact", 0) > 0


def test_probe_key_equal_to_empty_sentinel_never_matches():
    """A probe key of -1 (the table's empty sentinel) reads empty slots as
    hits inside the kernel; the operator must mask it to no-match so both
    backends agree (regression: fabricated joins / wrong semi/anti)."""
    import numpy as np

    from repro.core import dtypes as dt
    from repro.core import operators as ops_mod
    from repro.core.table import DeviceTable

    build = DeviceTable.from_numpy(
        {"k": np.asarray([5, 7], np.int32),
         "pay": np.asarray([50, 70], np.int32)},
        {"k": dt.INT32, "pay": dt.INT32})
    probe = DeviceTable.from_numpy(
        {"k": np.asarray([-1, 5, 99], np.int32)},
        {"k": dt.INT32})
    for join_type in ("inner", "left_semi", "left_anti"):
        results = {}
        for backend in kernel_ops.BACKENDS:
            with kernel_ops.use_backend(backend):
                join = ops_mod.HashJoin(
                    ["k"], ["k"], () if "semi" in join_type
                    or "anti" in join_type else ["pay"],
                    join_type=join_type)
                join.open()
                join.add_build(build)
                join.seal_build()
                if backend == "pallas":
                    assert join._hash_state is not None, "fell back"
                (out,) = join.add_input(probe)
                results[backend] = sorted(
                    np.asarray(out.columns["k"])[
                        np.asarray(out.validity)].tolist())
        assert results["pallas"] == results["jnp"], (join_type, results)


def test_sentinel_probe_key_expansion_join():
    """PR-5 sentinel regression ported to the expansion probe: a probe key
    of -1 must never match even though the kernel reads empty slots as
    hits, for every join type, with duplicate build keys exercising
    ``hash_probe_multi``."""
    import numpy as np

    from repro.core import dtypes as dt
    from repro.core import operators as ops_mod
    from repro.core.table import DeviceTable

    build = DeviceTable.from_numpy(
        {"k": np.asarray([5, 5, 7], np.int32),
         "pay": np.asarray([50, 51, 70], np.int32)},
        {"k": dt.INT32, "pay": dt.INT32})
    probe = DeviceTable.from_numpy(
        {"k": np.asarray([-1, 5, 99], np.int32)}, {"k": dt.INT32})
    for join_type in ("inner", "left_outer", "left_semi", "left_anti"):
        payload = () if join_type in ("left_semi", "left_anti") else ["pay"]
        results = {}
        for backend in kernel_ops.BACKENDS:
            with kernel_ops.use_backend(backend):
                join = ops_mod.HashJoin(["k"], ["k"], payload,
                                        join_type=join_type, max_matches=4)
                join.open()
                join.add_build(build)
                join.seal_build()
                if backend == "pallas":
                    assert join._hash_state is not None, "fell back"
                    assert join._multi == (join_type in ("inner",
                                                         "left_outer"))
                (out,) = join.add_input(probe)
                valid = np.asarray(out.validity)
                results[backend] = sorted(
                    np.asarray(out.columns["k"])[valid].tolist())
        assert results["pallas"] == results["jnp"], (join_type, results)
        assert -1 not in results["pallas"] or join_type in (
            "left_anti", "left_outer"), (join_type, results)


def test_sentinel_and_out_of_window_composite_packed_join():
    """PR-5 sentinel regression ported to the packed-composite path: probe
    tuples outside the pack windows (including -1 components) map to the
    empty sentinel and must never match; in-window-but-absent tuples must
    miss; present tuples must hit — identically on both backends."""
    import numpy as np

    from repro.core import dtypes as dt
    from repro.core import operators as ops_mod
    from repro.core.table import DeviceTable

    build = DeviceTable.from_numpy(
        {"a": np.asarray([5, 7], np.int32),
         "b": np.asarray([1, 2], np.int32),
         "pay": np.asarray([50, 70], np.int32)},
        {"a": dt.INT32, "b": dt.INT32, "pay": dt.INT32})
    probe = DeviceTable.from_numpy(
        {"a": np.asarray([-1, 5, 5, 7, 99], np.int32),
         "b": np.asarray([1, 1, 2, 2, 1], np.int32)},
        {"a": dt.INT32, "b": dt.INT32})
    for join_type in ("inner", "left_semi", "left_anti"):
        payload = () if join_type in ("left_semi", "left_anti") else ["pay"]
        results = {}
        for backend in kernel_ops.BACKENDS:
            with kernel_ops.use_backend(backend):
                join = ops_mod.HashJoin(["a", "b"], ["a", "b"], payload,
                                        join_type=join_type, max_matches=1)
                join.open()
                join.add_build(build)
                join.seal_build()
                if backend == "pallas":
                    assert join._hash_state is not None, "no pack derived"
                    assert join._pack is not None
                (out,) = join.add_input(probe)
                valid = np.asarray(out.validity)
                results[backend] = sorted(zip(
                    np.asarray(out.columns["a"])[valid].tolist(),
                    np.asarray(out.columns["b"])[valid].tolist()))
        assert results["pallas"] == results["jnp"], (join_type, results)
    # the inner case (last iteration order-independent check): only the
    # tuples actually present on the build side match
    inner = ops_mod.HashJoin(["a", "b"], ["a", "b"], ["pay"],
                             join_type="inner", max_matches=1)
    with kernel_ops.use_pallas():
        inner.open()
        inner.add_build(build)
        inner.seal_build()
        (out,) = inner.add_input(probe)
        valid = np.asarray(out.validity)
        got = sorted(zip(np.asarray(out.columns["a"])[valid].tolist(),
                         np.asarray(out.columns["b"])[valid].tolist()))
    assert got == [(5, 1), (7, 2)], got


def test_jnp_backend_never_counts_fallbacks():
    """S2 regression: capacity-blocked aggregations and non-kernel joins
    under a *jnp* session must not inflate fallback counters — nothing
    "fell back" when no kernel was requested."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import relational as rel

    used: set = set()
    with kernel_ops.use_backend("jnp"), kernel_ops.record_kernels(used):
        n = 8
        vals = jnp.asarray(np.arange(n), jnp.float32)
        gids = jnp.zeros((n,), jnp.int32)
        order = jnp.arange(n, dtype=jnp.int32)
        valid = jnp.ones((n,), bool)
        # a group capacity past PALLAS_AGG_GROUP_LIMIT would mark
        # fallback_agg under pallas; under jnp it must mark nothing
        rel.segment_agg(vals, gids, order, valid,
                        rel.PALLAS_AGG_GROUP_LIMIT + 1, "sum")
    assert used == set(), used
    # executor-level: a full jnp query session reports no dispatches at all
    _, catalog = dataset(SF)
    _, stats = run_backend(catalog, 3, 1, "jnp")
    assert not any(k.startswith("fallback") for k in stats["kernel_dispatch"])


def test_agg_group_limit_boundary():
    """S3 off-by-one: the dispatch bound is *inclusive* — exactly
    ``1 << 16`` groups still dispatches the pallas agg kernel, one more
    takes the jnp fallback (and marks it). All three accumulators share
    the bound; the int path must not inherit the old 2^24 count limit."""
    import jax.numpy as jnp

    from repro.core import relational as rel

    assert rel.PALLAS_AGG_GROUP_LIMIT == 1 << 16
    n = 4
    vals = jnp.ones((n,), jnp.float32)
    ivals = jnp.ones((n,), jnp.int32)
    gids = jnp.zeros((n,), jnp.int32)
    order = jnp.arange(n, dtype=jnp.int32)
    valid = jnp.ones((n,), bool)
    with kernel_ops.use_pallas():
        for kind, v in (("sum", vals), ("sum", ivals), ("count", ivals),
                        ("min", ivals), ("max", vals)):
            used: set = set()
            with kernel_ops.record_kernels(used):
                rel.segment_agg(v, gids, order, valid,
                                rel.PALLAS_AGG_GROUP_LIMIT, kind)
            assert "agg" in used and "fallback_agg" not in used, (kind, used)
            used = set()
            with kernel_ops.record_kernels(used):
                rel.segment_agg(v, gids, order, valid,
                                rel.PALLAS_AGG_GROUP_LIMIT + 1, kind)
            assert "fallback_agg" in used and "agg" not in used, (kind, used)


def test_integer_sums_stay_exact_past_float32_range():
    """Integer segmented sums must bypass the float32 kernel accumulator:
    2^24 + 1 + 1 is not representable in float32 (regression: silent
    precision loss on int measures)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import relational as rel

    vals = jnp.asarray([1 << 24, 1, 1], jnp.int32)
    gids = jnp.asarray([0, 0, 0], jnp.int32)
    order = jnp.arange(3, dtype=jnp.int32)
    valid = jnp.ones((3,), bool)
    for backend in kernel_ops.BACKENDS:
        with kernel_ops.use_backend(backend):
            out = rel.segment_agg(vals, gids, order, valid, 4, "sum")
        assert int(np.asarray(out)[0]) == (1 << 24) + 2, backend


def test_dispatch_counts_are_per_specialization():
    """A jit specialization that falls back to the jnp path (group
    capacity past the kernel limit — integer and min/max measures now
    dispatch kernels, so capacity is the remaining fallback axis) must
    not replay the kernel counts recorded by an in-capacity
    specialization of the same table_op (regression: over-counting)."""
    import numpy as np

    from repro.core import relational as rel

    counts: dict = {}
    with kernel_ops.use_pallas(), kernel_ops.collect_dispatches(counts):
        # direct segment_agg calls mark only at trace time; go through a
        # table_op to exercise the replay machinery
        from repro.core import dtypes as dt
        from repro.core.operators import _aggregate
        from repro.core.table import DeviceTable

        def agg_with(max_groups):
            t = DeviceTable.from_numpy(
                {"g": np.asarray([0, 1, 0], np.int32),
                 "v": np.asarray([1.0, 2.0, 3.0], np.float32)},
                {"g": dt.INT32, "v": dt.FLOAT32})
            return _aggregate(t, ("g",), (("s", "sum", "v"),), max_groups)

        agg_with(4)
        after_small = counts.get("agg", 0)
        assert after_small > 0
        agg_with(rel.PALLAS_AGG_GROUP_LIMIT + 1)
        assert counts.get("agg", 0) == after_small, counts
        assert counts.get("fallback_agg", 0) > 0, counts


def test_scheduler_run_honors_use_pallas_scope():
    """`with use_pallas(): session.run(q)` must execute (and key its
    caches) under pallas, like the batch path (regression: the scheduled
    path ignored the thread-scoped switch)."""
    _, catalog = dataset(SF)
    session = Session(catalog, num_workers=1)
    plan = queries.build_query(1, catalog)
    with kernel_ops.use_pallas():
        h = session.submit(plan)
        h.result()
    assert h.kernel_backend == "pallas"
    assert h.executor_stats["kernel_backend"] == "pallas"
    assert h.executor_stats["kernel_dispatch"].get("agg", 0) > 0
    session.reset_scheduler()


def test_scheduler_caches_key_on_backend():
    """Flipping session.kernel_backend must miss both caches: a result
    computed by one backend is never served to the other."""
    _, catalog = dataset(SF)
    session = Session(catalog, num_workers=1, kernel_backend="jnp")
    plan = queries.build_query(6, catalog)
    a = session.run(plan)
    session.kernel_backend = "pallas"
    b = session.run(plan)
    stats = session.scheduler().stats()
    assert stats["result_cache_hits"] == 0
    assert stats["result_cache_misses"] == 2
    assert_results_match(a, b, 6)
    # same backend again: now it hits
    session.run(plan)
    assert session.scheduler().stats()["result_cache_hits"] == 1
    session.reset_scheduler()


# ---------------------------------------------------------------------------
# full sweep (own CI job; deselected from tier-1 via pyproject addopts)
# ---------------------------------------------------------------------------

@pytest.mark.kernel_backend
@pytest.mark.parametrize("qnum", sorted(queries.QUERIES))
def test_full_query_sweep_backend_differential(qnum):
    """All 22 queries × W∈{1,2}: pallas == jnp == oracle, with nonzero
    dispatch counts wherever the query shape exercises a kernel."""
    data, catalog = dataset(SF)
    ref = oracle.ORACLES[qnum](data)
    for w in (1, 2):
        res_j, _ = run_backend(catalog, qnum, w, "jnp")
        assert_results_match(res_j, ref, qnum)
        res_p, stats = run_backend(catalog, qnum, w, "pallas")
        assert_results_match(res_p, ref, qnum)
        assert_results_match(res_p, res_j, qnum)
        assert stats["kernel_backend"] == "pallas"
        kd = stats["kernel_dispatch"]
        for kind in EXPECTED_KINDS.get(qnum, ()):
            assert _dispatched(kd, kind), (qnum, w, kind, kd)
        if w == 2 and _has_repartition(qnum, catalog):
            # a planned hash exchange sizes its receive buffers with the
            # radix_histogram kernel (the metadata phase)
            assert kd.get("partition", 0) > 0, (qnum, w, kd)


@pytest.mark.kernel_backend
def test_cold_fallback_coverage():
    """Fallback-gap contract: with expansion probes, composite-key packing
    and the integer/min-max accumulators in place, at least 8 of the 22
    TPC-H queries must report zero probe+agg fallback dispatches on a cold
    (first-run, streaming) pallas session at W=1."""
    _, catalog = dataset(SF)
    clean = []
    for qnum in sorted(queries.QUERIES):
        _, stats = run_backend(catalog, qnum, 1, "pallas")
        kd = stats["kernel_dispatch"]
        if kd.get("fallback_probe", 0) == 0 and kd.get("fallback_agg", 0) == 0:
            clean.append(qnum)
    assert len(clean) >= 8, (len(clean), clean)


def _has_repartition(qnum: int, catalog) -> bool:
    plan = queries.build_query(qnum, catalog, num_workers=2)
    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, (P.Repartition, P.Exchange)):
            return True
        stack.extend(node.children())
    return False


@pytest.mark.kernel_backend
@seeded_given(max_examples=8, _seed=20260731,
              qnum=sampled(*sorted(queries.QUERIES)), w=sampled(1, 2),
              batch_rows=sampled(2048, 8192), streaming=bools())
def test_property_random_morsel_settings_pallas(qnum, w, batch_rows,
                                                streaming):
    """Randomized batch/streaming settings: the pallas backend must match
    the oracle regardless of how the scan pipeline slices batches."""
    data, catalog = dataset(SF)
    res, stats = run_backend(catalog, qnum, w, "pallas",
                             batch_rows=batch_rows, streaming=streaming)
    assert_results_match(res, oracle.ORACLES[qnum](data), qnum)
    assert stats["kernel_backend"] == "pallas"
