"""Adaptive-execution feedback loop: store properties + warm-replan oracle.

Two layers lock down ``core.feedback`` (ROADMAP "Adaptive execution"):

* unmarked tests — tier-1: q-error algebra (property-tested via the
  ``_hypothesis_compat`` shim), capacity-normalized ``plan.feedback_key``
  stability, store bucketing/version invalidation, warm-bound soundness
  and tightness on a TPC-H slice, the scheduler's plan-cache q-error
  eviction + convergence, and the empty ``executor_stats`` shape
  regression (direct path and scheduler path must agree before any query
  runs);
* ``@pytest.mark.adaptive`` — the full 22-query cold-vs-warm sweep across
  the streaming, distributed (W=2), and pallas backend modes, plus the
  fallback-reduction contract: on warm runs the re-derived capacities must
  keep strictly more work on the pallas kernels for every query whose
  static bounds forced jnp fallbacks cold. Deselected from the default
  run (pyproject ``addopts``); its own CI job executes it.

Env knobs: ``ADAPTIVE_SF`` (oracle sweep scale, default 0.002) and
``ADAPTIVE_FALLBACK_SF`` (fallback-reduction scale, default 0.02 — large
enough that static aggregation bounds exceed the pallas group-capacity
limit, so cold runs genuinely fall back).
"""

from __future__ import annotations

import functools
import os

import numpy as np
import pytest

from repro.core import Session
from repro.core import plan as P
from repro.core.driver import empty_executor_stats
from repro.core.expr import col
from repro.core.feedback import FeedbackStore, qerror, referenced_sources
from repro.core.scheduler import SchedulerConfig
from repro.tpch import dbgen, oracle, queries

from _hypothesis_compat import ints, seeded_given
from repro.tpch.compare import assert_results_match

SF = float(os.environ.get("ADAPTIVE_SF", "0.002"))
FALLBACK_SF = float(os.environ.get("ADAPTIVE_FALLBACK_SF", "0.02"))


@functools.lru_cache(maxsize=2)
def dataset(sf: float):
    """(raw numpy tables, catalog) for one scale factor, cached."""
    return dbgen.generate(sf=sf), dbgen.load_catalog(sf=sf)


def fallback_count(stats) -> int:
    """Total jnp-fallback dispatches a pallas-backend query reported."""
    kd = stats.get("kernel_dispatch") or {}
    return sum(v for k, v in kd.items() if k.startswith("fallback"))


# ---------------------------------------------------------------------------
# tier-1: q-error algebra
# ---------------------------------------------------------------------------

@seeded_given(max_examples=50, est=ints(0, 1 << 20), obs=ints(0, 1 << 20))
def test_qerror_symmetric_and_bounded(est, obs):
    """q-error is multiplicative-symmetric, >= 1, and 1.0 iff exact
    (after the 1-row floor)."""
    q = qerror(est, obs)
    assert q == qerror(obs, est)
    assert q >= 1.0
    if max(est, 1) == max(obs, 1):
        assert q == 1.0
    else:
        assert q > 1.0


@seeded_given(max_examples=50, obs=ints(1, 1 << 16), lo=ints(0, 1 << 10),
              hi=ints(0, 1 << 10))
def test_qerror_monotone_in_overestimate(obs, lo, hi):
    """For a fixed observation, walking the estimate further above it
    never decreases the q-error (and symmetrically below)."""
    a, b = sorted((obs + lo, obs + lo + hi))
    assert qerror(a, obs) <= qerror(b, obs)
    a, b = sorted((max(obs - lo, 1), max(obs - lo - hi, 1)), reverse=True)
    assert qerror(a, obs) <= qerror(b, obs)


def test_qerror_floors_zero_rows():
    """Empty results and zero estimates stay finite (floored at 1 row)."""
    assert qerror(0, 0) == 1.0
    assert qerror(0, 10) == 10.0
    assert qerror(10, 0) == 10.0


# ---------------------------------------------------------------------------
# tier-1: capacity-normalized plan keys
# ---------------------------------------------------------------------------

def _scan():
    return P.TableScan("lineitem", columns=("l_orderkey", "l_quantity"))


def test_feedback_key_ignores_derived_capacities():
    """Plans that differ only in optimizer-derived knobs (capacities,
    agg mode, join distribution) share one feedback key, so a warm
    re-plan reads the observations the differently-sized cold plan
    wrote. Semantic fields still split the key."""
    agg = P.Aggregation(_scan(), ["l_orderkey"], [("n", "count", None)])
    resized = P.Aggregation(_scan(), ["l_orderkey"], [("n", "count", None)],
                            max_groups=1 << 20, mode="partial")
    assert P.feedback_key(agg) == P.feedback_key(resized)
    other_key = P.Aggregation(_scan(), ["l_quantity"],
                              [("n", "count", None)])
    assert P.feedback_key(agg) != P.feedback_key(other_key)

    probe = P.TableScan("lineitem", columns=("l_orderkey",))
    build = P.TableScan("orders", columns=("o_orderkey",))
    join = P.Join(probe, build, ["l_orderkey"], ["o_orderkey"])
    sized = P.Join(probe, build, ["l_orderkey"], ["o_orderkey"],
                   max_matches=7, distribution="partitioned",
                   build_rows=123)
    assert P.feedback_key(join) == P.feedback_key(sized)
    semi = P.Join(probe, build, ["l_orderkey"], ["o_orderkey"],
                  join_type="left_semi")
    assert P.feedback_key(join) != P.feedback_key(semi)


def test_feedback_key_looks_through_exchanges():
    """Repartition/Broadcast/Exchange wrappers are transparent: the
    pre-placement planning node and the exchange-wrapped executed node
    key to the same entry. Nested wrappers collapse too, and children
    inside a kept node are normalized the same way."""
    agg = P.Aggregation(_scan(), ["l_orderkey"], [("n", "count", None)])
    assert P.feedback_key(P.Repartition(agg, ["l_orderkey"])) \
        == P.feedback_key(agg)
    assert P.feedback_key(P.Broadcast(P.Repartition(agg, ["l_orderkey"]),
                                      num_workers=2)) == P.feedback_key(agg)
    probe = P.TableScan("lineitem", columns=("l_orderkey",))
    build = P.TableScan("orders", columns=("o_orderkey",))
    wrapped = P.Join(probe, P.Broadcast(build, num_workers=2),
                     ["l_orderkey"], ["o_orderkey"])
    bare = P.Join(probe, build, ["l_orderkey"], ["o_orderkey"])
    assert P.feedback_key(wrapped) == P.feedback_key(bare)


def test_feedback_key_stable_across_equivalent_plans():
    """Rebuilding the same logical plan object-for-object gives the same
    key string (keys must be value-, not identity-, derived)."""
    def build():
        return P.Aggregation(
            P.Filter(_scan(), col("l_quantity") < 10.0),
            ["l_orderkey"], [("s", "sum", "l_quantity")])
    assert P.feedback_key(build()) == P.feedback_key(build())


# ---------------------------------------------------------------------------
# tier-1: store bucketing + bookkeeping
# ---------------------------------------------------------------------------

def test_store_buckets_workers_and_versions():
    """key_for buckets by worker count and by the versions of every table
    the subtree scans: re-registering a referenced table orphans the old
    observations by construction (nothing to invalidate explicitly)."""
    _, catalog = dataset(SF)
    fb = FeedbackStore()
    agg = P.Aggregation(_scan(), ["l_orderkey"], [("n", "count", None)])
    assert referenced_sources(agg) == ("lineitem",)
    k1 = fb.key_for(agg, catalog, 1)
    assert k1 != fb.key_for(agg, catalog, 2)
    fb.record(k1, rows=42, estimated=100)
    src = catalog.get("lineitem")
    catalog.register(src)               # version bump, same data
    k1b = fb.key_for(agg, catalog, 1)
    assert k1b != k1
    assert fb.rows(k1b) is None         # stale entry no longer matches
    assert fb.rows(k1) == 42


def test_store_record_and_summary():
    fb = FeedbackStore()
    e = fb.record("k", rows=10, estimated=100)
    assert e.qerror == 10.0
    fb.record("k", rows=20, max_matches=3, skip_fraction=0.5)
    entry = fb.get("k")
    assert (entry.rows, entry.max_matches, entry.skip_fraction,
            entry.updates) == (20, 3, 0.5, 2)
    # get() is observation-side; rows() counts a planner hit
    assert fb.get("k").hits == 0
    assert fb.rows("k") == 20
    s = fb.summary()
    assert s["entries"] == 1 and s["updates"] == 2 and s["hits"] == 1
    # qerror reflects the estimate in force when it was recorded (the
    # estimate-less second record leaves it untouched)
    assert s["max_qerror"] == pytest.approx(qerror(100, 10))
    fb.clear()
    assert len(fb) == 0


# ---------------------------------------------------------------------------
# tier-1: executor_stats shape (regression: used to be a bare {})
# ---------------------------------------------------------------------------

def test_executor_stats_shape_before_any_query():
    """Both stats surfaces expose every key before a query runs, with the
    exact shape a Driver reports after one — callers can index
    ``stats['kernel_dispatch']``/``['feedback']`` unconditionally."""
    _, catalog = dataset(SF)
    shape = set(empty_executor_stats())
    session = Session(catalog)
    assert set(session.executor_stats()) == shape
    handle = session.submit(queries.build_query(6, catalog))
    assert set(handle.executor_stats) == shape     # possibly still queued
    handle.result()
    assert set(handle.executor_stats) == shape
    session.execute(session.optimize(queries.build_query(6, catalog)))
    assert set(session.executor_stats()) == shape
    session.reset_scheduler()


def test_executor_stats_feedback_summary():
    """With feedback on, the stats' ``feedback`` entry is the live store
    summary (accumulates across queries); off, it stays empty."""
    _, catalog = dataset(SF)
    session = Session(catalog, feedback=True)
    assert session.executor_stats()["feedback"]["entries"] == 0
    session.execute(session.optimize(queries.build_query(6, catalog)))
    assert session.executor_stats()["feedback"]["entries"] > 0
    plain = Session(catalog)
    plain.execute(plain.optimize(queries.build_query(6, catalog)))
    assert plain.executor_stats()["feedback"] == {}


# ---------------------------------------------------------------------------
# tier-1: warm bounds are sound and tighter, results identical
# ---------------------------------------------------------------------------

def _agg_bounds(plan):
    """[(node, max_groups)] for every Aggregation/Distinct in the tree."""
    out = []

    def visit(node):
        if isinstance(node, (P.Aggregation, P.Distinct)):
            out.append((node, node.max_groups))
        for c in node.children():
            visit(c)

    visit(plan)
    return out


@pytest.mark.parametrize("qnum", [3, 5, 10])
def test_warm_bounds_sound_and_tight(qnum):
    """Second (warm) runs of Q3/Q5/Q10 re-derive every aggregation bound
    from the cold run's observations: each warm ``max_groups`` must cover
    the observed group count (soundness) without exceeding the static
    bound (tightness), and the warm result must match cold and oracle."""
    data, catalog = dataset(SF)
    session = Session(catalog, feedback=True)
    fb = session.feedback_store()
    q = queries.build_query(qnum, catalog)
    cold_plan = session.optimize(q)
    cold = session.execute(cold_plan)
    warm_plan = session.optimize(q)
    warm = session.execute(warm_plan)

    assert_results_match(warm, cold, qnum)
    assert_results_match(warm, oracle.ORACLES[qnum](data), qnum)

    static = dict((P.feedback_key(n), mg) for n, mg in _agg_bounds(cold_plan))
    checked = 0
    for node, warm_mg in _agg_bounds(warm_plan):
        observed = fb.rows(fb.key_for(node, catalog, 1))
        if observed is None:
            continue
        checked += 1
        assert warm_mg >= observed, (qnum, warm_mg, observed)
        assert warm_mg <= static[P.feedback_key(node)], \
            (qnum, warm_mg, static[P.feedback_key(node)])
    assert checked > 0, f"q{qnum}: no aggregation bound was re-derived"


def test_feedback_off_is_inert():
    """A feedback-less session never grows a store and plans statically
    (guards against accidental always-on adaptivity)."""
    _, catalog = dataset(SF)
    session = Session(catalog)
    q = queries.build_query(3, catalog)
    p1 = session.optimize(q)
    session.execute(p1)
    p2 = session.optimize(q)
    assert session.feedback_store() is None
    assert P.fingerprint(p1) == P.fingerprint(p2)


# ---------------------------------------------------------------------------
# tier-1: scheduler plan-cache q-error eviction + convergence
# ---------------------------------------------------------------------------

def test_scheduler_replans_then_converges():
    """Cold plan is cached, found drifted after execution (q-error past
    the limit), and evicted; the warm re-plan's estimates match its own
    observations, so the third submit is a plan-cache hit."""
    _, catalog = dataset(SF)
    session = Session(
        catalog, feedback=True,
        scheduler_config=SchedulerConfig(cache_results=False))
    q = queries.build_query(3, catalog)
    h1 = session.submit(q)
    h1.result()
    h2 = session.submit(q)
    h2.result()
    h3 = session.submit(q)
    h3.result()
    assert not h1.plan_cache_hit
    assert not h2.plan_cache_hit       # cold entry was q-error-evicted
    assert h3.plan_cache_hit           # warm entry converged and stays
    assert h1._est_map and h2._est_map
    assert_results_match(h2.result(), h1.result(), 3)
    session.reset_scheduler()


def test_scheduler_static_plans_stay_cached():
    """Without feedback there is no q-error signal: identical submits hit
    the plan cache exactly as before this subsystem existed."""
    _, catalog = dataset(SF)
    session = Session(
        catalog, scheduler_config=SchedulerConfig(cache_results=False))
    q = queries.build_query(3, catalog)
    h1 = session.submit(q)
    h1.result()
    h2 = session.submit(q)
    h2.result()
    assert not h1.plan_cache_hit
    assert h2.plan_cache_hit
    assert h1._est_map == {} == h2._est_map
    session.reset_scheduler()


# ---------------------------------------------------------------------------
# -m adaptive: full cold-vs-warm TPC-H sweep, three backend modes
# ---------------------------------------------------------------------------

MODES = {
    "streaming": dict(),
    "w2": dict(num_workers=2),
    "pallas": dict(kernel_backend="pallas"),
}


@pytest.mark.adaptive
@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("qnum", sorted(queries.QUERIES))
def test_warm_replan_oracle_sweep(qnum, mode):
    """Every TPC-H query, run cold then warm on one shared feedback
    store, must produce oracle-identical results in every backend mode —
    adaptivity may only change capacities/ordering, never answers."""
    data, catalog = dataset(SF)
    session = Session(catalog, feedback=True, **MODES[mode])
    w = session.num_workers
    q = queries.build_query(qnum, catalog, num_workers=w)
    cold = session.execute(session.optimize(q))
    warm = session.execute(session.optimize(q))
    ref = oracle.ORACLES[qnum](data)
    assert_results_match(cold, ref, qnum)
    assert_results_match(warm, ref, qnum)
    assert_results_match(warm, cold, qnum)


def _drop_compiled_state():
    """Release every cached jit executable before a full-suite sweep.

    The parametrized oracle sweep leaves thousands of compiled CPU
    executables alive in one process; starting another 22-query pallas
    sweep on top of that state can segfault XLA's CPU compiler. Each
    sweep below passes standalone — clearing restores those conditions
    (at the cost of recompiling, which the sweeps pay anyway)."""
    import jax

    from repro.core import operators
    operators.clear_compile_caches()
    jax.clear_caches()


@pytest.mark.adaptive
def test_warm_runs_reduce_pallas_fallbacks():
    """At a scale where static bounds overflow the pallas capacities, the
    warm re-plan must strictly reduce the jnp-fallback dispatch count for
    every query that fell back cold — and at least 3 such queries must
    exist, or the scale no longer exercises the contract."""
    _drop_compiled_state()
    _, catalog = dataset(FALLBACK_SF)
    session = Session(catalog, feedback=True, kernel_backend="pallas")
    reduced, regressed = [], []
    for qnum in sorted(queries.QUERIES):
        q = queries.build_query(qnum, catalog)
        session.execute(session.optimize(q))
        cold = fallback_count(session.executor_stats())
        session.execute(session.optimize(q))
        warm = fallback_count(session.executor_stats())
        if warm > cold:
            regressed.append((qnum, cold, warm))
        if cold > 0 and warm < cold:
            reduced.append((qnum, cold, warm))
        if cold > 0 and warm >= cold:
            regressed.append((qnum, cold, warm))
    assert not regressed, f"warm runs did not reduce fallbacks: {regressed}"
    assert len(reduced) >= 3, (
        f"only {len(reduced)} queries showed fallback reduction at "
        f"sf={FALLBACK_SF}: {reduced}")


@pytest.mark.adaptive
def test_warm_replan_scheduler_sweep_w2():
    """The serving path at W=2: every query submitted twice through the
    scheduler (result cache off so warm really re-executes) stays
    oracle-identical, and the feedback store accumulates entries."""
    _drop_compiled_state()
    data, catalog = dataset(SF)
    session = Session(
        catalog, num_workers=2, feedback=True,
        scheduler_config=SchedulerConfig(cache_results=False))
    try:
        for qnum in sorted(queries.QUERIES):
            q = queries.build_query(qnum, catalog, num_workers=2)
            cold = session.submit(q).result()
            warm = session.submit(q).result()
            ref = oracle.ORACLES[qnum](data)
            assert_results_match(cold, ref, qnum)
            assert_results_match(warm, ref, qnum)
        assert session.executor_stats()["feedback"]["entries"] > 0
    finally:
        session.reset_scheduler()
