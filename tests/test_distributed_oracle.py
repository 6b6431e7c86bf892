"""Property-based differential harness: distributed plans vs the CPU oracle.

Every TPC-H query is planned by the optimizer *with physical exchange
placement* (``build_query(..., num_workers=W)`` inserts explicit
Repartition/Broadcast nodes), executed through the full
builder→optimizer→distributed-driver path, and compared to the pure-numpy
oracle (``tpch/oracle.py``). Distributed results are additionally
regression-checked against the single-worker run of the same query — the
paper's correctness bar for the exchange layer ("Rethinking Analytical
Processing in the GPU Era": validate distributed execution continuously
against a CPU baseline).

Layering:

* unmarked tests — a fast smoke slice that runs in tier-1;
* ``@pytest.mark.dist_oracle`` — the full 22-query × W∈{1,2,4} ×
  both-protocols sweep plus a randomized-config property pass, deselected
  from the default run (pyproject ``addopts``) and executed as its own CI
  job. ``DIST_ORACLE_SF`` / ``DIST_ORACLE_WORKERS`` shrink it for CI.

Config generation goes through ``tests/_hypothesis_compat.seeded_given``:
the real hypothesis engine when installed, deterministic seeded-random
draws otherwise — the harness never silently skips.
"""

from __future__ import annotations

import functools
import os

import pytest

from repro.core import HostExchange, ICIExchange, Session
from repro.core import plan as P
from repro.tpch import dbgen, oracle, queries

from _hypothesis_compat import bools, sampled, seeded_given
from repro.tpch.compare import assert_results_match

SF = float(os.environ.get("DIST_ORACLE_SF", "0.002"))
WORKER_COUNTS = tuple(
    int(w) for w in os.environ.get("DIST_ORACLE_WORKERS", "1,2,4").split(","))

PROTOCOLS = {"ici": ICIExchange, "host": HostExchange}


@functools.lru_cache(maxsize=2)
def dataset(sf: float):
    """(raw numpy tables, catalog) for one scale factor, cached."""
    return dbgen.generate(sf=sf), dbgen.load_catalog(sf=sf)


def run_distributed(catalog, qnum: int, num_workers: int, proto: str,
                    batch_rows: int = 8192, streaming: bool = True,
                    prefetch_depth: int = 2):
    """Plan ``qnum`` for ``num_workers`` (exchange placement on) and run it
    on a matching session; returns (result dict, exchange protocol)."""
    plan = queries.build_query(qnum, catalog, num_workers=num_workers)
    ex = PROTOCOLS[proto]()
    session = Session(catalog, num_workers=num_workers, exchange=ex,
                      batch_rows=batch_rows, streaming=streaming,
                      prefetch_depth=prefetch_depth)
    return session.execute(plan), ex


def count_exchange_nodes(plan: P.PlanNode):
    reps = bcasts = 0
    stack = [plan]
    while stack:
        n = stack.pop()
        reps += isinstance(n, P.Repartition)
        bcasts += isinstance(n, P.Broadcast)
        stack.extend(n.children())
    return reps, bcasts


# ---------------------------------------------------------------------------
# tier-1 smoke slice (fast, always on)
# ---------------------------------------------------------------------------

def test_distributed_plans_contain_exchange_nodes():
    """The tentpole is real: W>1 planning places physical exchange nodes
    (broadcast-join builds and/or shuffles), W=1 planning places none."""
    _, catalog = dataset(SF)
    placed = 0
    for qnum in (1, 3, 5, 13):
        r1, b1 = count_exchange_nodes(
            queries.build_query(qnum, catalog, num_workers=1))
        assert (r1, b1) == (0, 0), f"q{qnum}: W=1 plan must stay exchange-free"
        r4, b4 = count_exchange_nodes(
            queries.build_query(qnum, catalog, num_workers=4))
        placed += r4 + b4
    assert placed > 0


@seeded_given(max_examples=5, qnum=sampled(1, 3, 5, 6, 13, 22),
              w=sampled(2, 4), proto=sampled("ici", "host"),
              batch_rows=sampled(2048, 8192), streaming=bools())
def test_random_distributed_config_matches_oracle(qnum, w, proto, batch_rows,
                                                  streaming):
    data, catalog = dataset(SF)
    res, ex = run_distributed(catalog, qnum, w, proto,
                              batch_rows=batch_rows, streaming=streaming)
    assert_results_match(res, oracle.ORACLES[qnum](data), qnum)
    if proto == "ici":
        assert ex.stats.host_staged_bytes == 0


def test_distributed_matches_single_worker():
    """W>1 output is bit-for-bit the W=1 output (same canonical rows)."""
    data, catalog = dataset(SF)
    for qnum in (3, 5, 13):
        base, _ = run_distributed(catalog, qnum, 1, "ici")
        assert_results_match(base, oracle.ORACLES[qnum](data), qnum)
        for w in (2, 4):
            res, _ = run_distributed(catalog, qnum, w, "ici")
            assert_results_match(res, base, qnum)


# ---------------------------------------------------------------------------
# full sweep (own CI job; deselected from tier-1 via pyproject addopts)
# ---------------------------------------------------------------------------

@pytest.mark.dist_oracle
@pytest.mark.parametrize("qnum", sorted(queries.QUERIES))
def test_full_query_sweep_both_protocols(qnum):
    """All 22 queries × W∈WORKER_COUNTS × {ici, host} vs oracle, with the
    single-worker result as the distributed regression baseline and zero
    host staging asserted for the device-native path."""
    data, catalog = dataset(SF)
    ref = oracle.ORACLES[qnum](data)
    base, _ = run_distributed(catalog, qnum, 1, "ici")
    assert_results_match(base, ref, qnum)
    for w in WORKER_COUNTS:
        if w == 1:
            continue
        for proto in PROTOCOLS:
            res, ex = run_distributed(catalog, qnum, w, proto)
            assert_results_match(res, ref, qnum)
            assert_results_match(res, base, qnum)
            # every TPC-H query aggregates or sorts, so a distributed plan
            # always crosses at least one placed exchange
            assert ex.stats.rounds > 0, (qnum, w, proto)
            if proto == "ici":
                assert ex.stats.host_staged_bytes == 0, (qnum, w)
            else:
                # any actual shuffle on the host path stages through host
                if ex.stats.rounds:
                    assert ex.stats.host_staged_bytes > 0, (qnum, w)


@pytest.mark.dist_oracle
@seeded_given(max_examples=12, _seed=20260730,
              qnum=sampled(*sorted(queries.QUERIES)),
              sf=sampled(0.001, 0.002), w=sampled(*WORKER_COUNTS),
              proto=sampled("ici", "host"),
              batch_rows=sampled(1024, 4096, 16384),
              streaming=bools(), prefetch_depth=sampled(1, 2, 4))
def test_property_random_scale_and_morsel_settings(qnum, sf, w, proto,
                                                   batch_rows, streaming,
                                                   prefetch_depth):
    """Randomized scale factor, worker count, protocol, and morsel/prefetch
    settings: the distributed result must always match the oracle."""
    data, catalog = dataset(sf)
    res, ex = run_distributed(catalog, qnum, w, proto, batch_rows=batch_rows,
                              streaming=streaming,
                              prefetch_depth=prefetch_depth)
    assert_results_match(res, oracle.ORACLES[qnum](data), qnum)
    if proto == "ici":
        assert ex.stats.host_staged_bytes == 0
