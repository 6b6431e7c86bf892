"""Engine-vs-oracle validation of all 22 TPC-H queries (paper §3.4 workload).

Single-worker runs validate operator correctness, and Q13 and Q21 (the two
queries with no SQL text, so no SQL-path pallas case) also run under the
pallas backend. The 4-worker runs here validate the distributed path over
the host-staged baseline exchange; all 22 queries over the device-native
ICI exchange at W=4 are in tests/test_tpch_queries_w4.py. All shards
execute on one CPU device here; true multi-device placement is covered by
tests/test_distributed.py.
"""

import pytest

from repro.core import HostExchange, ICIExchange, Session
from repro.tpch import dbgen, oracle, queries, sqltext

from repro.tpch.compare import assert_results_match

SF = 0.005


@pytest.fixture(scope="module")
def data():
    return dbgen.generate(sf=SF)


@pytest.fixture(scope="module")
def catalog():
    return dbgen.load_catalog(sf=SF)


# the session's default backend for every query, pallas for the two
# queries that have no SQL text (tests/test_sql_tpch_pallas.py runs the
# other twenty under pallas from their SQL text)
_SINGLE_WORKER_CASES = (
    [pytest.param(q, None, id=str(q)) for q in sorted(queries.QUERIES)]
    + [pytest.param(q, "pallas", id=f"{q}-pallas")
       for q in sorted(sqltext.UNSUPPORTED)])


@pytest.mark.parametrize("qnum,backend", _SINGLE_WORKER_CASES)
def test_query_single_worker(qnum, backend, data, catalog):
    session = Session(catalog, num_workers=1, batch_rows=16384,
                      kernel_backend=backend)
    res = session.execute(queries.build_query(qnum, catalog))
    assert_results_match(res, oracle.ORACLES[qnum](data), qnum)


@pytest.mark.parametrize("qnum", [5, 13])
def test_query_distributed_host_exchange(qnum, data, catalog):
    session = Session(catalog, num_workers=4, exchange=HostExchange(),
                      batch_rows=8192)
    res = session.execute(queries.build_query(qnum, catalog))
    assert_results_match(res, oracle.ORACLES[qnum](data), qnum)


def test_exchange_stats_accumulate(data, catalog):
    ex = ICIExchange()
    session = Session(catalog, num_workers=4, exchange=ex, batch_rows=8192)
    session.execute(queries.build_query(5, catalog))
    assert ex.stats.rounds > 0
    assert ex.stats.bytes_moved > 0
    # device-native exchange never stages through the host
    assert ex.stats.host_staged_bytes == 0


def test_host_exchange_stages_bytes(data, catalog):
    ex = HostExchange()
    session = Session(catalog, num_workers=4, exchange=ex, batch_rows=8192)
    session.execute(queries.build_query(5, catalog))
    assert ex.stats.host_staged_bytes > 0   # the cost the paper eliminates


def test_partitioned_join_distribution(data, catalog):
    """Large-large joins via partitioned (exchange both sides) distribution."""
    from repro.core import plan as P
    plan = P.Aggregation(
        P.Join(probe=P.TableScan("lineitem", columns=["l_orderkey"]),
               build=P.TableScan("orders", columns=["o_orderkey", "o_custkey"]),
               probe_keys=["l_orderkey"], build_keys=["o_orderkey"],
               build_payload=["o_custkey"], distribution="partitioned"),
        group_keys=[], aggs=[("n", "count", None), ("s", "sum", "o_custkey")],
        max_groups=1)
    session = Session(catalog, num_workers=4, exchange=ICIExchange(),
                      batch_rows=8192)
    res = session.execute(plan)
    li, o = data["lineitem"], data["orders"]
    _, (ck,) = oracle._lookup(o["o_orderkey"], [o["o_custkey"]],
                              li["l_orderkey"])
    assert int(res["n"][0]) == len(li["l_orderkey"])
    assert int(res["s"][0]) == int(ck.sum())
