"""All 22 TPC-H queries at W=4 over the device-native ICI exchange (the
mesh cell's worker count and protocol), against the numpy oracle.

The builder plans of tests/test_tpch_queries.py, in a file of their own so
that each file stays a few minutes of one test worker. All shards execute
on one CPU device here; true multi-device placement is covered by
tests/test_distributed.py.
"""

import pytest

from repro.core import ICIExchange, Session
from repro.tpch import dbgen, oracle, queries

from repro.tpch.compare import assert_results_match

SF = 0.005


@pytest.fixture(scope="module")
def data():
    return dbgen.generate(sf=SF)


@pytest.fixture(scope="module")
def catalog():
    return dbgen.load_catalog(sf=SF)


@pytest.mark.parametrize("qnum", sorted(queries.QUERIES))
def test_query_distributed_ici(qnum, data, catalog):
    session = Session(catalog, num_workers=4, exchange=ICIExchange(),
                      batch_rows=8192)
    res = session.execute(queries.build_query(qnum, catalog))
    assert_results_match(res, oracle.ORACLES[qnum](data), qnum)
