"""The 20 TPC-H SQL texts under the pallas kernel backend (the
single-chip cell's backend; interpret mode off-TPU), against the numpy
oracle.

One session mode of ``sql_oracle.MODES``; the streaming mode is in
``test_sql_frontend.py``. Each mode has a file of its own so that the
modes spread over workers.
"""

import pytest

from repro.tpch import dbgen, sqltext

from sql_oracle import check_tpch_sql_text, mode_session

SF = 0.002


@pytest.fixture(scope="module")
def data():
    return dbgen.generate(sf=SF)


@pytest.fixture(scope="module")
def catalog():
    return dbgen.load_catalog(sf=SF)


@pytest.fixture(scope="module")
def session(catalog):
    return mode_session(catalog, "pallas")


@pytest.mark.parametrize("qnum", sqltext.SUPPORTED)
def test_tpch_from_sql_text(qnum, session, catalog, data):
    check_tpch_sql_text(session, qnum, catalog, data)
