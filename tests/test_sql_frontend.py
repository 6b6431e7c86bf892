"""SQL frontend (tier-1): lowering goldens, loud failures, unified API.

Four layers, none needing optional dependencies:

* golden lowering -- one representative SQL text per construct lowers to
  the *same optimized plan fingerprint* as the equivalent hand-built
  fluent query (and lowering is deterministic across calls);
* loud unsupported surface -- every rejected construct raises
  ``SqlUnsupportedError``/``SqlParseError`` *naming the construct*; the
  engine never silently returns wrong rows;
* SQL-text TPC-H -- the 20 ported queries (``repro.tpch.sqltext``) run
  end-to-end from their SQL text and match the numpy oracle (streaming
  single worker here; the W=2, W=4 and pallas modes run the same body,
  ``sql_oracle.check_tpch_sql_text``, in ``test_sql_tpch_<mode>.py``);
* unified execution API -- ``ExecutionOptions`` accepted consistently by
  ``collect``/``submit``/``run``/``Session.sql``, explain delegation, and
  the SQL-text plan/result cache key prefix.
"""

import numpy as np
import pytest

from repro.core import ExecutionOptions, SqlParseError, SqlUnsupportedError
from repro.core import plan as P
from repro.core.builder import table as _t
from repro.core.expr import col, date_lit, lit
from repro.tpch import dbgen, oracle, sqltext

from repro.tpch.compare import assert_results_match
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
    return mode_session(catalog, "streaming")


def _fp(qb, session):
    return P.fingerprint(session.optimize(qb.plan))


# ---------------------------------------------------------------------------
# golden lowering: SQL text -> same optimized fingerprint as the builder
# ---------------------------------------------------------------------------

class TestGoldenLowering:
    def test_filter_project(self, session, catalog):
        sql = session.sql(
            "SELECT l_orderkey, l_extendedprice * (1.0 - l_discount) AS rev "
            "FROM lineitem WHERE l_quantity < 24.0")
        hand = (_t(catalog, "lineitem")
                .filter(col("l_quantity") < lit(24.0))
                .project("l_orderkey",
                         rev=col("l_extendedprice")
                         * (lit(1.0) - col("l_discount"))))
        assert _fp(sql, session) == _fp(hand, session)

    def test_group_aggregate(self, session, catalog):
        sql = session.sql(
            "SELECT l_returnflag, sum(l_quantity) AS sum_qty, count(*) AS n "
            "FROM lineitem GROUP BY l_returnflag")
        # the frontend aggregates into positional slots then projects to
        # the output names -- mirror that exactly
        hand = (_t(catalog, "lineitem")
                .group_by("l_returnflag")
                .agg(__agg1=("sum", "l_quantity"), __agg2=("count", None))
                .project("l_returnflag", sum_qty=col("__agg1"),
                         n=col("__agg2")))
        assert _fp(sql, session) == _fp(hand, session)

    def test_join(self, session, catalog):
        sql = session.sql(
            "SELECT o_orderdate, l_extendedprice FROM lineitem, orders "
            "WHERE l_orderkey = o_orderkey AND o_orderdate < DATE '1995-03-15'")
        hand = (_t(catalog, "lineitem")
                .join(_t(catalog, "orders")
                      .filter(col("o_orderdate") < date_lit("1995-03-15")),
                      ["l_orderkey"], ["o_orderkey"],
                      payload=["o_orderdate"])
                .project("o_orderdate", "l_extendedprice"))
        assert _fp(sql, session) == _fp(hand, session)

    def test_semi_join_in_subquery(self, session, catalog):
        sql = session.sql(
            "SELECT count(*) AS n FROM orders WHERE o_custkey IN "
            "(SELECT c_custkey FROM customer WHERE c_acctbal > 0.0)")
        hand = (_t(catalog, "orders")
                .join(_t(catalog, "customer")
                      .filter(col("c_acctbal") > lit(0.0))
                      .project("c_custkey"),
                      ["o_custkey"], ["c_custkey"], how="left_semi")
                .agg(__agg1=("count", None))
                .project(n=col("__agg1")))
        assert _fp(sql, session) == _fp(hand, session)

    def test_anti_join_not_exists(self, session, catalog):
        sql = session.sql(
            "SELECT count(*) AS n FROM customer WHERE NOT EXISTS "
            "(SELECT * FROM orders WHERE o_custkey = c_custkey)")
        hand = (_t(catalog, "customer")
                .join(_t(catalog, "orders"),
                      ["c_custkey"], ["o_custkey"], how="left_anti")
                .agg(__agg1=("count", None))
                .project(n=col("__agg1")))
        assert _fp(sql, session) == _fp(hand, session)

    def test_order_by_limit_fuses(self, session):
        qb = session.sql("SELECT o_orderkey, o_totalprice FROM orders "
                         "ORDER BY o_totalprice DESC LIMIT 10")
        order_bys = [n for n in _walk(session.optimize(qb.plan))
                     if isinstance(n, P.OrderBy)]
        assert order_bys and order_bys[0].limit == 10

    def test_deterministic(self, session):
        text = ("SELECT l_returnflag, sum(l_quantity) AS q FROM lineitem "
                "WHERE l_shipdate <= DATE '1998-09-02' GROUP BY l_returnflag")
        assert _fp(session.sql(text), session) == \
            _fp(session.sql(text), session)


def _walk(node):
    yield node
    for c in node.children():
        yield from _walk(c)


# ---------------------------------------------------------------------------
# unsupported constructs fail loudly, naming the construct
# ---------------------------------------------------------------------------

class TestLoudFailures:
    @pytest.mark.parametrize("sql, needle", [
        ("SELECT * FROM lineitem FULL OUTER JOIN orders "
         "ON l_orderkey = o_orderkey", "FULL"),
        ("SELECT l_orderkey, sum(l_quantity) OVER () FROM lineitem",
         "OVER"),
        ("SELECT * FROM lineitem, orders", "cross join"),
        ("SELECT p_name FROM part WHERE p_name LIKE 'x_y'", "_"),
        ("SELECT count(*) AS n FROM orders o1, orders o2 "
         "WHERE o1.o_custkey = o2.o_custkey", "unique"),
    ])
    def test_unsupported_named(self, session, sql, needle):
        with pytest.raises((SqlUnsupportedError, SqlParseError)) as ei:
            session.sql(sql).collect()
        assert needle.lower() in str(ei.value).lower()

    def test_parse_error(self, session):
        with pytest.raises(SqlParseError):
            session.sql("SELEC oops FROM lineitem")

    def test_unknown_column_schema_error(self, session):
        from repro.core import SchemaError
        with pytest.raises(SchemaError):
            session.sql("SELECT nope FROM lineitem")

    def test_unported_tpch_raise_keyerror(self, catalog):
        for qnum in sqltext.UNSUPPORTED:
            with pytest.raises(KeyError):
                sqltext.sql_text(qnum, catalog)


# ---------------------------------------------------------------------------
# the 20 ported TPC-H queries, from SQL text, vs the numpy oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qnum", sqltext.SUPPORTED)
def test_tpch_from_sql_text(qnum, session, catalog, data):
    check_tpch_sql_text(session, qnum, catalog, data)


def test_at_least_15_queries_ported():
    assert len(sqltext.SUPPORTED) >= 15


# ---------------------------------------------------------------------------
# unified execution API
# ---------------------------------------------------------------------------

class TestUnifiedApi:
    def test_options_num_workers_collect(self, session, catalog, data):
        opts = ExecutionOptions(num_workers=2)
        res = session.sql(sqltext.sql_text(6, catalog)).collect(options=opts)
        assert_results_match(res, oracle.ORACLES[6](data), 6)

    def test_options_attached_at_sql(self, session, catalog):
        q = session.sql("SELECT count(*) AS n FROM orders",
                        options=ExecutionOptions(num_workers=2))
        base = session.sql("SELECT count(*) AS n FROM orders").collect()
        assert q.collect()["n"] == base["n"]

    def test_options_optimize_false(self, session):
        opts = ExecutionOptions(optimize=False)
        out = session.sql(
            "SELECT o_orderkey FROM orders WHERE o_orderkey <= 32 "
            "ORDER BY o_orderkey").collect(options=opts)
        keys = out["o_orderkey"]
        assert len(keys) > 0 and keys.max() <= 32
        assert list(keys) == sorted(keys)

    def test_builder_collect_shim(self, session):
        # the old positional signature still works unchanged
        out = session.table("orders").agg(n=("count", None)).collect(True)
        assert int(out["n"][0]) > 0

    def test_run_shim_accepts_plan_and_builder(self, session):
        qb = session.table("orders").agg(n=("count", None))
        assert session.run(qb.plan)["n"] == session.run(qb)["n"]

    def test_submit_options_and_sql_cache_prefix(self, session, catalog):
        text = "SELECT count(*) AS n FROM customer"
        h1 = session.sql(text).submit(
            options=ExecutionOptions(priority=3, num_workers=2))
        r1 = h1.result()
        assert h1.num_workers == 2 and h1.priority == 3
        assert h1._result_key.startswith("sql=")
        assert ":w2:" in h1._result_key
        # identical text+options -> result-cache hit under the same key
        h2 = session.sql(text).submit(
            options=ExecutionOptions(num_workers=2))
        assert h2.result()["n"] == r1["n"]
        assert h2.cache_hit
        # same logical plan WITHOUT sql text keys separately (no collision)
        h3 = session.table("customer").agg(n=("count", None)) \
            .project("n").submit()
        assert not h3._result_key.startswith("sql=")
        assert h3.result()["n"] == r1["n"]

    def test_options_kernel_backend_pinned(self, session):
        h = session.sql("SELECT count(*) AS n FROM nation").submit(
            options=ExecutionOptions(kernel_backend="jnp"))
        assert h.kernel_backend == "jnp"
        assert int(h.result()["n"][0]) == 25

    def test_explain_delegates_to_session(self, session):
        q = session.sql("SELECT count(*) AS n FROM nation")
        txt = q.explain()
        assert "TableScan" in txt or "Aggregation" in txt
        analyzed = q.explain(analyze=True)
        assert len(analyzed) > len(txt) or "rows" in analyzed

    def test_explain_unbound_analyze_raises(self, catalog):
        qb = _t(catalog, "nation").agg(n=("count", None))
        assert "Aggregation" in qb.explain()
        with pytest.raises(RuntimeError):
            qb.explain(analyze=True)

    def test_sql_results_are_numpy(self, session):
        out = session.sql("SELECT n_nationkey FROM nation "
                          "ORDER BY n_nationkey LIMIT 3").collect()
        assert isinstance(out["n_nationkey"], np.ndarray)
        assert list(out["n_nationkey"]) == [0, 1, 2]
