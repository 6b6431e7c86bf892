"""SQL cross-engine differential sweeps (``-m sql_oracle``; own CI job).

Two layers, both driven from *SQL text* through ``Session.sql``:

* the 20 ported TPC-H texts vs in-process DuckDB (row counts +
  per-column checksums, ``tests/sql_oracle.py``) on three session modes
  -- streaming single-worker, distributed W=2 (ICI exchange), and the
  pallas kernel backend (interpret mode off-TPU);
* a seeded SQL fuzzer over the TPC-H schema diffed against DuckDB --
  plan shapes TPC-H never exercises.

The same texts against the numpy oracle run in tier-1, one file per mode
(``test_sql_frontend.py``, ``test_sql_tpch_<mode>.py``). Both layers here
skip loudly when the ``[sql]`` extra is not installed. Checksums
accumulate into ``results/sql_oracle/checksums_<mode>.json`` (the CI
artifact).

Env knobs: ``SQL_ORACLE_SF`` (default 0.002), ``SQL_ORACLE_FUZZ_N``
(default 24), ``SQL_ORACLE_SEED`` (default 7).
"""

import json
import os
import pathlib

import pytest

from repro.core import Session
from repro.tpch import dbgen, sqltext

from sql_oracle import (HAVE_DUCKDB, SqlMismatch, check_sql,
                        connect_with_catalog, fuzz_queries, mode_session,
                        require_duckdb, run_duckdb)

pytestmark = pytest.mark.sql_oracle

SF = float(os.environ.get("SQL_ORACLE_SF", "0.002"))
FUZZ_N = int(os.environ.get("SQL_ORACLE_FUZZ_N", "24"))
SEED = int(os.environ.get("SQL_ORACLE_SEED", "7"))

MODES = ["streaming", "w2", "pallas"]

_checksums = {m: {} for m in MODES}


@pytest.fixture(scope="module")
def catalog():
    return dbgen.load_catalog(sf=SF)


@pytest.fixture(scope="module")
def sessions(catalog):
    return {m: mode_session(catalog, m) for m in MODES}


@pytest.fixture(scope="module")
def duck(catalog):
    require_duckdb()
    con = connect_with_catalog(catalog)
    yield con
    con.close()


@pytest.fixture(scope="session", autouse=True)
def _dump_checksums():
    yield
    out = pathlib.Path("results/sql_oracle")
    out.mkdir(parents=True, exist_ok=True)
    for mode, sums in _checksums.items():
        if sums:
            (out / f"checksums_{mode}.json").write_text(
                json.dumps(sums, indent=2, sort_keys=True))


# ---------------------------------------------------------------------------
# TPC-H SQL texts vs DuckDB (row counts + per-column checksums)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("qnum", sqltext.SUPPORTED)
def test_tpch_sql_vs_duckdb(qnum, mode, sessions, catalog, duck):
    text = sqltext.sql_text(qnum, catalog)
    sums = check_sql(sessions[mode], duck, text)
    _checksums[mode][f"q{qnum}"] = sums


# ---------------------------------------------------------------------------
# seeded fuzz sweep vs DuckDB
# ---------------------------------------------------------------------------

def test_fuzz_vs_duckdb(sessions, catalog, duck):
    """Each fuzzed query runs three times: once on the plain streaming
    session, then twice on a session sharing one adaptive feedback store
    — the cold run seeds observed cardinalities, the warm run re-plans
    from them (tighter capacities, feedback-driven join sides), and both
    are checksum-diffed against DuckDB. Plan shapes TPC-H never exercises
    are exactly where an unsound warm bound would silently drop rows."""
    queries = fuzz_queries(SEED, FUZZ_N, catalog)
    adaptive = Session(catalog, batch_rows=16384, feedback=True)
    failures, skipped, checked = [], 0, 0
    for i, sql in enumerate(queries):
        ref = run_duckdb(duck, sql)
        if "cnt" in ref and len(ref["cnt"]) == 1 and ref["cnt"][0] == 0:
            # empty global aggregate: SQL NULL semantics vs the engine's
            # zero-initialized accumulators -- out of scope by design
            skipped += 1
            continue
        try:
            qb = sessions["streaming"].sql(sql)
            from sql_oracle import diff_results
            sums = diff_results(qb.collect(), ref, qb.schema, sql=sql)
            _checksums["streaming"][f"fuzz{i:03d}"] = sums
            for run in ("cold", "warm"):
                aqb = adaptive.sql(sql)
                diff_results(aqb.collect(), ref, aqb.schema,
                             sql=f"[feedback {run}] {sql}")
            checked += 1
        except SqlMismatch as exc:
            failures.append(str(exc))
    assert not failures, (
        f"{len(failures)}/{checked} fuzzed queries diverged from DuckDB:\n\n"
        + "\n\n".join(failures[:5]))
    # the sweep must actually exercise the engine, not skip its way green
    assert checked >= max(1, FUZZ_N // 2), \
        f"only {checked}/{FUZZ_N} fuzzed queries were comparable"
    # the adaptive pass must have fed the planner real observations
    assert adaptive.executor_stats()["feedback"]["entries"] > 0


def test_duckdb_available_reporting():
    """Loud, greppable signal in CI logs about the optional dependency."""
    if not HAVE_DUCKDB:
        pytest.skip("duckdb is NOT installed -- the differential layers "
                    "above were skipped; install the [sql] extra")
