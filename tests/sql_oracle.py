"""Cross-engine differential oracle: our SQL frontend vs in-process DuckDB.

The hand-written numpy oracle (``repro.tpch.oracle``) only covers the 22
TPC-H shapes; this harness makes *any* SQL text a correctness check by the
transpile-and-checksum pattern:

1. ``export_catalog`` materializes the registered catalog into an
   in-process DuckDB connection, decoding the engine's storage encodings
   (dict32 codes -> strings, fixed-width bytes -> trimmed varchar,
   date32 day counts -> DATE);
2. the *same SQL text* runs on both engines;
3. ``diff_results`` compares row counts, then per-column MD5 checksums of
   the canonically sorted, stringified values (exact for int/string/date
   columns; float columns compare by ``allclose`` under an rtol matching
   the float32-vs-float64 precision gap).

``fuzz_queries`` is the seeded generator: random filter/join/aggregate
queries over the TPC-H schema, constrained to the engine's supported
surface (PK-covering equi-joins, int/dict group keys) so every generated
query must agree with DuckDB -- a disagreement is an engine bug, never a
"the fuzzer asked for too much" artifact.

``mode_session`` and ``check_tpch_sql_text`` are the one body of the
SQL-text TPC-H sweeps against the numpy oracle: one test file per session
mode (``test_sql_frontend.py`` for streaming, ``test_sql_tpch_<mode>.py``
for the others) and the DuckDB sweep in ``test_sql_oracle.py``.

DuckDB is an *optional* dependency (the ``[sql]`` pyproject extra); import
this module's ``require_duckdb`` in tests to skip loudly when absent.
"""

from __future__ import annotations

import datetime
import hashlib
import random
from typing import Dict, Iterable, List, Optional

import numpy as np

from repro.core import ICIExchange, Session
from repro.tpch import oracle, sqltext
from repro.tpch.compare import assert_results_match

try:
    import duckdb
    HAVE_DUCKDB = True
    _DUCKDB_ERR = None
except ImportError as _e:          # pragma: no cover - exercised in CI matrix
    duckdb = None
    HAVE_DUCKDB = False
    _DUCKDB_ERR = _e


def require_duckdb():
    """Skip the calling test loudly when duckdb is not installed."""
    if not HAVE_DUCKDB:
        import pytest
        pytest.skip("duckdb is not installed -- install the [sql] extra "
                    f"(pip install 'presto-gpu-repro[sql]'): {_DUCKDB_ERR}")


# ---------------------------------------------------------------------------
# catalog export
# ---------------------------------------------------------------------------

_EPOCH = datetime.date(1970, 1, 1)


def _decode_column(arr: np.ndarray, dt) -> list:
    """Storage-encoded numpy column -> python values DuckDB understands."""
    if dt.name == "dict32":
        d = dt.dictionary
        return [d[int(c)] for c in arr]
    if dt.name == "bytes":
        return [bytes(row).decode("ascii", "replace").rstrip("\x00 ")
                for row in arr]
    if dt.name == "date32":
        return [_EPOCH + datetime.timedelta(days=int(v)) for v in arr]
    if dt.name == "bool":
        return [bool(v) for v in arr]
    if dt.name in ("float32", "float64"):
        return [float(v) for v in arr]
    return [int(v) for v in arr]


_DUCK_TYPES = {
    "int32": "INTEGER", "int64": "BIGINT", "float32": "DOUBLE",
    "float64": "DOUBLE", "bool": "BOOLEAN", "date32": "DATE",
    "dict32": "VARCHAR", "bytes": "VARCHAR",
}


def _host_columns(source) -> Dict[str, np.ndarray]:
    """Full host-side data of a TableSource (InMemoryTable fast path;
    generic sources re-read through their morsel stream)."""
    if hasattr(source, "data"):
        return source.data
    cols: Dict[str, List[np.ndarray]] = {c: [] for c in source.schema}
    for m in source._host_morsels(1, None, 65536):
        for c in source.schema:
            col, valid = m.columns[c][0], m.validity[0]
            cols[c].append(np.asarray(col)[np.asarray(valid)])
    return {c: np.concatenate(v) for c, v in cols.items()}


def export_catalog(con, catalog, tables: Optional[Iterable[str]] = None):
    """Create + populate one DuckDB table per catalog table."""
    for name in sorted(tables if tables is not None else catalog.tables()):
        src = catalog.get(name)
        schema = src.schema
        decl = ", ".join(f'"{c}" {_DUCK_TYPES[t.name]}'
                         for c, t in schema.items())
        con.execute(f'DROP TABLE IF EXISTS "{name}"')
        con.execute(f'CREATE TABLE "{name}" ({decl})')
        data = _host_columns(src)
        decoded = [_decode_column(np.asarray(data[c]), schema[c])
                   for c in schema]
        if decoded and decoded[0]:
            ph = ", ".join("?" for _ in schema)
            con.executemany(f'INSERT INTO "{name}" VALUES ({ph})',
                            list(zip(*decoded)))


def connect_with_catalog(catalog):
    """In-memory DuckDB connection pre-loaded with the catalog."""
    con = duckdb.connect(":memory:")
    export_catalog(con, catalog)
    return con


# ---------------------------------------------------------------------------
# result normalization + diff
# ---------------------------------------------------------------------------

def run_duckdb(con, sql: str) -> Dict[str, list]:
    """Run ``sql`` on DuckDB; returns {column: list-of-python-values}."""
    cur = con.execute(sql)
    names = [d[0] for d in cur.description]
    rows = cur.fetchall()
    return {n: [r[i] for r in rows] for i, n in enumerate(names)}


def _norm_engine(result: Dict[str, np.ndarray], schema) -> Dict[str, list]:
    """Engine result -> comparable python values, decoding through the
    builder's output ``schema`` (dict32 codes, bytes rows, date32 days)."""
    out = {}
    for name, arr in result.items():
        dt = schema.get(name)
        a = np.asarray(arr)
        if dt is not None and dt.name in ("dict32", "bytes", "date32", "bool"):
            out[name] = _decode_column(a, dt)
        elif a.ndim > 1 and a.dtype == np.uint8:    # bytes w/o schema hint
            out[name] = [bytes(r).decode("ascii", "replace").rstrip("\x00 ")
                         for r in a]
        elif a.dtype.kind == "f":
            out[name] = [float(v) for v in a]
        elif a.dtype.kind == "b":
            out[name] = [bool(v) for v in a]
        else:
            out[name] = [int(v) for v in a]
    return out


def _norm_duck(result: Dict[str, list]) -> Dict[str, list]:
    """DuckDB result -> the same comparable python values."""
    out = {}
    for name, vals in result.items():
        norm = []
        for v in vals:
            if isinstance(v, datetime.datetime):
                v = v.date()
            if isinstance(v, datetime.date):
                norm.append(v)
            elif isinstance(v, bool):
                norm.append(v)
            elif isinstance(v, int):
                norm.append(int(v))
            elif isinstance(v, float):
                norm.append(float(v))
            elif isinstance(v, str):
                norm.append(v.rstrip())
            elif v is None:
                norm.append(None)
            else:                                   # Decimal etc.
                norm.append(float(v))
        out[name] = norm
    return out


def _cell_str(v) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, datetime.date):
        return v.isoformat()
    if isinstance(v, str):
        return v.rstrip()
    if v is None:
        return "<null>"
    return str(int(v))


def _sort_order(cols: Dict[str, list], names: List[str]) -> List[int]:
    """Canonical row order: lexicographic over stringified exact cells,
    with floats relative-rounded so both engines sort identically."""
    def key(i):
        row = []
        for n in names:
            v = cols[n][i]
            if isinstance(v, float) and not isinstance(v, bool):
                row.append(("f", round(v / max(abs(v), 1.0), 4)))
            else:
                row.append(("s", _cell_str(v)))
        return row
    n_rows = len(cols[names[0]]) if names else 0
    return sorted(range(n_rows), key=key)


def column_checksum(values: Iterable[str]) -> str:
    """MD5 over newline-joined canonical cell strings."""
    h = hashlib.md5()
    for v in values:
        h.update(v.encode("utf-8", "replace"))
        h.update(b"\n")
    return h.hexdigest()


class SqlMismatch(AssertionError):
    """The two engines disagreed on the same SQL text."""


def diff_results(engine: Dict[str, np.ndarray], duck: Dict[str, list],
                 schema, sql: str = "", rtol: float = 2e-3,
                 atol: float = 1e-2) -> Dict[str, str]:
    """Compare an engine result against a DuckDB result for the same SQL.

    Raises ``SqlMismatch`` on row-count or checksum/allclose divergence;
    returns the per-column checksums on success (for artifact logging).
    """
    e = _norm_engine(engine, schema)
    d = _norm_duck(duck)
    missing = sorted(set(e) ^ set(d))
    if missing:
        raise SqlMismatch(
            f"column sets differ (engine {sorted(e)} vs duckdb {sorted(d)}; "
            f"odd ones out {missing})\nsql: {sql}")
    names = list(e)
    n_e = len(e[names[0]]) if names else 0
    n_d = len(d[names[0]]) if names else 0
    if n_e != n_d:
        raise SqlMismatch(
            f"row counts differ: engine {n_e} vs duckdb {n_d}\nsql: {sql}")

    float_cols = [n for n in names
                  if any(isinstance(v, float) and not isinstance(v, bool)
                         for v in e[n] + d[n])]
    eo, do = _sort_order(e, names), _sort_order(d, names)
    checksums = {}
    for n in names:
        ev = [e[n][i] for i in eo]
        dv = [d[n][i] for i in do]
        if n in float_cols:
            ea = np.array([np.nan if v is None else float(v) for v in ev])
            da = np.array([np.nan if v is None else float(v) for v in dv])
            if not np.allclose(ea, da, rtol=rtol, atol=atol, equal_nan=True):
                bad = int(np.argmax(~np.isclose(ea, da, rtol=rtol, atol=atol,
                                                equal_nan=True)))
                raise SqlMismatch(
                    f"float column '{n}' diverges at sorted row {bad}: "
                    f"engine {ea[bad]!r} vs duckdb {da[bad]!r}\nsql: {sql}")
            checksums[n] = f"allclose:{len(ea)}"
        else:
            ce = column_checksum(_cell_str(v) for v in ev)
            cd = column_checksum(_cell_str(v) for v in dv)
            if ce != cd:
                diff_at = next((i for i in range(len(ev))
                                if _cell_str(ev[i]) != _cell_str(dv[i])), -1)
                raise SqlMismatch(
                    f"column '{n}' checksum mismatch ({ce} vs {cd}); first "
                    f"divergent sorted row {diff_at}: "
                    f"engine {ev[diff_at]!r} vs duckdb {dv[diff_at]!r}"
                    f"\nsql: {sql}")
            checksums[n] = ce
    return checksums


def check_sql(session, con, sql: str, rtol: float = 2e-3) -> Dict[str, str]:
    """Run ``sql`` on both engines and diff; returns per-column checksums."""
    qb = session.sql(sql)
    engine = qb.collect()
    duck = run_duckdb(con, sql)
    return diff_results(engine, duck, qb.schema, sql=sql, rtol=rtol)


# ---------------------------------------------------------------------------
# TPC-H SQL texts vs the numpy oracle, one session per mode
# ---------------------------------------------------------------------------

# streaming single worker; W=2 and W=4 over the ICI exchange (W=4 is the
# mesh cell's worker count and protocol); the pallas kernel backend
# (interpret mode off-TPU), which the single-chip cell runs
MODES = ("streaming", "w2", "w4", "pallas")


def mode_session(catalog, mode: str):
    """The ``Session`` each SQL differential sweep runs ``mode`` on."""
    if mode in ("w2", "w4"):
        return Session(catalog, num_workers=int(mode[1:]),
                       exchange=ICIExchange(), batch_rows=8192)
    if mode == "pallas":
        return Session(catalog, kernel_backend="pallas", batch_rows=16384)
    if mode == "streaming":
        return Session(catalog, batch_rows=16384)
    raise ValueError(f"unknown mode {mode!r}; known: {MODES}")


def check_tpch_sql_text(session, qnum: int, catalog, data) -> None:
    """Run TPC-H query ``qnum`` from its SQL text on ``session`` and
    assert its rows match the numpy oracle's over ``data``."""
    res = session.sql(sqltext.sql_text(qnum, catalog)).collect()
    assert_results_match(res, oracle.ORACLES[qnum](data), qnum)


# ---------------------------------------------------------------------------
# seeded SQL fuzzer over the TPC-H schema
# ---------------------------------------------------------------------------

# (probe, build, probe_key, build_key): every join builds on the build
# table's primary key, so the lowering's unique-coverage requirement holds
# by construction and the engine's static match capacities are exact
_JOINS = [
    ("lineitem", "orders", "l_orderkey", "o_orderkey"),
    ("lineitem", "part", "l_partkey", "p_partkey"),
    ("lineitem", "supplier", "l_suppkey", "s_suppkey"),
    ("orders", "customer", "o_custkey", "c_custkey"),
    ("partsupp", "part", "ps_partkey", "p_partkey"),
    ("partsupp", "supplier", "ps_suppkey", "s_suppkey"),
    ("customer", "nation", "c_nationkey", "n_nationkey"),
    ("supplier", "nation", "s_nationkey", "n_nationkey"),
]

# per-table columns by role: int keys we may group/select, float measures,
# date columns, dict32 columns (grouped or compared by equality)
_TABLES = {
    "lineitem": dict(pk=None, ints=["l_orderkey", "l_linenumber",
                                    "l_partkey", "l_suppkey"],
                     floats=["l_quantity", "l_extendedprice", "l_discount",
                             "l_tax"],
                     dates=["l_shipdate", "l_commitdate", "l_receiptdate"],
                     dicts=["l_returnflag", "l_linestatus", "l_shipmode"]),
    "orders": dict(pk="o_orderkey", ints=["o_orderkey", "o_custkey",
                                          "o_shippriority"],
                   floats=["o_totalprice"], dates=["o_orderdate"],
                   dicts=["o_orderpriority", "o_orderstatus"]),
    "customer": dict(pk="c_custkey", ints=["c_custkey", "c_nationkey"],
                     floats=["c_acctbal"], dates=[], dicts=["c_mktsegment"]),
    "part": dict(pk="p_partkey", ints=["p_partkey", "p_size"],
                 floats=["p_retailprice"], dates=[],
                 dicts=["p_brand", "p_container", "p_mfgr"]),
    "supplier": dict(pk="s_suppkey", ints=["s_suppkey", "s_nationkey"],
                     floats=["s_acctbal"], dates=[], dicts=[]),
    "partsupp": dict(pk=None, ints=["ps_partkey", "ps_suppkey",
                                    "ps_availqty"],
                     floats=["ps_supplycost"], dates=[], dicts=[]),
    "nation": dict(pk="n_nationkey", ints=["n_nationkey", "n_regionkey"],
                   floats=[], dates=[], dicts=["n_name"]),
}

_AGGS = ["count", "sum", "avg", "min", "max"]


def _sample_literal(rng: random.Random, catalog, table: str, column: str):
    """A literal drawn from the live column data (filters stay selective
    but never vacuous)."""
    src = catalog.get(table)
    dt = src.schema[column]
    data = _host_columns(src)[column]
    v = data[rng.randrange(len(data))]
    if dt.name == "dict32":
        return "'" + dt.dictionary[int(v)] + "'"
    if dt.name == "date32":
        return "DATE '" + (_EPOCH + datetime.timedelta(days=int(v))).isoformat() + "'"
    if dt.name in ("float32", "float64"):
        # full repr of the float32 value: both engines parse it to exactly
        # the stored value, so comparisons agree at the boundary row
        return repr(float(v))
    return str(int(v))


def _filter(rng: random.Random, catalog, table: str, cols) -> str:
    kind = rng.choice(["int", "float", "date", "dict"])
    pool = {"int": cols["ints"], "float": cols["floats"],
            "date": cols["dates"], "dict": cols["dicts"]}[kind]
    if not pool:
        pool, kind = cols["ints"], "int"
    c = rng.choice(pool)
    lit = _sample_literal(rng, catalog, table, c)
    if kind == "dict":
        return f"{c} {rng.choice(['=', '<>'])} {lit}"
    op = rng.choice(["<", "<=", ">", ">=", "="])
    return f"{c} {op} {lit}"


def _agg_items(rng: random.Random, cols) -> List[str]:
    items = ["count(*) AS cnt"]
    for i in range(rng.randint(1, 3)):
        kind = rng.choice(_AGGS)
        if kind == "count":
            continue
        pool = cols["floats"] or cols["ints"]
        c = rng.choice(pool)
        if kind in ("sum", "avg") and c not in cols["floats"]:
            kind = rng.choice(["min", "max"])
        items.append(f"{kind}({c}) AS agg{i}")
    return items


def fuzz_queries(seed: int, n: int, catalog) -> List[str]:
    """``n`` deterministic random SQL texts over the TPC-H schema, all
    inside the engine's supported surface (so any cross-engine diff is a
    real bug)."""
    rng = random.Random(seed)
    out = []
    while len(out) < n:
        shape = rng.choice(["scan_agg", "group", "join_agg", "join_group",
                            "scan_rows"])
        if shape in ("scan_agg", "group", "scan_rows"):
            t = rng.choice(sorted(_TABLES))
            cols = _TABLES[t]
            where = " AND ".join(_filter(rng, catalog, t, cols)
                                 for _ in range(rng.randint(1, 2)))
            if shape == "scan_agg":
                out.append(f"SELECT {', '.join(_agg_items(rng, cols))} "
                           f"FROM {t} WHERE {where}")
            elif shape == "group":
                keys = rng.sample(cols["ints"] + cols["dicts"],
                                  rng.randint(1, 2))
                sel = ", ".join(keys + _agg_items(rng, cols))
                out.append(f"SELECT {sel} FROM {t} WHERE {where} "
                           f"GROUP BY {', '.join(keys)} "
                           f"ORDER BY {', '.join(keys)}")
            else:
                if cols["pk"] is None:
                    continue
                extra = [c for c in cols["ints"] + cols["floats"]
                         if c != cols["pk"]]
                sel = ", ".join([cols["pk"]] + rng.sample(
                    extra, min(len(extra), rng.randint(1, 2))))
                out.append(f"SELECT {sel} FROM {t} WHERE {where} "
                           f"ORDER BY {cols['pk']}")
        else:
            probe, build, pk_col, bk_col = rng.choice(_JOINS)
            pc, bc = _TABLES[probe], _TABLES[build]
            where = [_filter(rng, catalog, probe, pc)]
            if rng.random() < 0.7:
                where.append(_filter(rng, catalog, build, bc))
            cond = " AND ".join([f"{pk_col} = {bk_col}"] + where)
            if shape == "join_agg":
                out.append(f"SELECT {', '.join(_agg_items(rng, pc))} "
                           f"FROM {probe}, {build} WHERE {cond}")
            else:
                pool = bc["dicts"] + bc["ints"]
                keys = rng.sample(pool, min(len(pool), rng.randint(1, 2)))
                sel = ", ".join(keys + _agg_items(rng, pc))
                out.append(f"SELECT {sel} FROM {probe}, {build} "
                           f"WHERE {cond} GROUP BY {', '.join(keys)} "
                           f"ORDER BY {', '.join(keys)}")
    return out


def fuzz_small_queries(seed: int, n: int, catalog) -> List[str]:
    """``n`` deterministic *small-query* SQL texts: the serving-side
    point-lookup / low-cardinality-group-by corpus the inter-query
    batching scheduler exists for (``tests/test_batching.py``).

    Every text is a single-table scan -> filter -> project/aggregate with
    no ORDER BY — the plan family ``core.batch.extract_shape`` accepts —
    and within each template only the comparison literals vary, so texts
    from the same template are mutually compatible for stacked launches.
    Texts that still fall outside the batchable surface (e.g. a date
    filter the optimizer rewrites) simply run solo: the differential
    contract is identical either way, a DuckDB diff is an engine bug."""
    rng = random.Random(seed)
    pk_tables = [t for t in sorted(_TABLES) if _TABLES[t]["pk"]]
    dict_tables = [t for t in sorted(_TABLES) if _TABLES[t]["dicts"]]
    out: List[str] = []
    while len(out) < n:
        mode = len(out) % 3
        if mode == 0:            # point lookup on a primary key
            t = rng.choice(pk_tables)
            cols = _TABLES[t]
            pk = cols["pk"]
            extra = [c for c in cols["ints"] + cols["floats"] if c != pk]
            sel = ", ".join([pk] + rng.sample(extra, min(2, len(extra))))
            out.append(f"SELECT {sel} FROM {t} WHERE {pk} = "
                       f"{_sample_literal(rng, catalog, t, pk)}")
        elif mode == 1:          # filtered global aggregate
            t = rng.choice(sorted(_TABLES))
            cols = _TABLES[t]
            out.append(f"SELECT {', '.join(_agg_items(rng, cols))} "
                       f"FROM {t} WHERE {_filter(rng, catalog, t, cols)}")
        else:                    # low-cardinality group-by (dict32 key)
            t = rng.choice(dict_tables)
            cols = _TABLES[t]
            key = rng.choice(cols["dicts"])
            sel = ", ".join([key] + _agg_items(rng, cols))
            out.append(f"SELECT {sel} FROM {t} "
                       f"WHERE {_filter(rng, catalog, t, cols)} "
                       f"GROUP BY {key}")
    return out
