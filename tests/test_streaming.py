"""Streaming executor tests: morsel prefetcher, TableSource.stream(),
per-morsel fused pipelines, zone-map skipping end-to-end, executor stats."""

import numpy as np
import pytest

from repro.core import Session, dtypes as dt
from repro.core.expr import col, lit
from repro.core.operators import FilterProject, HashAggregation, Pipeline
from repro.core.streaming import (HostMorsel, MorselPrefetcher, ScanStats,
                                  morsel_to_device)
from repro.storage import (ColumnChunkTable, PagedTableSource, write_paged_table,
                           write_table)
from repro.tpch import dbgen, queries


def _data(n=1000):
    rng = np.random.default_rng(7)
    return {
        "k": np.arange(n, dtype=np.int32),
        "v": rng.random(n).astype(np.float32),
        "s": dt.encode_bytes([f"row{i}" for i in range(n)], 8),
    }


SCHEMA = {"k": dt.INT32, "v": dt.FLOAT32, "s": dt.bytes_(8)}


def _collect(batches):
    """Valid rows of a stream of batches, per column (to_numpy masks)."""
    out = {}
    for b in batches:
        for c, a in b.to_numpy().items():
            out.setdefault(c, []).append(a)
    return {c: np.concatenate(v) for c, v in out.items()}


def _assert_same(got, want):
    assert set(got) == set(want)
    for c in want:
        np.testing.assert_array_equal(got[c], want[c])


# -- stream() == scan() across every backend --------------------------------

def _make_sources(tmp_path, n=1000, chunks=4):
    from repro.core.session import InMemoryTable
    data = _data(n)
    write_table(str(tmp_path), "t", data, SCHEMA, chunks=chunks)
    write_paged_table(str(tmp_path), "t", data, SCHEMA, row_groups=chunks)
    return data, [
        InMemoryTable("t", data, SCHEMA),
        ColumnChunkTable(str(tmp_path), "t"),
        PagedTableSource(str(tmp_path), "t"),
    ]


@pytest.mark.parametrize("workers", [1, 3])
def test_stream_matches_scan_all_backends(tmp_path, workers):
    _, sources = _make_sources(tmp_path)
    for src in sources:
        scanned = _collect(src.scan(workers, None, 256))
        stats = ScanStats()
        streamed = _collect(src.stream(workers, None, 256, stats=stats))
        _assert_same(streamed, scanned)
        assert stats.morsels > 0
        assert stats.bytes_transferred > 0
        assert stats.read_seconds > 0


def test_paged_source_roundtrip_matches_inmemory(tmp_path):
    data, (mem, cc, paged) = _make_sources(tmp_path)
    want = _collect(mem.scan(2, None, 512))
    _assert_same(_collect(cc.scan(2, None, 512)), want)
    _assert_same(_collect(paged.scan(2, None, 512)), want)
    for c in data:
        np.testing.assert_array_equal(np.sort(want[c], axis=0),
                                      np.sort(data[c], axis=0))


@pytest.mark.parametrize("n,workers,batch", [
    (1000, 1, 256), (1000, 4, 100), (1000, 4, 250), (1003, 4, 100),
    (999, 3, 1000), (7, 8, 4), (0, 4, 16)])
def test_in_memory_morsels_are_views(n, workers, batch):
    """``InMemoryTable`` morsels equal a zero-padded copy of each worker's
    rows, and are read-only views: a batch in which every worker has all
    its rows views the table's own column, one that runs past the end
    views a padded copy made once for every later scan."""
    import math

    from repro.core.session import InMemoryTable

    data = _data(n)
    src = InMemoryTable("t", data, SCHEMA)
    per_worker = math.ceil(n / workers) if n else 1
    morsels = list(src._host_morsels(workers, None, batch))
    again = list(src._host_morsels(workers, None, batch))
    assert len(morsels) == max(1, math.ceil(per_worker / batch))
    for b, (m, m2) in enumerate(zip(morsels, again)):
        lo = b * batch
        cap = min(lo + batch, per_worker) - lo
        want_valid = np.zeros((workers, cap), dtype=bool)
        full = True
        for c, col in data.items():
            want = np.zeros((workers, cap) + col.shape[1:], col.dtype)
            for k in range(workers):
                rows = col[k * per_worker + lo:
                           min(k * per_worker + lo + cap, n)]
                want[k, :len(rows)] = rows
                want_valid[k, :len(rows)] = True
                full &= len(rows) == cap
            np.testing.assert_array_equal(m.columns[c], want)
            assert not m.columns[c].flags.writeable
            assert np.shares_memory(m.columns[c], src.data[c]) == full
            assert np.shares_memory(m.columns[c], m2.columns[c])
        np.testing.assert_array_equal(m.validity, want_valid)


# -- zone-map skipping: identical results with skipping on/off --------------

@pytest.mark.parametrize("backend", ["colchunk", "paged"])
def test_skipping_on_off_identical(tmp_path, backend):
    data = _data(4000)
    pred = (col("k") >= lit(500)) & (col("k") < lit(900))
    if backend == "colchunk":
        write_table(str(tmp_path), "t", data, SCHEMA, chunks=8)
        on = ColumnChunkTable(str(tmp_path), "t", skip_with_stats=True)
        off = ColumnChunkTable(str(tmp_path), "t", skip_with_stats=False)
    else:
        write_paged_table(str(tmp_path), "t", data, SCHEMA, row_groups=8)
        on = PagedTableSource(str(tmp_path), "t", skip_with_stats=True)
        off = PagedTableSource(str(tmp_path), "t", skip_with_stats=False)

    def run(src):
        fp = FilterProject(pred)
        got = []
        for m in src.stream(1, None, 1 << 20, filter_expr=pred):
            got.extend(fp.add_input(m))
        return _collect(got)

    r_on, r_off = run(on), run(off)
    _assert_same(r_on, r_off)
    np.testing.assert_array_equal(np.sort(r_on["k"]), np.arange(500, 900))
    assert on.chunks_skipped > 0          # pruned without being read
    assert off.chunks_skipped == 0


def test_all_chunks_skipped_yields_empty_morsel(tmp_path):
    data = _data(1000)
    write_table(str(tmp_path), "t", data, SCHEMA, chunks=4)
    src = ColumnChunkTable(str(tmp_path), "t")
    pred = col("k") > lit(10_000_000)
    batches = list(src.stream(2, None, 1 << 20, filter_expr=pred))
    assert len(batches) == 1              # shape-preserving empty morsel
    assert int(batches[0].num_valid()) == 0
    assert src.chunks_skipped == 4


# -- prefetcher behavior -----------------------------------------------------

def _host_gen(n_morsels, fail_at=None):
    for i in range(n_morsels):
        if fail_at is not None and i == fail_at:
            raise RuntimeError("storage exploded")
        yield HostMorsel({"k": np.full((1, 8), i, dtype=np.int32)},
                         np.ones((1, 8), dtype=bool), {"k": dt.INT32})


def test_prefetcher_preserves_order_and_counts():
    stats = ScanStats()
    got = [int(np.asarray(t.columns["k"])[0, 0])
           for t in MorselPrefetcher(_host_gen(7), depth=2, stats=stats)]
    assert got == list(range(7))
    assert stats.morsels == 7
    assert stats.bytes_transferred > 0


def test_prefetcher_early_abandon_stops_producer():
    pf = MorselPrefetcher(_host_gen(100), depth=2)
    it = iter(pf)
    next(it), next(it)
    it.close()                            # downstream Limit abandons the scan
    pf._thread.join(timeout=5)
    assert not pf._thread.is_alive()


def test_prefetcher_propagates_reader_errors():
    pf = MorselPrefetcher(_host_gen(5, fail_at=2), depth=2)
    with pytest.raises(RuntimeError, match="storage exploded"):
        list(pf)


def test_morsel_to_device_roundtrip():
    m = HostMorsel({"k": np.arange(6, dtype=np.int32).reshape(1, 6)},
                   np.ones((1, 6), dtype=bool), {"k": dt.INT32})
    t = morsel_to_device(m)
    np.testing.assert_array_equal(np.asarray(t.columns["k"]),
                                  m.columns["k"])


# -- Pipeline operator -------------------------------------------------------

def test_pipeline_composes_like_sequential():
    from repro.core.session import InMemoryTable
    data = _data(2000)
    src = InMemoryTable("t", data, SCHEMA)
    pred = col("k") < lit(1200)
    pipe = Pipeline([
        FilterProject(pred, [("v2", col("v") * lit(2.0))]),
        HashAggregation([], [("s", "sum", "v2"), ("n", "count", None)],
                        "single", 1),
    ])
    pipe.open()
    outs = []
    for b in src.scan(1, None, 300):
        outs.extend(pipe.add_input(b))
    outs.extend(pipe.finish())
    got = _collect(outs)
    want = data["v"][data["k"] < 1200] * 2.0
    assert int(got["n"][0]) == 1200
    np.testing.assert_allclose(got["s"][0], want.sum(), rtol=1e-5)


# -- driver + session integration -------------------------------------------

@pytest.fixture(scope="module")
def storage_setup(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tpch_stream"))
    data = dbgen.write_dataset(root, sf=0.002, chunks=8)
    return root, data


def test_streaming_session_equals_sync(storage_setup):
    root, _ = storage_setup
    for qnum in (1, 6):
        cat_a = dbgen.storage_catalog(root)
        cat_b = dbgen.storage_catalog(root)
        res_s = Session(cat_a, num_workers=2, streaming=True).execute(
            queries.build_query(qnum, cat_a))
        res_m = Session(cat_b, num_workers=2, streaming=False).execute(
            queries.build_query(qnum, cat_b))
        for c in res_s:
            np.testing.assert_allclose(res_s[c], res_m[c], rtol=1e-5)


def test_explain_analyze_reports_skipping(storage_setup):
    root, _ = storage_setup
    cat = dbgen.storage_catalog(root)
    session = Session(cat, num_workers=2)
    text = session.explain(queries.build_query(6, cat), analyze=True)
    assert "== executor stats ==" in text
    line = next(l for l in text.splitlines()
                if l.startswith("scan lineitem"))
    skipped = int(line.split("chunks_skipped=")[1].split()[0])
    assert skipped > 0                    # Q6's date range prunes chunks
    stats = session.executor_stats()
    li = stats["tables"]["lineitem"]
    assert li["bytes_read"] > 0
    assert li["bytes_transferred"] > 0
    assert 0.0 <= li["prefetch_overlap"] <= 1.0


def test_sync_mode_populates_scan_stats(storage_setup):
    root, _ = storage_setup
    cat = dbgen.storage_catalog(root)
    session = Session(cat, num_workers=2, streaming=False)
    session.execute(queries.build_query(6, cat))
    li = session.executor_stats()["tables"]["lineitem"]
    assert li["morsels"] > 0
    assert li["bytes_read"] > 0
    assert li["bytes_transferred"] > 0
    assert li["chunks_skipped"] > 0


def test_legacy_scan_only_source_still_streams():
    """A TableSource written against the pre-morsel contract (overrides
    scan() only) must keep working through stream() and the driver."""
    from repro.core import Catalog, TableSource, plan as P
    from repro.core.table import DeviceTable

    data = _data(500)

    class Legacy(TableSource):
        name = "legacy"
        schema = SCHEMA

        def num_rows(self):
            return 500

        def scan(self, num_workers, columns, batch_rows, filter_expr=None):
            cols = list(columns) if columns else list(data)
            for lo in range(0, 500, 200):
                hi = min(lo + 200, 500)
                yield DeviceTable.from_numpy(
                    {c: data[c][lo:hi] for c in cols},
                    {c: SCHEMA[c] for c in cols})

    class LegacyStacked(Legacy):
        # DeviceTable.from_numpy yields unstacked [cap] batches; wrap to
        # the worker-stacked layout the driver expects
        def scan(self, num_workers, columns, batch_rows, filter_expr=None):
            for b in super().scan(1, columns, batch_rows, filter_expr):
                yield DeviceTable(
                    {c: a[None] for c, a in b.columns.items()},
                    b.validity[None], b.schema)

    src = LegacyStacked()
    stats = ScanStats()
    streamed = _collect(src.stream(1, None, 200, stats=stats))
    _assert_same(streamed, _collect(src.scan(1, None, 200)))
    assert stats.morsels == 3
    assert stats.bytes_transferred > 0

    cat = Catalog()
    cat.register(src)
    session = Session(cat, num_workers=1)
    res = session.execute(P.TableScan("legacy", columns=["k"],
                                      filter=col("k") < lit(100)))
    np.testing.assert_array_equal(np.sort(res["k"]), np.arange(100))


def test_limit_over_storage_stream_terminates(storage_setup):
    root, _ = storage_setup
    from repro.core import plan as P
    cat = dbgen.storage_catalog(root)
    session = Session(cat, num_workers=2)
    res = session.execute(P.Limit(P.TableScan("lineitem",
                                              columns=["l_orderkey"]), 5))
    assert len(res["l_orderkey"]) == 5
