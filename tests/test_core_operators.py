"""Unit + property tests for the core engine primitives and operators."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax.numpy as jnp

from repro.core import dtypes as dt
from repro.core import relational as rel
from repro.core import operators as ops
from repro.core.expr import col, prefix_code, year
from repro.core.spill import SpillManager
from repro.core.table import DeviceTable, concat_tables


def _table(data, schema, capacity=None):
    return DeviceTable.from_numpy(data, schema, capacity)


# ---------------------------------------------------------------------------
# relational primitives
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(-1000, 1000), min_size=1, max_size=80),
       st.booleans())
def test_lexsort_single_key_matches_numpy(vals, desc):
    v = np.array(vals, dtype=np.int32)
    validity = np.ones(len(v), dtype=bool)
    order = np.asarray(rel.lexsort([jnp.asarray(v)], jnp.asarray(validity),
                                   [desc]))
    got = v[order]
    want = np.sort(v)[::-1] if desc else np.sort(v)
    np.testing.assert_array_equal(got, want)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 5), st.integers(0, 5)),
                min_size=1, max_size=60))
def test_lexsort_two_keys_stable(pairs):
    a = np.array([p[0] for p in pairs], dtype=np.int32)
    b = np.array([p[1] for p in pairs], dtype=np.int32)
    validity = np.ones(len(a), dtype=bool)
    order = np.asarray(rel.lexsort([jnp.asarray(a), jnp.asarray(b)],
                                   jnp.asarray(validity)))
    want = np.lexsort((b, a))   # numpy: last key is primary
    np.testing.assert_array_equal(order, want)


def test_lexsort_invalid_rows_last():
    v = np.array([5, 1, 3, 2], dtype=np.int32)
    validity = np.array([True, False, True, True])
    order = np.asarray(rel.lexsort([jnp.asarray(v)], jnp.asarray(validity)))
    assert order[-1] == 1          # the invalid row
    np.testing.assert_array_equal(v[order[:3]], [2, 3, 5])


def test_lexsort_descending_int32_min():
    """Regression: descending used to negate keys, and -INT32_MIN overflows
    back to INT32_MIN, sorting it first instead of last."""
    lo = np.iinfo(np.int32).min
    v = np.array([lo, 0, 5, lo, 7], dtype=np.int32)
    validity = np.ones(len(v), dtype=bool)
    order = np.asarray(rel.lexsort([jnp.asarray(v)], jnp.asarray(validity),
                                   [True]))
    np.testing.assert_array_equal(v[order], [7, 5, 0, lo, lo])


def test_lexsort_descending_negative_zero_stable():
    """Regression: descending no longer rewrites float keys (-0.0 -> 0.0);
    equal keys keep their original relative order."""
    f = np.array([-0.0, 1.0, 0.0, -1.0], dtype=np.float32)
    validity = np.ones(len(f), dtype=bool)
    order = np.asarray(rel.lexsort([jnp.asarray(f)], jnp.asarray(validity),
                                   [True]))
    assert f[order[0]] == 1.0 and f[order[-1]] == -1.0
    # the two zeros tie; stability keeps row 0 (-0.0) before row 2 (0.0)
    assert list(order[1:3]) == [0, 2]
    assert np.signbit(f[order[1]]) and not np.signbit(f[order[2]])


def test_lexsort_descending_bytes_key():
    """Descending over fixed-width bytes keys (multi-pass path)."""
    rows = ["bb", "aa", "cc", "ab"]
    data = dt.encode_bytes(rows, 2)
    validity = np.ones(len(rows), dtype=bool)
    order = np.asarray(rel.lexsort([jnp.asarray(data)], jnp.asarray(validity),
                                   [True]))
    assert [rows[i] for i in order] == ["cc", "bb", "ab", "aa"]


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 7), min_size=1, max_size=100))
def test_group_rows_matches_numpy_unique(keys):
    k = np.array(keys, dtype=np.int32)
    validity = np.ones(len(k), dtype=bool)
    g = rel.group_rows([jnp.asarray(k)], jnp.asarray(validity), 16)
    assert int(g.num_groups) == len(np.unique(k))
    # every group's representative key is a real key
    reps = np.asarray(g.key_rows)[: int(g.num_groups)]
    assert set(k[reps].tolist()) == set(np.unique(k).tolist())


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 4), st.floats(-100, 100)),
                min_size=1, max_size=100))
def test_segment_sum_matches_numpy(rows):
    k = np.array([r[0] for r in rows], dtype=np.int32)
    v = np.array([r[1] for r in rows], dtype=np.float32)
    validity = np.ones(len(k), dtype=bool)
    g = rel.group_rows([jnp.asarray(k)], jnp.asarray(validity), 8)
    sums = np.asarray(rel.segment_agg(jnp.asarray(v), g.gids, g.order,
                                      jnp.asarray(validity), 8, "sum"))
    uniq = np.unique(k)
    want = np.array([v[k == u].sum() for u in uniq], dtype=np.float32)
    got = {int(k[r]): s for r, s in zip(np.asarray(g.key_rows)[:len(uniq)],
                                        sums[:len(uniq)])}
    for u, w in zip(uniq, want):
        np.testing.assert_allclose(got[int(u)], w, rtol=1e-4, atol=1e-3)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 50), min_size=1, max_size=60),
       st.lists(st.integers(0, 50), min_size=1, max_size=60))
def test_join_probe_matches_numpy(build, probe):
    bk = np.unique(np.array(build, dtype=np.int32))     # unique build side
    pk = np.array(probe, dtype=np.int32)
    bt = rel.join_build(jnp.asarray(bk), jnp.ones(len(bk), dtype=bool))
    res = rel.join_probe(bt, jnp.asarray(pk), jnp.ones(len(pk), dtype=bool), 1)
    matched = np.zeros(len(pk), dtype=bool)
    matched[np.asarray(res.probe_idx)[np.asarray(res.valid)]] = True
    np.testing.assert_array_equal(matched, np.isin(pk, bk))


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 9), min_size=1, max_size=40),
       st.integers(2, 5))
def test_partition_layout_conserves_rows(keys, nparts):
    k = np.array(keys, dtype=np.int32)
    validity = np.ones(len(k), dtype=bool)
    pids = rel.partition_ids([jnp.asarray(k)], jnp.asarray(validity), nparts)
    cap = len(k)    # ample capacity -> nothing dropped
    gather, out_valid = rel.partition_layout(pids, jnp.asarray(validity),
                                             nparts, cap)
    assert int(np.asarray(out_valid).sum()) == len(k)
    got = np.sort(k[np.asarray(gather)[np.asarray(out_valid)]])
    np.testing.assert_array_equal(got, np.sort(k))
    # every row landed in the partition its hash says
    placed = np.asarray(gather).reshape(nparts, cap)
    valid2 = np.asarray(out_valid).reshape(nparts, cap)
    for p in range(nparts):
        rows = placed[p][valid2[p]]
        np.testing.assert_array_equal(np.asarray(pids)[rows], p)


# ---------------------------------------------------------------------------
# operators
# ---------------------------------------------------------------------------

_SCHEMA = {"k": dt.INT32, "v": dt.FLOAT32}


def test_filter_project_fused():
    t = _table({"k": np.arange(10), "v": np.arange(10, dtype=np.float32)},
               _SCHEMA)
    fp = ops.FilterProject(col("k") >= 5, [("doubled", col("v") * 2.0)])
    out = fp.add_input(t)[0]
    np.testing.assert_allclose(out.to_numpy()["doubled"],
                               np.arange(5, 10) * 2.0)


def test_streaming_aggregation_concat_based():
    """Paper §3.2: batch-wise partial agg + concat + re-aggregate."""
    agg = ops.HashAggregation(["k"], [("s", "sum", "v"), ("c", "count", None),
                                      ("m", "max", "v"), ("a", "avg", "v")],
                              mode="single", max_groups=8)
    agg.open()
    rng = np.random.default_rng(1)
    ks, vs = [], []
    for _ in range(5):   # five streamed batches
        k = rng.integers(0, 5, 64)
        v = rng.random(64).astype(np.float32)
        ks.append(k); vs.append(v)
        assert agg.add_input(_table({"k": k, "v": v}, _SCHEMA)) == []
    out = agg.finish()[0].to_numpy()
    k, v = np.concatenate(ks), np.concatenate(vs)
    order = np.argsort(out["k"])
    for i, u in enumerate(np.unique(k)):
        j = order[i]
        np.testing.assert_allclose(out["s"][j], v[k == u].sum(), rtol=1e-4)
        assert out["c"][j] == (k == u).sum()
        np.testing.assert_allclose(out["m"][j], v[k == u].max(), rtol=1e-6)
        np.testing.assert_allclose(out["a"][j], v[k == u].mean(), rtol=1e-4)


def test_partial_final_modes_compose():
    """Velox Partial/Final modes with an exchange in between."""
    rng = np.random.default_rng(2)
    k = rng.integers(0, 6, 256)
    v = rng.random(256).astype(np.float32)
    partial = ops.HashAggregation(["k"], [("a", "avg", "v")], "partial",
                                  max_groups=8)
    partial.open()
    partial.add_input(_table({"k": k[:128], "v": v[:128]}, _SCHEMA))
    p1 = partial.finish()[0]
    partial.open()
    partial.add_input(_table({"k": k[128:], "v": v[128:]}, _SCHEMA))
    p2 = partial.finish()[0]
    assert "a__sum" in p1.column_names and "a__cnt" in p1.column_names
    final = ops.HashAggregation(["k"], [("a", "avg", "v")], "final",
                                max_groups=8)
    final.open()
    final.add_input(concat_tables([p1, p2]))
    out = final.finish()[0].to_numpy()
    order = np.argsort(out["k"])
    for i, u in enumerate(np.unique(k)):
        np.testing.assert_allclose(out["a"][order[i]], v[k == u].mean(),
                                   rtol=1e-4)


def test_partial_emit_threshold_flow_control():
    agg = ops.HashAggregation(["k"], [("c", "count", None)], "partial",
                              max_groups=512, emit_rows=4)
    agg.open()
    emitted = []
    for i in range(4):
        k = np.arange(i * 8, i * 8 + 8)     # all-new groups each batch
        emitted += agg.add_input(_table({"k": k,
                                         "v": np.zeros(8, np.float32)},
                                        _SCHEMA))
    emitted += agg.finish()
    assert len(emitted) >= 2                # streamed early at the threshold


# streaming aggregation's merge policy, against numpy and against the
# former policy of re-aggregating the accumulator with every batch

_AGGS = [("s", "sum", "v"), ("c", "count", None), ("m", "max", "v")]
_FINAL_SCHEMA = {"k": dt.INT32, "s": dt.FLOAT32, "c": dt.INT32,
                 "m": dt.FLOAT32}


def _merge_every_batch(batches, agg, on_merge=None):
    """Output of the former policy: every batch's partial in
    ``max_groups`` slots, merged into the accumulator at once;
    ``on_merge(i, acc)`` after batch ``i`` may take the accumulator away
    (returns None)."""
    acc = None
    for i, b in enumerate(batches):
        part = ops._aggregate(b, agg.group_keys, agg.specs, agg.max_groups)
        acc = part if acc is None else ops._aggregate(
            concat_tables([acc, part]), agg.group_keys,
            ops.merge_specs(agg.specs), agg.max_groups)
        if on_merge is not None:
            acc = on_merge(i, acc)
    return acc


def _agg_batches(rng, mode, max_groups, rows, n_batches, workers):
    """``n_batches`` tables of ``rows`` slots (the last 3 dead) with keys
    in ``[0, max_groups)``; worker-stacked ``[workers, rows]`` when
    ``workers``; final mode gets partial columns."""
    def one():
        keys = rng.integers(0, max_groups, rows - 3).astype(np.int32)
        return _keyed_table(rng, mode, keys, rows)

    if not workers:
        return [one() for _ in range(n_batches)]
    return [_stack([one() for _ in range(workers)]) for _ in range(n_batches)]


def _keyed_table(rng, mode, keys, rows):
    """A table of ``rows`` slots whose valid rows carry ``keys`` and random
    values (partial columns in final mode)."""
    live, data = len(keys), {"k": keys}
    if mode == "final":
        data.update(s=rng.random(live).astype(np.float32),
                    c=rng.integers(1, 5, live).astype(np.int32),
                    m=rng.random(live).astype(np.float32))
        return _table(data, _FINAL_SCHEMA, rows)
    data["v"] = rng.random(live).astype(np.float32)
    return _table(data, _SCHEMA, rows)


def _stack(ts):
    """Worker-stacked ``[len(ts), rows]`` table of local tables ``ts``."""
    return DeviceTable(
        {n: jnp.stack([t.columns[n] for t in ts]) for n in ts[0].columns},
        jnp.stack([t.validity for t in ts]), ts[0].schema)


def _occupied_slots(batches):
    """The power of two at or above the most distinct valid keys any
    batch (or worker) holds, at least 1."""
    most = max(len(np.unique(cols["k"][val]))
               for b in batches for cols, val in _workers(b))
    return 1 << max(most - 1, 0).bit_length()


def _by_key(cols, validity):
    """``{key: (s, c, m)}`` of one worker's valid output rows."""
    return {int(k): (float(s), int(c), float(m)) for k, s, c, m in zip(
        *(np.asarray(cols[n])[validity] for n in ("k", "s", "c", "m")))}


def _workers(table):
    """Per worker, ``(columns, validity)`` as numpy."""
    val = np.asarray(table.validity)
    cols = {n: np.asarray(a) for n, a in table.columns.items()}
    if val.ndim == 1:
        return [(cols, val)]
    return [({n: a[w] for n, a in cols.items()}, val[w])
            for w in range(val.shape[0])]


def _numpy_groups(batches, mode, worker):
    """``{key: (s, c, m)}`` over one worker's valid input rows."""
    parts = [_workers(b)[worker] for b in batches]
    k = np.concatenate([c["k"][v] for c, v in parts])
    if mode == "final":
        s, c, m = (np.concatenate([cs[n][v] for cs, v in parts])
                   for n in ("s", "c", "m"))
    else:
        s = m = np.concatenate([cs["v"][v] for cs, v in parts])
        c = np.ones(len(k), np.int64)
    return {int(u): (float(s[k == u].astype(np.float64).sum()),
                     int(c[k == u].sum()), float(m[k == u].max()))
            for u in np.unique(k)}


def _assert_groups(got, want, rtol):
    assert set(got) == set(want)
    for key, (s, c, m) in want.items():
        np.testing.assert_allclose(got[key][0], s, rtol=rtol)
        assert got[key][1] == c and got[key][2] == m


@pytest.mark.parametrize("mode", ["single", "partial", "final"])
@pytest.mark.parametrize("max_groups,rows,n_batches,workers", [
    (64, 16, 6, 0),     # partials wait under 2 x max_groups: one merge
    (32, 32, 4, 0),     # batch of max_groups rows: a merge per batch
    (16, 48, 3, 0),     # batch above max_groups rows: a merge per batch
    (32, 16, 7, 0),     # merges at the threshold, and one at finish
    (64, 16, 5, 2),     # worker-stacked, waiting
    (16, 32, 3, 2),     # worker-stacked, a merge per batch
])
def test_streaming_aggregation_merges_at_threshold(
        mode, max_groups, rows, n_batches, workers):
    batches = _agg_batches(np.random.default_rng(max_groups + rows),
                           mode, max_groups, rows, n_batches, workers)
    agg = ops.HashAggregation(["k"], _AGGS, mode, max_groups=max_groups)
    counts = {}
    agg.open()
    with ops.collect_agg_stats(counts):
        for b in batches:
            assert agg.add_input(b) == []
        streamed = dict(counts)
        out = agg.finish()[0]
    assert counts["partials"] == n_batches
    # only finish's merge of several tables reads the occupied prefixes
    assert streamed.get("compacted_merges", 0) == 0
    assert counts.get("compacted_merges", 0) == \
        counts["merges"] - streamed.get("merges", 0)
    slots = min(max_groups, rows)
    if n_batches * slots < 2 * max_groups:
        assert streamed.get("merges", 0) == 0
        assert counts["merges"] == 1
        assert counts["merge_rows"] == \
            n_batches * min(slots, _occupied_slots(batches))
    elif rows >= max_groups:
        assert streamed["merges"] == counts["merges"] == n_batches - 1
        assert counts["merge_rows"] == (n_batches - 1) * 2 * max_groups
    else:
        assert 1 <= streamed["merges"] < counts["merges"] < n_batches - 1
    former = _merge_every_batch(batches, agg)
    for w, ((cols, val), (fcols, fval)) in enumerate(
            zip(_workers(out), _workers(former))):
        got = _by_key(cols, val)
        _assert_groups(got, _numpy_groups(batches, mode, w), rtol=1e-5)
        _assert_groups(got, _by_key(fcols, fval), rtol=1e-6)


def _counted_batches(rng, mode, counts, rows):
    """One batch of ``rows`` slots per entry of ``counts``, holding exactly
    that many distinct keys of ``[0, 64)`` (no valid row for 0; every slot
    valid where it equals ``rows``, else the last 3 dead); an entry that
    is a list gives a worker-stacked batch, one count per worker."""
    def one(n):
        live = 0 if n == 0 else rows if n == rows else rows - 3
        keys = rng.choice(64, n, replace=False).astype(np.int32)
        return _keyed_table(rng, mode, rng.permutation(keys[np.concatenate(
            [np.arange(n), rng.integers(0, max(n, 1), live - n)])]), rows)

    return [_stack([one(n) for n in c]) if isinstance(c, list) else one(c)
            for c in counts]


@pytest.mark.parametrize("mode", ["single", "partial", "final"])
@pytest.mark.parametrize("counts,occupied", [
    ([5, 3, 9], 16),                     # local: cut to 16 of 32 slots
    ([[2, 7], [3, 1], [4, 5]], 8),       # W=2, workers hold other counts
    ([5, 0, 3], 8),                      # a partial with no valid rows
    ([[0, 6], [0, 2]], 8),               # a worker with no valid rows
    ([0, 0], 1),                         # nothing valid anywhere
    ([32, 4, 6], 32),                    # a partial full to its slots
])
def test_finish_merges_occupied_prefix(mode, counts, occupied):
    """finish merges each held partial's first B slots, B the power of two
    at or above the most groups any partial (or worker) holds, with the
    groups numpy gives."""
    rows, max_groups = 32, 64
    batches = _counted_batches(np.random.default_rng(len(counts) + occupied),
                               mode, counts, rows)
    assert _occupied_slots(batches) == occupied
    agg = ops.HashAggregation(["k"], _AGGS, mode, max_groups=max_groups)
    stats = {}
    agg.open()
    with ops.collect_agg_stats(stats):
        for b in batches:
            assert agg.add_input(b) == []
        out = agg.finish()[0]
    merged = len(batches) * min(occupied, rows)
    assert stats == {"partials": len(batches), "merges": 1,
                     "merge_rows": merged, "compacted_merges": 1}
    assert ops._rows(out) == min(max_groups, merged)
    for w, (cols, val) in enumerate(_workers(out)):
        _assert_groups(_by_key(cols, val), _numpy_groups(batches, mode, w),
                       rtol=1e-5)


@pytest.mark.parametrize("point", ["spill", "emit"])
def test_flush_and_emit_points_match_merging_every_batch(point, tmp_path):
    """A spill flush or partial emission happens at the batch it did when
    every batch was merged into the accumulator, with the same groups."""
    max_groups, threshold = 64, 20
    batches = _agg_batches(np.random.default_rng(7), "partial", max_groups,
                           16, 8, 0)
    if point == "spill":
        mgr = SpillManager(device_budget=0, spill_dir=str(tmp_path))
        kw = {"spill": mgr, "spill_flush_groups": threshold}
    else:
        kw = {"emit_rows": threshold}
    agg = ops.HashAggregation(["k"], _AGGS, "partial", max_groups, **kw)
    agg.open()
    points, outs = [], []
    for i, b in enumerate(batches):
        flushed = len(agg._flushed)
        emitted = agg.add_input(b)
        if emitted or len(agg._flushed) > flushed:
            points.append(i)
        outs += emitted
    outs += agg.finish()

    former_points, former_outs = [], []

    def on_merge(i, acc):
        if int(acc.num_valid()) >= threshold:
            former_points.append(i)
            former_outs.append(acc)
            return None
        return acc

    last = _merge_every_batch(batches, agg, on_merge)
    assert points == former_points and len(points) >= 2
    if point == "spill":
        assert mgr.stats.summary()["host"]["spills"] == len(former_points)
        got = _by_key(*_workers(outs[0])[0])
        _assert_groups(got, _numpy_groups(batches, "partial", 0), rtol=1e-5)
    else:
        if last is not None:
            former_outs.append(last)
        assert len(outs) == len(former_outs)
        for a, b in zip(outs, former_outs):
            _assert_groups(_by_key(*_workers(a)[0]),
                           _by_key(*_workers(b)[0]), rtol=1e-6)


def test_join_types_against_numpy():
    rng = np.random.default_rng(3)
    bk = np.unique(rng.integers(0, 40, 30)).astype(np.int32)
    bp = (bk * 10).astype(np.int32)
    pk = rng.integers(0, 40, 100).astype(np.int32)
    build = _table({"k": bk, "payload": bp}, {"k": dt.INT32, "payload": dt.INT32})
    probe = _table({"k": pk, "v": np.zeros(100, np.float32)}, _SCHEMA)

    for jt in ("inner", "left_semi", "left_anti"):
        j = ops.HashJoin(["k"], ["k"], ["payload"] if jt == "inner" else (),
                         join_type=jt)
        j.add_build(build)
        j.seal_build()
        out = j.add_input(probe)[0].to_numpy()
        m = np.isin(pk, bk)
        if jt == "inner":
            np.testing.assert_array_equal(np.sort(out["k"]), np.sort(pk[m]))
            np.testing.assert_array_equal(out["payload"], out["k"] * 10)
        elif jt == "left_semi":
            np.testing.assert_array_equal(np.sort(out["k"]), np.sort(pk[m]))
        else:
            np.testing.assert_array_equal(np.sort(out["k"]), np.sort(pk[~m]))


def test_join_expansion_one_to_many():
    build = _table({"k": np.array([1, 1, 1, 2], np.int32),
                    "p": np.array([10, 11, 12, 20], np.int32)},
                   {"k": dt.INT32, "p": dt.INT32})
    probe = _table({"k": np.array([1, 2, 3], np.int32),
                    "v": np.zeros(3, np.float32)}, _SCHEMA)
    j = ops.HashJoin(["k"], ["k"], ["p"], max_matches=4)
    j.add_build(build)
    j.seal_build()
    out = j.add_input(probe)[0].to_numpy()
    assert sorted(out["p"].tolist()) == [10, 11, 12, 20]


def test_left_outer_join_matched_flag():
    build = _table({"k": np.array([1], np.int32), "p": np.array([9], np.int32)},
                   {"k": dt.INT32, "p": dt.INT32})
    probe = _table({"k": np.array([1, 2], np.int32),
                    "v": np.zeros(2, np.float32)}, _SCHEMA)
    j = ops.HashJoin(["k"], ["k"], ["p"], join_type="left_outer")
    j.add_build(build)
    j.seal_build()
    out = j.add_input(probe)[0].to_numpy()
    by_k = dict(zip(out["k"].tolist(),
                    zip(out["p"].tolist(), out["__matched"].tolist())))
    assert by_k[1] == (9, True)
    assert by_k[2] == (0, False)


def test_orderby_limit_and_descending():
    t = _table({"k": np.array([3, 1, 2, 5, 4], np.int32),
                "v": np.array([1, 2, 3, 4, 5], np.float32)}, _SCHEMA)
    ob = ops.OrderBy(["k"], [True], limit=3)
    ob.open()
    ob.add_input(t)
    out = ob.finish()[0].to_numpy()
    np.testing.assert_array_equal(out["k"], [5, 4, 3])


def test_compact_moves_valid_rows_front():
    t = _table({"k": np.arange(8), "v": np.zeros(8, np.float32)}, _SCHEMA)
    t = t.filter(jnp.asarray(np.array([0, 1, 0, 1, 1, 0, 0, 1], bool)))
    c = t.compact()
    assert bool(c.validity[:4].all()) and not bool(c.validity[4:].any())
    np.testing.assert_array_equal(np.asarray(c.columns["k"][:4]), [1, 3, 4, 7])


# ---------------------------------------------------------------------------
# expressions
# ---------------------------------------------------------------------------

def test_year_expr_exact_on_boundaries():
    days = np.array([dt.date_to_i32(s) for s in
                     ("1992-01-01", "1992-12-31", "1996-02-29", "1998-08-02")],
                    dtype=np.int32)
    t = _table({"d": days}, {"d": dt.DATE32})
    got = np.asarray(year(col("d")).evaluate(t))
    np.testing.assert_array_equal(got, [1992, 1992, 1996, 1998])


def test_prefix_code():
    phones = dt.encode_bytes(["13-555", "31-123", "07-999"], 15)
    t = _table({"p": phones}, {"p": dt.bytes_(15)})
    got = np.asarray(prefix_code(col("p"), 2).evaluate(t))
    np.testing.assert_array_equal(got, [13, 31, 7])


@settings(max_examples=30, deadline=None)
@given(st.lists(st.text(alphabet="abcx y", min_size=0, max_size=20),
                min_size=1, max_size=30),
       st.text(alphabet="abc", min_size=1, max_size=3))
def test_contains_property(strings, needle):
    width = 24
    data = dt.encode_bytes(strings, width)
    t = _table({"s": data}, {"s": dt.bytes_(width)})
    got = np.asarray(col("s").contains(needle).evaluate(t))
    want = np.array([needle in s[:width] for s in strings])
    np.testing.assert_array_equal(got, want)


def test_multi_part_contains_ordered():
    data = dt.encode_bytes(["xx special yy requests", "requests special",
                            "specialrequests", "nothing"], 24)
    t = _table({"s": data}, {"s": dt.bytes_(24)})
    got = np.asarray(col("s").contains("special", "requests").evaluate(t))
    np.testing.assert_array_equal(got, [True, False, True, False])
