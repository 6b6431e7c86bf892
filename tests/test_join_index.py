"""The sorted-key join's direct-address index against its binary search.

``relational.join_build(..., slots=S)`` adds a prefix count of the valid
build keys over ``[lo, lo + S)``; the probe then reads each key's
``[start, end)`` from it instead of two ``searchsorted`` calls. The two
must agree for every probe key below INT32_MAX, and ``HashJoin`` must
pick the index only for exact keys over a dense enough range.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Session
from repro.core import dtypes as dt
from repro.core import operators as ops
from repro.core import relational as rel
from repro.core.scheduler import SchedulerConfig
from repro.core.table import DeviceTable
from repro.kernels import ops as kernel_ops
from repro.tpch import dbgen

I32 = np.iinfo(np.int32)


def _slots(keys, valid):
    vk = keys[valid].astype(np.int64)
    return 1 << int(np.ceil(np.log2(vk.max() - vk.min() + 2)))


def _probes(rng, keys, valid, n=64):
    """Probe keys below, inside and above the valid range, and the int32
    extremes (INT32_MAX excluded: see the test below)."""
    vk = keys[valid].astype(np.int64)
    lo, hi = int(vk.min()), int(vk.max())
    near = np.concatenate([rng.integers(lo - 5, hi + 6, n),
                           [lo - 1, lo, hi, hi + 1, hi + 2]])
    edges = [I32.min, I32.min + 1, -1, 0, 1, I32.max - 1]
    return np.clip(np.concatenate([near, edges]), I32.min,
                   I32.max - 1).astype(np.int32)


def _case(name, rng):
    """(build keys, build validity) of one case, 1-D or stacked [W, cap]."""
    if name == "duplicates_invalid":
        keys = rng.integers(0, 40, 96)
    elif name == "negative":
        keys = rng.integers(-1000, -960, 96)
    elif name == "near_int32_min":
        keys = I32.min + rng.integers(0, 50, 96)
    elif name == "near_int32_max":
        keys = I32.max - 1 - rng.integers(0, 50, 96)
    elif name == "stacked":
        keys = rng.integers(-30, 90, (3, 64))
    else:
        raise ValueError(name)
    valid = rng.random(keys.shape) < 0.7
    valid.reshape(-1)[0] = True
    return keys.astype(np.int32), valid


def _build_probe(keys, valid, probes, slots):
    """(match range, join_probe result, semi mask) of one build."""
    bt = rel.join_build(jnp.asarray(keys), jnp.asarray(valid), slots)
    pk = jnp.asarray(probes)
    pv = jnp.asarray(np.arange(len(probes)) % 5 != 0)
    start, end = rel._match_range(bt, pk)
    res = rel.join_probe(bt, pk, pv, 3)
    return (np.asarray(start), np.asarray(end),
            jax.tree.map(np.asarray, res),
            np.asarray(rel.semi_mask(bt, pk, pv)))


@pytest.mark.parametrize("name", ["duplicates_invalid", "negative",
                                  "near_int32_min", "near_int32_max",
                                  "stacked"])
@pytest.mark.parametrize("seed", [0, 1])
def test_index_bounds_equal_binary_search(name, seed):
    rng = np.random.default_rng(seed)
    keys, valid = _case(name, rng)
    slots = _slots(keys, valid)
    probes = _probes(rng, keys, valid)
    if keys.ndim == 1:
        dense = _build_probe(keys, valid, probes, slots)
        sorted_ = _build_probe(keys, valid, probes, 0)
    else:   # one program per worker under vmap, as a stacked build runs
        w = keys.shape[0]
        probes = np.stack([probes] * w)
        dense, sorted_ = (
            jax.tree.map(np.asarray, jax.vmap(functools.partial(
                _build_probe_traced, slots=s))(keys, valid, probes))
            for s in (slots, 0))
    (s1, e1, r1, m1), (s0, e0, r0, m0) = dense, sorted_
    np.testing.assert_array_equal(s1, s0)
    np.testing.assert_array_equal(e1, e0)
    np.testing.assert_array_equal(r1.valid, r0.valid)
    np.testing.assert_array_equal(r1.match_count, r0.match_count)
    np.testing.assert_array_equal(r1.build_idx[r1.valid], r0.build_idx[r0.valid])
    np.testing.assert_array_equal(r1.probe_idx[r1.valid], r0.probe_idx[r0.valid])
    np.testing.assert_array_equal(m1, m0)
    assert r1.valid.any() and m1.any()


def _build_probe_traced(keys, valid, probes, slots):
    bt = rel.join_build(keys, valid, slots)
    pv = jnp.arange(probes.shape[0]) % 5 != 0
    start, end = rel._match_range(bt, probes)
    return (start, end, rel.join_probe(bt, probes, pv, 3),
            rel.semi_mask(bt, probes, pv))


@pytest.mark.parametrize("top", [9, I32.max - 1])
def test_int32_max_probe_never_matches_padded_rows(top):
    """Invalid build rows sort to the end as INT32_MAX; the index counts
    valid keys alone, so a probe key of INT32_MAX finds nothing, also
    where INT32_MAX lies inside the index's range."""
    keys = np.asarray([top - 6, top, top, top - 5, top - 2], np.int32)
    valid = np.asarray([True, True, False, True, False])
    slots = _slots(keys, valid)
    bt = rel.join_build(jnp.asarray(keys), jnp.asarray(valid), slots)
    assert (I32.max - (top - 6) < slots) == (top > 9)
    pk = jnp.asarray([I32.max, top, top - 2], np.int32)
    pv = jnp.ones(3, bool)
    res = rel.join_probe(bt, pk, pv, 4)
    assert np.asarray(res.match_count).tolist() == [0, 1, 0]
    assert np.asarray(rel.semi_mask(bt, pk, pv)).tolist() == [False, True,
                                                             False]


# ---------------------------------------------------------------------------
# HashJoin: which builds take the index, and equal rows on either path
# ---------------------------------------------------------------------------

def _tables(build_keys, probe_keys, composite):
    bk = np.asarray(build_keys, np.int32)
    pk = np.asarray(probe_keys, np.int32)
    bcols = {"k": bk, "pay": np.arange(len(bk), dtype=np.int32) * 10}
    pcols = {"k": pk, "pv": np.arange(len(pk), dtype=np.int32)}
    bschema = {"k": dt.INT32, "pay": dt.INT32}
    pschema = {"k": dt.INT32, "pv": dt.INT32}
    if composite:                  # a hashed two-column key
        bcols["g"], pcols["g"] = bk % 3, pk % 3
        bschema["g"] = pschema["g"] = dt.INT32
    b = DeviceTable.from_numpy(bcols, bschema)
    bvalid = jnp.asarray(np.arange(len(bk)) % 7 != 3)
    return (DeviceTable(b.columns, bvalid, b.schema),
            DeviceTable.from_numpy(pcols, pschema))


def _join_rows(build, probe, join_type, max_matches, keys):
    """Sorted valid output rows of a jnp-backend HashJoin, and its
    ``executor_stats()["join"]`` counters."""
    payload = () if join_type in ("left_semi", "left_anti") else ("pay",)
    counts = {}
    with kernel_ops.use_backend("jnp"), ops.collect_join_stats(counts):
        join = ops.HashJoin(keys, keys, payload, join_type=join_type,
                            max_matches=max_matches)
        join.open()
        join.add_build(build)
        join.seal_build()
        (out,) = join.add_input(probe)
    live = np.asarray(out.validity)
    cols = sorted(out.column_names)
    rows = sorted(zip(*(np.asarray(out.columns[c])[live].tolist()
                        for c in cols)))
    return rows, counts


# build keys: 24 keys over 0..19 with duplicates (passes the 16x rule),
# 24 keys spread 10^6 apart (fails it), and keys up to INT32_MAX (dense,
# but a valid INT32_MAX key keeps the binary search)
_DENSE = [i % 20 for i in range(24)]
_SPARSE = [i * 1_000_003 - 7_000_000 for i in range(24)]
_TOP = [I32.max - i % 4 for i in range(24)]
_PROBE = [-3, 0, 1, 5, 5, 19, 22, 40, 2_000_003, -5_999_997, 1_000_003 * 9,
          I32.max - 1]


@pytest.mark.parametrize("join_type, max_matches", [
    ("inner", 1), ("inner", 3), ("left_semi", 1), ("left_anti", 1),
    ("left_outer", 3)])
@pytest.mark.parametrize("build, composite, path", [
    (_DENSE, False, "dense"), (_SPARSE, False, "sorted"),
    (_TOP, False, "sorted"), (_DENSE, True, "sorted")])
def test_hash_join_index_path(monkeypatch, join_type, max_matches, build,
                              composite, path):
    keys = ("k", "g") if composite else ("k",)
    probe = _PROBE + [b for b in build[::5]]
    b, p = _tables(build, probe, composite)
    rows, counts = _join_rows(b, p, join_type, max_matches, keys)
    assert counts["dense_probes" if path == "dense" else "sorted_probes"] == 1
    assert counts.get("dense_probes" if path == "sorted" else
                      "sorted_probes", 0) == 0
    assert counts["index_slots"] == (32 if path == "dense" else 0)
    # the same join with the index ruled out: the binary search's rows
    monkeypatch.setattr(ops, "MAX_INDEX_SLOTS_PER_ROW", 0)
    want, counts = _join_rows(b, p, join_type, max_matches, keys)
    assert counts["sorted_probes"] == 1 and counts["index_slots"] == 0
    assert rows == want
    assert rows or join_type == "left_anti"


def test_session_join_reports_index_and_warm_run_traces_nothing():
    sess = Session(dbgen.load_catalog(sf=0.002), batch_rows=2048,
                   kernel_backend="jnp",
                   scheduler_config=SchedulerConfig(cache_results=False))
    text = ("SELECT o_orderpriority, count(*) AS n FROM orders, customer "
            "WHERE o_custkey = c_custkey AND c_mktsegment = 'BUILDING' "
            "GROUP BY o_orderpriority")
    handles = []
    for _ in range(2):
        handles.append(sess.sql(text).submit())
        handles[-1].result()
    sess.scheduler().close()
    cold, warm = handles
    assert cold.executor_stats["traces"].get("join", 0) > 0
    assert warm.executor_stats["traces"] == {}
    join = warm.executor_stats["join"]
    assert join["dense_probes"] > 0 and join["sorted_probes"] == 0
    assert join["index_slots"] >= 1 << 8
    shown = sess.sql(text).explain(analyze=True)
    stats = sess.executor_stats()["join"]
    assert "join: " + " ".join(f"{k}={v}" for k, v in stats.items()) in shown
