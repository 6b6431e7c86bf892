"""The sorted-key join's direct-address index against its binary search,
and the join-key encoding ``HashJoin.seal_build`` decides.

``relational.join_build(..., slots=S)`` adds a prefix count of the valid
build keys over ``[lo, lo + S)``; the probe then reads each key's
``[start, end)`` from it instead of two ``searchsorted`` calls. The two
must agree for every probe key below INT32_MAX, and ``HashJoin`` must
pick the index only for exact keys (raw or packed) over a dense enough
range. Composite keys whose hashes collide must join exactly on both
backends: packed where their windows fit int32, else hashed, with semi
and anti joins reading the whole hash run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Catalog, Session
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
    if composite:                  # a two-column key
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
# but a valid INT32_MAX key keeps the binary search). With g = k % 3 the
# first packs into windows 20 x 3: S = 64 <= 16 x 32 row slots, dense
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
    (_TOP, False, "sorted"), (_DENSE, True, "dense")])
def test_hash_join_index_path(monkeypatch, join_type, max_matches, build,
                              composite, path):
    keys = ("k", "g") if composite else ("k",)
    probe = _PROBE + [b for b in build[::5]]
    b, p = _tables(build, probe, composite)
    rows, counts = _join_rows(b, p, join_type, max_matches, keys)
    assert counts["dense_probes" if path == "dense" else "sorted_probes"] == 1
    assert counts.get("dense_probes" if path == "sorted" else
                      "sorted_probes", 0) == 0
    slots = 64 if composite else 32
    assert counts["index_slots"] == (slots if path == "dense" else 0)
    assert counts["hashed_builds"] == 0
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


# ---------------------------------------------------------------------------
# composite keys whose hashes collide join exactly on both backends
# ---------------------------------------------------------------------------

def _colliding(a, b):
    """Indices of the tuples ``(a[i], b[i])`` whose
    ``relational.hash_combine`` another tuple shares, ordered by hash, and
    those hashes."""
    h = np.asarray(rel.hash_combine([jnp.asarray(a), jnp.asarray(b)]))
    order = np.argsort(h, kind="stable")
    hs = h[order]
    same = hs[1:] == hs[:-1]
    dup = np.concatenate([[False], same]) | np.concatenate([same, [False]])
    return order[dup], hs[dup]


def _pair_keys(a, b):
    return (np.asarray(a, np.int64) << 32) | (np.asarray(b, np.int64)
                                              & 0xFFFFFFFF)


# every tuple of [0, 2^16) x [0, 4): 2^18 build rows need 2^19 Pallas
# slots, past MAX_HASH_TABLE_SLOTS, so both backends probe sorted keys
_GRID_A, _GRID_B = (g.reshape(-1) for g in np.meshgrid(
    np.arange(1 << 16, dtype=np.int32), np.arange(4, dtype=np.int32),
    indexing="ij"))


@pytest.mark.parametrize("join_type", ["left_semi", "left_anti"])
@pytest.mark.parametrize("windows", ["packed", "hashed"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_colliding_composite_keys_join_exactly(backend, windows, join_type):
    """Every tuple whose hash another build tuple shares is probed, so
    each hash run's later row is asked for too. Windows 2^16 x 4 pack
    (exact, the index); one more row at (2^30, -2^20) widens them past
    2^31 - 1, and the key is hashed, its semi/anti probe reading the
    build's longest hash run."""
    a, b = _GRID_A, _GRID_B
    if windows == "hashed":
        a = np.append(a, np.int32(1 << 30))
        b = np.append(b, np.int32(-(1 << 20)))
    hit, _ = _colliding(a, b)
    assert len(hit) >= 2
    rng = np.random.default_rng(0)
    pa = np.concatenate([a[hit], rng.integers(0, 1 << 16, 80)])
    pb = np.concatenate([b[hit], rng.integers(0, 4, 64),
                         rng.integers(4, 9, 16)])
    build = DeviceTable.from_numpy({"a": a, "b": b},
                                   {"a": dt.INT32, "b": dt.INT32})
    probe = DeviceTable.from_numpy(
        {"a": pa.astype(np.int32), "b": pb.astype(np.int32),
         "i": np.arange(len(pa), dtype=np.int32)},
        {"a": dt.INT32, "b": dt.INT32, "i": dt.INT32})
    counts = {}
    with kernel_ops.use_backend(backend), ops.collect_join_stats(counts):
        join = ops.HashJoin(("a", "b"), ("a", "b"), join_type=join_type)
        join.open()
        join.add_build(build)
        join.seal_build()
        (out,) = join.add_input(probe)
    got = np.asarray(out.columns["i"])[np.asarray(out.validity)]
    member = np.isin(_pair_keys(pa, pb), _pair_keys(a, b))
    want = np.flatnonzero(member if join_type == "left_semi" else ~member)
    np.testing.assert_array_equal(np.sort(got), want)
    assert counts["hashed_builds"] == (windows == "hashed")
    assert counts.get("dense_probes", 0) == (windows == "packed")
    if windows == "hashed":
        assert join._matches >= 2        # the collisions' runs, read whole


_Q5_SHAPED = ("SELECT o_nationkey, count(*) AS n, sum(o_price) AS revenue "
              "FROM orders, customer "
              "WHERE o_custkey = c_custkey AND o_nationkey = c_nationkey "
              "GROUP BY o_nationkey ORDER BY o_nationkey")


def _q5_shaped_catalog():
    """Q5's semi join in small: 140,000 customers (past the Pallas table's
    slots, as TPC-H SF 1's 150,000), among them pairs whose (c_custkey,
    c_nationkey) share a hash, and orders naming each of them. Returns
    the catalog and the numpy oracle's (nation, count, revenue)."""
    n = 140_000
    nation = (np.arange(1, n + 1) * 7 % 25).astype(np.int32)
    k, g = (x.reshape(-1) for x in np.meshgrid(
        np.arange(1, 1 << 15, dtype=np.int32),
        np.arange(25, dtype=np.int32), indexing="ij"))
    hit, hs = _colliding(k, g)
    planted: list = []
    for i in range(len(hit) - 1):
        x, y = hit[i], hit[i + 1]
        if (hs[i] == hs[i + 1] and k[x] != k[y]
                and not {k[x], k[y]} & set(planted)):
            nation[k[x] - 1], nation[k[y] - 1] = g[x], g[y]
            planted += [k[x], k[y]]
        if len(planted) >= 16:
            break
    assert len(planted) >= 2
    rng = np.random.default_rng(5)
    planted = np.asarray(planted, np.int32)
    others = rng.integers(1, n + 1, 4000).astype(np.int32)
    cust = np.concatenate([np.repeat(planted, 3), planted, others])
    nat = nation[cust - 1]
    wrong = np.concatenate([np.zeros(3 * len(planted), bool),
                            np.ones(len(planted), bool),
                            rng.random(len(others)) < 0.5])
    nat = np.where(wrong, (nat + 1 + rng.integers(0, 24, len(cust))) % 25,
                   nat).astype(np.int32)
    price = rng.integers(1, 1000, len(cust)).astype(np.int32)
    cat = Catalog()
    cat.register_numpy("customer", {"c_custkey": np.arange(1, n + 1,
                                                           dtype=np.int32),
                                    "c_nationkey": nation},
                       {"c_custkey": dt.INT32, "c_nationkey": dt.INT32},
                       unique_keys=(("c_custkey",),))
    cat.register_numpy("orders", {"o_custkey": cust, "o_nationkey": nat,
                                  "o_price": price},
                       {"o_custkey": dt.INT32, "o_nationkey": dt.INT32,
                        "o_price": dt.INT32})
    keep = nation[cust - 1] == nat
    groups = np.unique(nat[keep])
    want = [(int(q), int(np.sum(nat[keep] == q)),
             int(price[keep][nat[keep] == q].sum())) for q in groups]
    return cat, want


@pytest.mark.parametrize("backend, workers", [
    ("jnp", 1), ("pallas", 1), ("jnp", 2)])
def test_q5_shaped_semi_join_keeps_colliding_customers(backend, workers):
    cat, want = _q5_shaped_catalog()
    sess = Session(cat, kernel_backend=backend, num_workers=workers,
                   batch_rows=1 << 15,
                   scheduler_config=SchedulerConfig(cache_results=False))
    assert "Join(left_semi" in sess.sql(_Q5_SHAPED).explain()
    handle = sess.sql(_Q5_SHAPED).submit()
    res = handle.result()
    sess.scheduler().close()
    got = list(zip(*(np.asarray(res[c]).tolist()
                     for c in ("o_nationkey", "n", "revenue"))))
    assert [(int(q), int(c), int(round(r))) for q, c, r in got] == want
    assert handle.executor_stats["join"]["hashed_builds"] == 0
