"""Pallas kernels vs ref.py oracles: shape/dtype sweeps + hypothesis
property tests, all in interpret mode on CPU. The ``seeded_given`` sweeps
exercise the public ``kernels.ops`` wrappers (the layer the engine
dispatches through) on the degenerate shapes the engine produces: empty
batches, all-invalid batches, multi-slab group counts, and probe tables
whose occupied runs exhaust ``max_probes``."""

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, ints, sampled, seeded_given, settings, st

from repro.kernels import ops, ref
from repro.kernels.block_prefix_sum import block_prefix_sum
from repro.kernels.hash_probe import build_table, hash_probe
from repro.kernels.radix_histogram import radix_histogram
from repro.kernels.segmented_agg import GROUP_BLOCK, segmented_sum


# ---------------------------------------------------------------------------
# segmented aggregation
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 40), st.floats(-10, 10)),
                min_size=1, max_size=300),
       st.sampled_from([8, 64, 200]))
def test_segmented_sum_property(rows, row_block):
    gids = jnp.asarray([r[0] for r in rows], jnp.int32)
    vals = jnp.asarray([r[1] for r in rows], jnp.float32)
    got = segmented_sum(gids, vals, 41, row_block=row_block, interpret=True)
    want = ref.segmented_agg(gids, vals, 41, "sum")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_segmented_sum_multi_slab():
    # more groups than one GROUP_BLOCK slab
    rng = np.random.default_rng(2)
    n, g = 5000, 2500
    gids = jnp.asarray(rng.integers(0, g, n), jnp.int32)
    vals = jnp.asarray(rng.normal(0, 1, n), jnp.float32)
    got = segmented_sum(gids, vals, g, interpret=True)
    want = ref.segmented_agg(gids, vals, g, "sum")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# radix histogram
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(0, 15), min_size=1, max_size=500),
       st.sampled_from([16, 32]))
def test_radix_histogram_property(pids, nparts):
    p = jnp.asarray(pids, jnp.int32)
    got = radix_histogram(p, nparts, row_block=128, interpret=True)
    want = ref.radix_histogram(p, nparts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# hash probe
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("table_size,n_keys,n_probes",
                         [(64, 30, 100), (256, 200, 500), (1024, 100, 64)])
def test_hash_probe_matches_ref(table_size, n_keys, n_probes):
    rng = np.random.default_rng(3)
    keys = jnp.asarray(rng.choice(10_000, n_keys, replace=False), jnp.int32)
    vals = keys * 7
    tk, tv = build_table(keys, vals, table_size)
    probes = jnp.asarray(rng.integers(0, 10_000, n_probes), jnp.int32)
    got_f, got_v = hash_probe(tk, tv, probes, max_probes=table_size,
                              probe_block=64, interpret=True)
    want_f, want_v = ref.hash_probe(tk, tv, probes, empty_key=-1)
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))
    np.testing.assert_array_equal(np.asarray(got_v)[np.asarray(got_f)],
                                  np.asarray(want_v)[np.asarray(want_f)])
    # semantic check against plain membership
    member = np.isin(np.asarray(probes), np.asarray(keys))
    np.testing.assert_array_equal(np.asarray(got_f), member)
    np.testing.assert_array_equal(np.asarray(got_v)[member],
                                  np.asarray(probes)[member] * 7)


# ---------------------------------------------------------------------------
# block prefix sum (compaction addresses)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.lists(st.booleans(), min_size=1, max_size=600),
       st.sampled_from([64, 128, 256]))
def test_block_prefix_sum_property(mask, row_block):
    m = jnp.asarray(mask, jnp.bool_)
    pos, total = block_prefix_sum(m, row_block=row_block, interpret=True)
    want_pos, want_total = ref.block_prefix_sum(m)
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(want_pos))
    assert int(total) == int(want_total)


def test_prefix_sum_crosses_blocks():
    m = jnp.ones((1000,), jnp.bool_)
    pos, total = block_prefix_sum(m, row_block=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(pos), np.arange(1000))
    assert int(total) == 1000


# ---------------------------------------------------------------------------
# wrapper-vs-ref property sweeps (the kernels.ops dispatch surface)
# ---------------------------------------------------------------------------

def test_empty_inputs_all_wrappers():
    """Zero-row batches are legal engine states; every wrapper must return
    correctly shaped empties instead of dividing by a zero block count."""
    e_i = jnp.zeros((0,), jnp.int32)
    e_f = jnp.zeros((0,), jnp.float32)
    e_b = jnp.zeros((0,), jnp.bool_)
    out = ops.segmented_sum(e_i, e_f, 17)
    np.testing.assert_array_equal(np.asarray(out), np.zeros(17))
    np.testing.assert_array_equal(
        np.asarray(ops.radix_histogram(e_i, 8)), np.zeros(8, np.int32))
    pos, total = ops.block_prefix_sum(e_b)
    assert pos.shape == (0,) and int(total) == 0
    tk, tv = ops.build_table(e_i, e_i, 16)
    assert int((np.asarray(tk) != -1).sum()) == 0
    found, vals = ops.hash_probe(tk, tv, e_i)
    assert found.shape == (0,) and vals.shape == (0,)


@seeded_given(max_examples=10, n=ints(1, 400), num_groups=sampled(8, 40, 130))
def test_all_invalid_rows_property(n, num_groups):
    """All-dropped inputs (every gid/pid out of range, every mask bit off,
    an empty probe table) aggregate to zero everywhere."""
    rng = np.random.default_rng(n * 1000 + num_groups)
    gids = jnp.asarray(
        rng.integers(num_groups, num_groups + 50, n), jnp.int32)
    vals = jnp.asarray(rng.normal(0, 1, n), jnp.float32)
    got = ops.segmented_sum(gids, vals, num_groups)
    want = ref.segmented_agg(gids, vals, num_groups, "sum")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(num_groups))

    hist = ops.radix_histogram(gids, num_groups)  # every pid out of range
    np.testing.assert_array_equal(np.asarray(hist),
                                  np.zeros(num_groups, np.int32))

    mask = jnp.zeros((n,), jnp.bool_)
    pos, total = ops.block_prefix_sum(mask)
    want_pos, want_total = ref.block_prefix_sum(mask)
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(want_pos))
    assert int(total) == int(want_total) == 0

    # probe against a table with zero valid build rows: all miss
    keys = jnp.asarray(rng.integers(0, 1000, 16), jnp.int32)
    tk, tv = ops.build_table(keys, keys, 64,
                             valid=jnp.zeros((16,), jnp.bool_))
    found, _ = ops.hash_probe(tk, tv, keys)
    assert not bool(found.any())


@seeded_given(max_examples=8, n=ints(1, 4000),
              num_groups=sampled(GROUP_BLOCK + 1, 2 * GROUP_BLOCK,
                                 3 * GROUP_BLOCK + 7),
              row_block=sampled(128, 1024))
def test_multi_slab_groups_property(n, num_groups, row_block):
    """num_groups > GROUP_BLOCK forces >1 accumulation slab; the kernel
    must agree with the segment_sum oracle across the slab boundary."""
    rng = np.random.default_rng(n)
    gids = jnp.asarray(rng.integers(0, num_groups + 20, n), jnp.int32)
    vals = jnp.asarray(rng.normal(0, 1, n), jnp.float32)
    got = ops.segmented_sum(gids, vals, num_groups, row_block=row_block)
    want = ref.segmented_agg(gids, vals, num_groups, "sum")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


@seeded_given(max_examples=8, n_keys=ints(4, 500),
              table_pow=sampled(64, 256, 1024), max_probes=sampled(2, 4, 8))
def test_max_probes_exhaustion_property(n_keys, table_pow, max_probes):
    """An under-provisioned ``max_probes`` may miss keys parked deep in an
    occupied run but must never fabricate a match; once ``max_probes``
    covers the longest occupied run (+1 for the terminating empty slot)
    the probe agrees with the oracle exactly. This is the contract
    ``HashJoin`` relies on when it derives ``max_probes`` from the built
    table's occupancy."""
    n_keys = min(n_keys, table_pow // 2)     # load factor <= 1/2
    rng = np.random.default_rng(n_keys * table_pow)
    keys = jnp.asarray(rng.choice(100_000, n_keys, replace=False), jnp.int32)
    vals = jnp.asarray(rng.integers(0, 1 << 30, n_keys), jnp.int32)
    tk, tv = ops.build_table(keys, vals, table_pow)
    probes = jnp.concatenate(
        [keys, jnp.asarray(rng.integers(0, 100_000, 200), jnp.int32)])
    want_f, want_v = ref.hash_probe(tk, tv, probes, empty_key=-1)

    # exhaustion: found-set is a subset of the oracle's, values agree on it
    got_f, got_v = ops.hash_probe(tk, tv, probes, max_probes=max_probes)
    got_f, got_v = np.asarray(got_f), np.asarray(got_v)
    assert not (got_f & ~np.asarray(want_f)).any()
    np.testing.assert_array_equal(got_v[got_f], np.asarray(want_v)[got_f])

    # sufficiency: the longest occupied run bounds the probe sequence
    occ = np.asarray(tk) != -1
    runs = np.diff(np.concatenate(
        ([0], np.roll(occ, len(occ) - 1 - int(np.where(~occ)[0][-1]))
         .astype(np.int8), [0])))
    longest = int((np.where(runs == -1)[0] - np.where(runs == 1)[0]).max()) \
        if occ.any() else 0
    got_f, got_v = ops.hash_probe(tk, tv, probes, max_probes=longest + 1)
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))
    np.testing.assert_array_equal(np.asarray(got_v)[np.asarray(want_f)],
                                  np.asarray(want_v)[np.asarray(want_f)])


# ---------------------------------------------------------------------------
# expansion probe (hash_probe_multi) + composite-key packing
# ---------------------------------------------------------------------------

def _multi_oracle(tk, tv, probes):
    """All table values per probe key, as sorted lists (the kernel emits
    build-row order, and table values are build row indices)."""
    tk, tv = np.asarray(tk), np.asarray(tv)
    return [sorted(tv[tk == p].tolist()) for p in np.asarray(probes)]


@seeded_given(max_examples=8, n_keys=ints(4, 200), dup_factor=sampled(1, 3, 6),
              max_matches=sampled(1, 2, 4, 8))
def test_expansion_probe_matches_oracle_property(n_keys, dup_factor,
                                                 max_matches):
    """With enough match capacity the expansion probe returns exactly the
    duplicate build rows per key (in ascending build-row order); with less,
    it returns a prefix — never a fabricated or repeated row."""
    rng = np.random.default_rng(n_keys * 31 + dup_factor)
    base = rng.choice(50_000, n_keys, replace=False)
    keys_np = np.repeat(base, rng.integers(1, dup_factor + 1, n_keys))
    table_size = 1024
    keys_np = keys_np[: table_size // 2]     # load factor <= 1/2
    keys = jnp.asarray(keys_np, jnp.int32)
    rows = jnp.arange(keys.shape[0], dtype=jnp.int32)
    tk, tv = ops.build_table(keys, rows, table_size)
    probes = jnp.asarray(np.concatenate(
        [base, rng.integers(0, 50_000, 64)]), jnp.int32)

    count, slots = ops.hash_probe_multi(tk, tv, probes, max_matches,
                                        max_probes=table_size)
    count, slots = np.asarray(count), np.asarray(slots)
    want = _multi_oracle(tk, tv, probes)
    for i, w in enumerate(want):
        got = slots[i, : count[i]].tolist()
        assert count[i] == min(len(w), max_matches), (i, count[i], w)
        # ascending build-row order == the oracle's sorted order, so the
        # capacity-clipped kernel keeps exactly the first-m prefix
        assert got == w[:max_matches], (i, got, w)


@seeded_given(max_examples=8, n_keys=ints(4, 200), max_probes=sampled(2, 4, 8))
def test_expansion_probe_exhaustion_subset_property(n_keys, max_probes):
    """The ⊆-contract under an under-provisioned ``max_probes``, mirroring
    the single-match exhaustion sweep: matches may be missed (a run longer
    than the probe budget) but never invented, and what is returned is a
    prefix of the oracle's match list."""
    rng = np.random.default_rng(n_keys * 7)
    table_size = 256
    keys_np = rng.choice(10_000, min(n_keys, table_size // 2), replace=True)
    keys = jnp.asarray(keys_np, jnp.int32)
    rows = jnp.arange(keys.shape[0], dtype=jnp.int32)
    tk, tv = ops.build_table(keys, rows, table_size)
    probes = jnp.asarray(np.concatenate(
        [keys_np, rng.integers(0, 10_000, 100)]), jnp.int32)

    count, slots = ops.hash_probe_multi(tk, tv, probes, 8,
                                        max_probes=max_probes)
    count, slots = np.asarray(count), np.asarray(slots)
    want = _multi_oracle(tk, tv, probes)
    for i, w in enumerate(want):
        got = slots[i, : count[i]].tolist()
        assert got == w[: len(got)], (i, got, w)   # prefix, never invented


def test_expansion_probe_sentinel_key_reports_bogus_match():
    """PR-5 sentinel regression, expansion mode: a probe key equal to the
    empty sentinel (-1) compares equal to empty slots inside the kernel
    and reports a bogus match — the documented contract is that callers
    mask it (``relational``'s probe paths zero the count for sentinel
    keys), so masked counts must agree with the oracle exactly."""
    keys = jnp.asarray([5, 5, 9], jnp.int32)
    rows = jnp.arange(3, dtype=jnp.int32)
    tk, tv = ops.build_table(keys, rows, 16)
    probes = jnp.asarray([-1, 5, 9, 12], jnp.int32)
    count, slots = ops.hash_probe_multi(tk, tv, probes, 4, max_probes=16)
    count = np.asarray(count)
    assert count[0] >= 1                     # the raw kernel's bogus hit
    masked = np.where(np.asarray(probes) == -1, 0, count)
    np.testing.assert_array_equal(masked, [0, 2, 1, 0])
    np.testing.assert_array_equal(np.sort(np.asarray(slots)[1, :2]), [0, 1])


@seeded_given(max_examples=10, n=ints(1, 300),
              ncols=sampled(2, 3), span_pow=sampled(4, 10, 16))
def test_packed_key_property(n, ncols, span_pow):
    """Composite-key packing (``relational.packed_key``): injective over
    in-window tuples, nonnegative (never the sentinel), decodable back to
    the original tuple, and exactly the sentinel for out-of-window rows."""
    from repro.core import relational as rel

    rng = np.random.default_rng(n * 100 + ncols + span_pow)
    pack, prod = [], 1
    for _ in range(ncols):
        lo = int(rng.integers(-50, 50))
        # keep the window product inside the int32 key lane — the same
        # eligibility bound operators._pack_windows enforces
        budget = (np.iinfo(np.int32).max) // prod
        span = int(rng.integers(1, min(1 << span_pow, budget) + 1))
        prod *= span
        pack.append((lo, span))
    cols_np = []
    for lo, span in pack:
        # mostly in-window values, with some out-of-window outliers
        c = rng.integers(lo, lo + span, n)
        out = rng.random(n) < 0.15
        c = np.where(out, rng.integers(lo - 100, lo + span + 100, n), c)
        cols_np.append(c.astype(np.int32))
    in_window = np.ones(n, bool)
    for c, (lo, span) in zip(cols_np, pack):
        in_window &= (c >= lo) & (c < lo + span)

    key = np.asarray(rel.packed_key(
        [jnp.asarray(c) for c in cols_np], tuple(pack)))

    # sentinel preservation: out-of-window rows pack to the empty sentinel,
    # in-window rows never do (they are nonnegative by construction)
    np.testing.assert_array_equal(key == -1, ~in_window)
    assert (key[in_window] >= 0).all()

    # round-trip: decode in-window keys back to the original tuples
    dec = key[in_window].astype(np.int64)
    decoded = []
    for lo, span in reversed(pack):
        decoded.append((dec % span + lo).astype(np.int32))
        dec //= span
    for c, d in zip(cols_np, reversed(decoded)):
        np.testing.assert_array_equal(d, c[in_window])

    # injectivity over in-window tuples
    tuples = {tuple(c[i] for c in cols_np) for i in range(n) if in_window[i]}
    assert len(np.unique(key[in_window])) == len(tuples)


def test_packed_key_fits_int32_lane():
    """Windows sized to the int32 budget pack without overflow: the
    largest in-window tuple maps to span1*span2 - 1."""
    from repro.core import relational as rel

    span1, span2 = 1 << 16, (1 << 15) - 1    # product < 2^31 - 1
    pack = ((0, span1), (0, span2))
    c1 = jnp.asarray([0, span1 - 1], jnp.int32)
    c2 = jnp.asarray([0, span2 - 1], jnp.int32)
    key = np.asarray(rel.packed_key([c1, c2], pack))
    assert key[0] == 0
    assert key[1] == span1 * span2 - 1       # the largest in-window tuple
    assert (key >= 0).all()


# ---------------------------------------------------------------------------
# integer / min-max accumulators
# ---------------------------------------------------------------------------

@seeded_given(max_examples=10, n=ints(1, 2000),
              num_groups=sampled(8, 200, GROUP_BLOCK + 5))
def test_segmented_int_sum_property(n, num_groups):
    """Int accumulator vs the int32 segment_sum oracle, including values
    past float32's exact-integer range (the reason the kernel exists)."""
    import jax

    rng = np.random.default_rng(n + num_groups)
    gids = jnp.asarray(rng.integers(0, num_groups + 10, n), jnp.int32)
    vals = jnp.asarray(rng.integers(-(1 << 24), 1 << 24, n), jnp.int32)
    got = ops.segmented_int_sum(gids, vals, num_groups)
    in_range = np.asarray(gids) < num_groups
    want = jax.ops.segment_sum(vals[jnp.asarray(in_range)],
                               gids[jnp.asarray(in_range)],
                               num_segments=num_groups)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_segmented_int_sum_exact_past_2_24():
    gids = jnp.zeros((3,), jnp.int32)
    vals = jnp.asarray([1 << 24, 1, 1], jnp.int32)
    out = ops.segmented_int_sum(gids, vals, 2)
    assert int(np.asarray(out)[0]) == (1 << 24) + 2


@seeded_given(max_examples=10, n=ints(1, 2000),
              num_groups=sampled(8, 200, GROUP_BLOCK + 5),
              kind=sampled("min", "max"), floats=sampled(False, True))
def test_segmented_minmax_property(n, num_groups, kind, floats):
    """Min/max accumulators vs segment_min/max, floats and ints; empty
    groups hold the reduction identity on both sides."""
    import jax

    rng = np.random.default_rng(n * 3 + num_groups)
    gids = jnp.asarray(rng.integers(0, num_groups + 10, n), jnp.int32)
    if floats:
        vals = jnp.asarray(rng.normal(0, 100, n), jnp.float32)
    else:
        vals = jnp.asarray(rng.integers(-(1 << 30), 1 << 30, n), jnp.int32)
    got = ops.segmented_minmax(gids, vals, num_groups, kind)
    in_range = jnp.asarray(np.asarray(gids) < num_groups)
    seg = jax.ops.segment_min if kind == "min" else jax.ops.segment_max
    want = seg(vals[in_range], gids[in_range], num_segments=num_groups)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@seeded_given(max_examples=6, n_keys=ints(1, 300), dup=sampled(False, True))
def test_build_table_probe_invariant_property(n_keys, dup):
    """Any table the cooperative build produces must satisfy the linear
    probe invariant: every inserted key is reachable from its home slot
    through a gap-free occupied run (ref.hash_probe finds all of them)."""
    rng = np.random.default_rng(n_keys)
    table_size = 1024
    keys_np = rng.choice(5000, n_keys, replace=dup)
    keys = jnp.asarray(keys_np, jnp.int32)
    vals = jnp.arange(n_keys, dtype=jnp.int32)
    valid = jnp.asarray(rng.integers(0, 2, n_keys).astype(bool))
    tk, tv = ops.build_table(keys, vals, table_size, valid=valid)
    assert int((np.asarray(tk) != -1).sum()) == int(valid.sum())
    found, _ = ref.hash_probe(tk, tv, keys, empty_key=-1)
    # every valid key must be found (invalid-only keys may still be found
    # when a duplicate of them was valid)
    assert bool(np.asarray(found)[np.asarray(valid)].all())
