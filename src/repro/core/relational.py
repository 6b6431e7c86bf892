"""Pure-JAX relational algorithms on static-shape columnar batches.

These are the TPU adaptations of cuDF's SIMT primitives:
dynamic hash tables become sort-based segmenting / open-addressing in fixed
buffers, dynamic output sizes become static-capacity expansions with planner
hints. The Pallas kernels in repro.kernels accelerate the hot spots; these
functions double as their oracles.

All functions operate on raw jnp arrays + a validity mask so they can be
reused by operators, kernels' ref.py, and the exchange partitioner.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels import ops as kernel_ops

INT32_MAX = jnp.iinfo(jnp.int32).max

# the pallas segmented-agg kernels accumulate in GROUP_BLOCK slabs; past
# this capacity (or for 8-byte values) the jnp segment_* path is both
# faster to trace and exact, so dispatch falls back. Inclusive bound,
# matching the VMEM sizing note in kernels/segmented_agg.py: exactly
# 1 << 16 groups still dispatches to the kernels; all accumulators
# (float sum, int sum, min/max) share it.
PALLAS_AGG_GROUP_LIMIT = 1 << 16


# ---------------------------------------------------------------------------
# hashing
# ---------------------------------------------------------------------------

def hash32(x: jax.Array) -> jax.Array:
    """Murmur3-style finalizer; output restricted to [0, 2^31-1)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return (x & jnp.uint32(0x7FFFFFFE)).astype(jnp.int32)


def hash_combine(cols: Sequence[jax.Array]) -> jax.Array:
    """Combine >=1 columns into a 31-bit hash key (verify-after-join).
    2-D columns (fixed-width bytes) hash by folding their byte lanes."""
    n = cols[0].shape[0]
    h = jnp.zeros((n,), dtype=jnp.uint32)

    def mix(h, c):
        hc = hash32(c.astype(jnp.int32)).astype(jnp.uint32)
        return h ^ (hc + jnp.uint32(0x9E3779B9) + (h << 6) + (h >> 2))

    for c in cols:
        if c.ndim == 2:       # bytes column: fold 4-byte words then mix
            folded = jnp.zeros((n,), dtype=jnp.uint32)
            for j in range(c.shape[1]):
                folded = folded * jnp.uint32(31) + c[:, j].astype(jnp.uint32)
            h = mix(h, folded)
        else:
            h = mix(h, c)
    return (h & jnp.uint32(0x7FFFFFFE)).astype(jnp.int32)


def join_key(cols: Sequence[jax.Array]) -> jax.Array:
    """Single int32 join key: one int column as it is, else the hash of
    ``hash_combine``, which unequal tuples may share: a join on it reads
    its whole hash run and verifies the original columns after (only for
    keys ``HashJoin`` can neither keep raw nor pack with ``packed_key``)."""
    if len(cols) == 1 and jnp.issubdtype(cols[0].dtype, jnp.integer):
        return cols[0].astype(jnp.int32)
    return hash_combine(cols)


def packed_key(cols: Sequence[jax.Array], pack: Sequence[Tuple[int, int]],
               empty_key: int = -1) -> jax.Array:
    """Injectively pack int columns into one nonnegative int32 key.

    ``pack`` gives a ``(lo, span)`` window per column: the valid build
    side's value range, read at ``HashJoin.seal_build`` (eligible only when
    the spans' product fits 2^31 - 1, so the fold below never overflows
    int32). In-range rows map to a *unique* key in ``[0, prod(spans))``:
    strictly nonnegative, so a packed key can never alias the empty-slot
    sentinel. Rows with any column outside its window cannot equal any
    build key and map to ``empty_key``; values are clipped before folding
    so even far-out-of-range probes stay overflow-free.
    """
    n = cols[0].shape[0]
    key = jnp.zeros((n,), jnp.int32)
    ok = jnp.ones((n,), bool)
    for c, (lo, span) in zip(cols, pack):
        c = c.astype(jnp.int32)
        ok = ok & (c >= lo) & (c < lo + span)
        key = key * span + jnp.clip(c - lo, 0, span - 1)
    return jnp.where(ok, key, empty_key)


# ---------------------------------------------------------------------------
# sorting
# ---------------------------------------------------------------------------

def lexsort(keys: List[jax.Array], validity: jax.Array,
            descending: Sequence[bool] = None) -> jax.Array:
    """Stable multi-key sort order; invalid rows sort last.

    ``keys[0]`` is the primary key. 2-D (bytes) keys are reduced to their
    per-row bytes interpreted big-endian via iterative column passes.
    """
    n = validity.shape[0]
    descending = descending or [False] * len(keys)
    order = jnp.arange(n, dtype=jnp.int32)

    def _passes(key, desc):
        # yield (column, descending) 1-D sort passes, least significant first
        if key.ndim == 2:   # fixed-width bytes: sort byte columns right-to-left
            cols = [key[:, j].astype(jnp.int32) for j in range(key.shape[1])]
            cols = list(reversed(cols))
        else:
            if jnp.issubdtype(key.dtype, jnp.floating):
                cols = [key]
            else:
                cols = [key.astype(jnp.int32)]
        return [(c, desc) for c in cols]

    # stable multi-pass sort: apply passes least-significant first, so the
    # *last* applied pass is the most significant. keys[0] is primary ->
    # iterate keys in reverse; validity is applied last (most significant:
    # valid rows (0) before invalid (1)).
    all_passes = []
    for key, desc in reversed(list(zip(keys, descending))):
        all_passes.extend(_passes(key, desc))
    all_passes.append(((~validity).astype(jnp.int32), False))

    for k, desc in all_passes:  # least-significant first
        cur = jnp.take(k, order)
        if desc:
            # Stable descending without negating values (negation corrupts
            # INT32_MIN, which overflows back to itself, and loses the
            # -0.0 < 0.0 total-order distinction on floats): stably argsort
            # the reversed array and flip the result, which reverses the
            # comparison while preserving original order among equal keys.
            perm = (cur.shape[0] - 1 - jnp.argsort(cur[::-1], stable=True))[::-1]
        else:
            perm = jnp.argsort(cur, stable=True)
        order = jnp.take(order, perm)
    return order


# ---------------------------------------------------------------------------
# group-by
# ---------------------------------------------------------------------------

class Groups(NamedTuple):
    """Output of ``group_rows``: permutation, dense group ids, count,
    representative row per group, and the group-slot validity mask."""

    order: jax.Array        # row permutation, valid rows first, grouped
    gids: jax.Array         # group id per *sorted* row; invalid -> max_groups
    num_groups: jax.Array   # scalar
    key_rows: jax.Array     # indices (into original rows) of one representative
                            # row per group, for gathering key columns
    group_valid: jax.Array  # bool[max_groups]


def group_rows(key_cols: List[jax.Array], validity: jax.Array,
               max_groups: int) -> Groups:
    """Assign dense group ids via sort + boundary detection.

    This is the sort-based groupby a TPU prefers over cuDF's dynamic hash
    table: lexsort the keys, mark rows where any key changes, prefix-sum the
    boundaries. Exact for arbitrarily many key columns (no hashing).
    """
    order = lexsort(key_cols, validity)
    valid_sorted = jnp.take(validity, order)
    change = jnp.zeros(order.shape, dtype=bool)
    for k in key_cols:
        ks = jnp.take(k, order, axis=0)
        if ks.ndim == 2:
            diff = jnp.any(ks[1:] != ks[:-1], axis=1)
        else:
            diff = ks[1:] != ks[:-1]
        change = change.at[1:].set(change[1:] | diff)
    change = change & valid_sorted
    gids = jnp.cumsum(change.astype(jnp.int32))
    gids = jnp.where(valid_sorted, gids, max_groups)
    num_groups = jnp.sum(change.astype(jnp.int32)) + jnp.any(validity).astype(jnp.int32)

    # representative original-row index per group (first row of each segment)
    first_of_group = valid_sorted & (jnp.concatenate([jnp.ones(1, bool), change[1:]]))
    reps = jnp.zeros(max_groups + 1, dtype=jnp.int32)
    reps = reps.at[jnp.where(first_of_group, gids, max_groups)].set(order)
    group_valid = jnp.arange(max_groups) < num_groups
    return Groups(order, gids, num_groups, reps[:max_groups], group_valid)


def segment_agg(values: jax.Array, gids: jax.Array, order: jax.Array,
                validity: jax.Array, max_groups: int, kind: str) -> jax.Array:
    """Aggregate ``values`` per group id. kind in sum|count|min|max.

    Under the 'pallas' kernel backend every kind dispatches to a
    segmented-agg kernel for 1-D 4-byte values: float sums to the MXU
    scatter-add, integer sums and counts to its int32-accumulator variant
    (exact past 2^24, wrapping at 2^31 like the oracle), min/max to the
    masked-reduction variant. The only remaining fallback is capacity —
    ``max_groups`` past ``PALLAS_AGG_GROUP_LIMIT`` (an inclusive bound:
    exactly ``1 << 16`` groups still dispatches) — plus 8-byte/multi-dim
    values; those run the ``jax.ops.segment_*`` path, which doubles as
    the kernel's oracle.
    """
    v = jnp.take(values, order, axis=0)
    valid_sorted = jnp.take(validity, order)
    seg = jnp.where(valid_sorted, gids, max_groups)

    kernel_kind_ok = (v.ndim == 1 and v.dtype.itemsize <= 4 and (
        kind == "count"
        or (kind in ("sum", "min", "max")
            and (jnp.issubdtype(v.dtype, jnp.floating)
                 or jnp.issubdtype(v.dtype, jnp.integer)))))
    pallas_ok = (kernel_ops.current_backend() == "pallas"
                 and kernel_kind_ok
                 and max_groups <= PALLAS_AGG_GROUP_LIMIT)
    if pallas_ok:
        if kind == "count":
            return kernel_ops.segmented_int_sum(
                seg, valid_sorted.astype(jnp.int32), max_groups)
        if kind == "sum":
            # zero dead rows: their values may be NaN/inf (dead-lane
            # arithmetic) and 0 * NaN would poison the one-hot matmul
            acc = jnp.where(valid_sorted, v, jnp.zeros((), v.dtype))
            if jnp.issubdtype(v.dtype, jnp.integer):
                # int32 accumulator: exact past 2^24, same wrap as oracle
                return kernel_ops.segmented_int_sum(
                    seg, acc, max_groups).astype(v.dtype)
            # float32 accumulation: inexact-by-reduction-order exactly
            # like any matmul reduction
            out = kernel_ops.segmented_sum(seg, acc.astype(jnp.float32),
                                           max_groups)
            return out.astype(v.dtype)
        # min/max: dead rows carry the reduction identity so NaN/inf
        # dead-lane arithmetic can't leak into a group
        acc = jnp.where(valid_sorted, v,
                        _extreme(v.dtype, +1 if kind == "min" else -1))
        return kernel_ops.segmented_minmax(seg, acc, max_groups, kind)

    if kernel_ops.current_backend() == "pallas" and kernel_kind_ok:
        # eligible shape/kind, blocked only by capacity: the static
        # max_groups bound pushed an otherwise kernel-servable
        # aggregation onto the jnp path. Recorded per dispatch so
        # adaptive re-planning can prove it shrank the count. Gated on
        # the pallas backend: a jnp session never "falls back", so its
        # kernel_dispatch stats must stay empty.
        kernel_ops.mark_fallback("agg")

    n = max_groups + 1
    if kind == "count":
        out = jax.ops.segment_sum(valid_sorted.astype(jnp.int32), seg, n,
                                  indices_are_sorted=True)
    elif kind == "sum":
        acc = jnp.where(valid_sorted, v, jnp.zeros((), dtype=v.dtype))
        out = jax.ops.segment_sum(acc, seg, n, indices_are_sorted=True)
    elif kind == "min":
        big = _extreme(v.dtype, +1)
        out = jax.ops.segment_min(jnp.where(valid_sorted, v, big), seg, n,
                                  indices_are_sorted=True)
    elif kind == "max":
        small = _extreme(v.dtype, -1)
        out = jax.ops.segment_max(jnp.where(valid_sorted, v, small), seg, n,
                                  indices_are_sorted=True)
    else:
        raise ValueError(kind)
    return out[:max_groups]


def _extreme(dtype, sign):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(sign * jnp.inf, dtype=dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if sign > 0 else info.min, dtype=dtype)


# ---------------------------------------------------------------------------
# joins (sort + searchsorted, or a direct-address index over the key
# range; the Pallas kernel gives the hash-table variant)
# ---------------------------------------------------------------------------

class BuildTable(NamedTuple):
    """Sorted join build side (keys, permutation, original validity), and
    optionally a direct-address index over the valid keys' range."""

    sorted_keys: jax.Array   # int32[B], invalid rows pushed to +inf end
    perm: jax.Array          # int32[B] permutation into original build rows
    validity: jax.Array      # original build validity
    # int32[S+1]: index[i] counts the valid keys below lo + i, where
    # lo = sorted_keys[0] is the smallest (None: binary search)
    index: Optional[jax.Array] = None


def _key_offset(keys: jax.Array, lo: jax.Array, slots: int) -> jax.Array:
    """``keys - lo`` clamped to [0, slots]; the difference is taken in
    uint32, where it is exact for every ``keys >= lo``, so no int32 key
    wraps around."""
    u32 = lambda x: lax.bitcast_convert_type(x, jnp.uint32)  # noqa: E731
    d = jnp.minimum(u32(keys) - u32(lo), jnp.uint32(slots))
    return jnp.where(keys < lo, 0, d.astype(jnp.int32))


def join_build(keys: jax.Array, validity: jax.Array,
               slots: int = 0) -> BuildTable:
    """Sort build keys (invalid rows to the end) for the probes.

    With ``slots`` S > 0 the table also carries a prefix count of the
    valid keys over ``[lo, lo + S)``, ``lo`` their minimum: the caller
    guarantees ``max - lo + 2 <= S``, so the last slot counts no key."""
    k = jnp.where(validity, keys, INT32_MAX)
    perm = jnp.argsort(k, stable=True).astype(jnp.int32)
    sorted_keys = jnp.take(k, perm)
    index = None
    if slots:
        off = _key_offset(keys, sorted_keys[0], slots)
        off = jnp.where(validity, off, slots)
        hist = jnp.zeros((slots,), jnp.int32).at[off].add(1, mode="drop")
        index = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                 jnp.cumsum(hist, dtype=jnp.int32)])
    return BuildTable(sorted_keys, perm, validity, index)


def _match_range(bt: BuildTable, probe_keys: jax.Array):
    """Per probe key, [start, end) of its equal keys in ``sorted_keys``:
    two reads of the index where the table has one, else two binary
    searches. The two agree for every key below INT32_MAX; at INT32_MAX
    the index does not count the invalid rows padded to the end."""
    if bt.index is None:
        start = jnp.searchsorted(bt.sorted_keys, probe_keys, side="left")
        end = jnp.searchsorted(bt.sorted_keys, probe_keys, side="right")
        return start.astype(jnp.int32), end.astype(jnp.int32)
    slots = bt.index.shape[0] - 1
    lo = bt.sorted_keys[0]
    i = _key_offset(probe_keys, lo, slots)
    nxt = jnp.minimum(i + (probe_keys >= lo), slots)
    return (jnp.take(bt.index, i, mode="clip"),
            jnp.take(bt.index, nxt, mode="clip"))


class ProbeResult(NamedTuple):
    """Expanded probe output: per output row the matched build/probe
    indices + liveness, and per probe row its match count."""

    build_idx: jax.Array     # int32[P*M] original build row per output row
    probe_idx: jax.Array     # int32[P*M] probe row per output row
    valid: jax.Array         # bool[P*M]
    match_count: jax.Array   # int32[P] matches per probe row (pre-expansion)


def join_probe(bt: BuildTable, probe_keys: jax.Array, probe_valid: jax.Array,
               max_matches: int) -> ProbeResult:
    """Expansion probe with static output capacity P * max_matches."""
    p = probe_keys.shape[0]
    start, end = _match_range(bt, probe_keys)
    count = jnp.where(probe_valid, end - start, 0)
    m = max_matches
    j = jnp.arange(p * m, dtype=jnp.int32)
    pi = j // m
    k = j % m
    within = k < jnp.take(count, pi)
    b = jnp.clip(jnp.take(start, pi) + k, 0, bt.sorted_keys.shape[0] - 1)
    bidx = jnp.take(bt.perm, b)
    valid = within & jnp.take(probe_valid, pi) & jnp.take(bt.validity, bidx)
    return ProbeResult(bidx, pi, valid, count)


def longest_run(bt: BuildTable) -> jax.Array:
    """Most valid build rows sharing one key: the candidates a probe of
    that key reads (0 for an empty build)."""
    k = bt.sorted_keys
    run = jnp.arange(1, k.shape[0] + 1) - jnp.searchsorted(k, k, side="left")
    return jnp.max(jnp.where(jnp.take(bt.validity, bt.perm), run, 0))


def semi_mask(bt: BuildTable, probe_keys: jax.Array,
              probe_valid: jax.Array) -> jax.Array:
    """probe rows with >=1 match (EXISTS). Anti = probe_valid & ~semi."""
    start, end = _match_range(bt, probe_keys)
    return probe_valid & (end > start)


# ---------------------------------------------------------------------------
# partitioning (exchange support)
# ---------------------------------------------------------------------------

def partition_ids(key_cols: Sequence[jax.Array], validity: jax.Array,
                  num_partitions: int) -> jax.Array:
    """Hash-partition rows for the exchange; invalid rows -> partition 0."""
    h = hash_combine(list(key_cols))
    pid = jnp.remainder(h, num_partitions)
    return jnp.where(validity, pid, 0).astype(jnp.int32)


def partition_layout(pids: jax.Array, validity: jax.Array, num_partitions: int,
                     part_capacity: int) -> Tuple[jax.Array, jax.Array]:
    """Stable scatter layout: row -> slot within [num_partitions, capacity].

    Returns (gather_idx, out_valid): ``gather_idx[p*cap + s]`` is the source
    row for slot s of partition p. Rows past a partition's capacity are
    dropped (callers size capacity from the flow-control governor; the
    Pallas radix_partition kernel mirrors this contract).
    """
    n = pids.shape[0]
    pids = jnp.where(validity, pids, num_partitions)  # invalid -> overflow bin
    order = jnp.argsort(pids, stable=True).astype(jnp.int32)
    sorted_pids = jnp.take(pids, order)
    # rank within partition = position - first position of this partition
    first = jnp.searchsorted(sorted_pids, jnp.arange(num_partitions + 1,
                                                     dtype=jnp.int32), side="left")
    rank = jnp.arange(n, dtype=jnp.int32) - jnp.take(first, sorted_pids)
    in_cap = (rank < part_capacity) & (sorted_pids < num_partitions)
    total = num_partitions * part_capacity
    slot = sorted_pids * part_capacity + jnp.clip(rank, 0, part_capacity - 1)
    slot = jnp.where(in_cap, slot, total)        # rejected rows scatter OOB
    gather = jnp.zeros((total,), dtype=jnp.int32)
    gather = gather.at[slot].set(order, mode="drop")
    out_valid = jnp.zeros((total,), dtype=bool)
    out_valid = out_valid.at[slot].set(True, mode="drop")
    return gather, out_valid
