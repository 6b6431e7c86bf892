"""Open-addressing hash-table probe (join inner loop) in Pallas.

The cuDF GPU join probes a dynamic hash table with warp-cooperative linear
probing. TPU adaptation: the table is a power-of-two key/value array,
resident in VMEM as byte planes of a 2-D ``[slots / 128, 128]`` layout —
the engine caps eligible builds at ``operators.MAX_HASH_TABLE_SLOTS``
(2^18 slots, 4 MiB of planes) and falls back to the sorted-key path
beyond that. Mosaic cannot gather per lane from a VMEM array, so a block
of probe keys fetches the table rows its probe sequences cross with a
one-hot matmul on the MXU, then resolves every probe step at once with
lane-wise compares and min-reductions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PROBE_BLOCK = 1024
MAX_PROBES_DEFAULT = 64
LANES = 128
NO_SLOT = 1 << 30          # "no such probe distance" in min-reductions
ROW_CHUNK = 256            # table rows per one-hot gather matmul
# a 2^18-slot table's planes (4 MiB, double-buffered) plus the gathered
# rows of a 1024-key block outgrow the 16 MiB default scoped VMEM; a v5e
# core has 128 MiB
PROBE_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 << 20)


def _hash(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    return x.astype(jnp.int32)


def table_layout(table_size: int) -> tuple:
    """``(rows, lanes)`` of the 2-D slot layout the probe kernels read:
    slot ``s`` lives at ``(s // lanes, s % lanes)``, with 128 lanes once
    the table has that many slots."""
    lanes = min(table_size, LANES)
    return table_size // lanes, lanes


def table_planes(table_keys, table_vals):
    """Byte planes of a built table for the MXU row gather:
    ``bfloat16[rows, 8 * lanes]`` holding the four little-endian bytes of
    every slot key, then of every slot value. Each byte is an integer in
    [0, 255], exact in bfloat16, so a one-hot matmul returns it exactly."""
    rows, lanes = table_layout(table_keys.shape[0])
    planes = [(x.astype(jnp.int32) >> (8 * b)) & 0xFF
              for x in (table_keys, table_vals) for b in range(4)]
    return jnp.concatenate([p.reshape(rows, lanes) for p in planes],
                           axis=1).astype(jnp.bfloat16)


def _bytes_to_int(g, first: int, lanes: int):
    """Reassemble int32 lanes from four gathered byte planes."""
    out = None
    for b in range(4):
        part = g[:, (first + b) * lanes:(first + b + 1) * lanes]
        part = part.astype(jnp.int32) << (8 * b)
        out = part if out is None else out | part
    return out


def _probe_windows(planes_ref, keys, *, table_size: int, max_probes: int):
    """Gather every slot a linear probe of ``keys`` may visit.

    A probe from home slot ``h`` reads slots ``h .. h + max_probes - 1``
    (mod ``table_size``). In the 2-D layout those lie in a few consecutive
    rows starting at ``h // lanes``; each row is fetched for all lanes at
    once as ``one_hot(row) @ planes`` on the MXU (Mosaic has no per-lane
    gather from a VMEM array), in chunks of ``ROW_CHUNK`` table rows so
    the kernel's size does not grow with the table. Returns
    ``[(slot_keys, slot_vals, dist)]`` per row window, each ``[PB,
    lanes]``, where ``dist`` is the slot's probe distance from ``h`` and
    is ``-1`` past the probe budget.
    """
    rows, lanes = table_layout(table_size)
    pb = keys.shape[0]
    h = _hash(keys) & (table_size - 1)
    row0, col0 = h // lanes, h % lanes
    n_win = min(-(-(lanes - 1 + max_probes) // lanes), rows + 1)
    win_rows = [((row0 + w) % rows)[:, None] for w in range(n_win)]
    chunk = min(rows, ROW_CHUNK)
    chunk_iota = jax.lax.broadcasted_iota(jnp.int32, (pb, chunk), 1)

    def gather(part, first_row, acc):
        onehot = [(chunk_iota + first_row == r).astype(jnp.bfloat16)
                  for r in win_rows]
        return tuple(a + jax.lax.dot(o, part,
                                     preferred_element_type=jnp.float32)
                     for a, o in zip(acc, onehot))

    zero = (jnp.zeros((pb, 8 * lanes), jnp.float32),) * n_win
    if chunk == rows:      # small table: one static gather
        gathered = gather(planes_ref[...], 0, zero)
    else:
        gathered = jax.lax.fori_loop(
            0, rows // chunk,
            lambda c, acc: gather(
                planes_ref[pl.ds(pl.multiple_of(c * chunk, chunk), chunk), :],
                c * chunk, acc),
            zero)
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (pb, lanes), 1)
    out = []
    for w, g in enumerate(gathered):
        dist = w * lanes + lane_iota - col0[:, None]
        dist = jnp.where((dist >= 0) & (dist < max_probes), dist, -1)
        out.append((_bytes_to_int(g, 0, lanes), _bytes_to_int(g, 4, lanes),
                    dist))
    return out


def _first(cond, dist):
    """Smallest probe distance where ``cond`` holds -> [PB] (NO_SLOT if
    none)."""
    return jnp.min(jnp.where(cond & (dist >= 0), dist, NO_SLOT), axis=1)


def probe_loop(planes_ref, keys, *, table_size: int, empty_key: int,
               max_probes: int):
    """Single-match linear probe over a block of keys.

    Shared by the standalone ``hash_probe`` kernel and the fused morsel
    kernel (``core.fused``); ``planes_ref`` holds ``table_planes``. A key
    is found when a slot holding it comes no later than the first empty
    slot of its probe sequence; the value is that slot's. A key equal to
    ``empty_key`` therefore "finds" the empty slot, as a slot-by-slot
    probe would, and callers mask it.
    """
    wins = _probe_windows(planes_ref, keys, table_size=table_size,
                          max_probes=max_probes)
    k = keys[:, None]
    empty = functools.reduce(jnp.minimum,
                             [_first(sk == empty_key, d) for sk, _, d in wins])
    hit_d = functools.reduce(jnp.minimum,
                             [_first(sk == k, d) for sk, _, d in wins])
    found = (hit_d < NO_SLOT) & (hit_d <= empty)
    hit_d = jnp.where(found, hit_d, NO_SLOT)[:, None]
    val = functools.reduce(
        jnp.add, [jnp.sum(jnp.where(d == hit_d, sv, 0), axis=1)
                  for _, sv, d in wins])
    return found, val


def probe_loop_multi(planes_ref, keys, *, table_size: int, empty_key: int,
                     max_probes: int, max_matches: int):
    """Multi-match (expansion) probe: walk the whole occupied run.

    Duplicate build keys occupy distinct slots of one linear-probe run
    (cooperative insertion places them round by round), so every slot
    holding the key before the run's first empty slot is a match, taken in
    probe order up to ``max_matches``. Matches land in build-row order --
    duplicates are placed along the run in ascending row index -- which is
    the same order the sorted-key oracle emits.

    Returns (count int32[PB], slots int32[PB, max_matches]); slots past a
    lane's count hold zeros and must be masked by the caller.
    """
    wins = _probe_windows(planes_ref, keys, table_size=table_size,
                          max_probes=max_probes)
    k = keys[:, None]
    empty = functools.reduce(jnp.minimum,
                             [_first(sk == empty_key, d) for sk, _, d in wins])
    hits = [(jnp.where((sk == k) & (d <= empty[:, None]), d, -1), sv)
            for sk, sv, d in wins]
    count = functools.reduce(
        jnp.add, [jnp.sum((d >= 0).astype(jnp.int32), axis=1)
                  for d, _ in hits])
    count = jnp.minimum(count, max_matches)
    slots, prev = [], jnp.full(keys.shape, -1, jnp.int32)
    for _ in range(max_matches):
        nxt = functools.reduce(jnp.minimum,
                               [_first(d > prev[:, None], d) for d, _ in hits])
        slots.append(functools.reduce(
            jnp.add, [jnp.sum(jnp.where(d == nxt[:, None], sv, 0), axis=1)
                      for d, sv in hits]))
        prev = nxt
    return count, jnp.stack(slots, axis=1)


def _kernel(planes_ref, pk_ref, found_ref, val_ref, *,
            table_size: int, empty_key: int, max_probes: int):
    found, val = probe_loop(planes_ref, pk_ref[...],
                            table_size=table_size, empty_key=empty_key,
                            max_probes=max_probes)
    found_ref[...] = found
    val_ref[...] = val


def _expand_kernel(planes_ref, pk_ref, cnt_ref, slot_ref, *,
                   table_size: int, empty_key: int, max_probes: int,
                   max_matches: int):
    count, slots = probe_loop_multi(
        planes_ref, pk_ref[...], table_size=table_size,
        empty_key=empty_key, max_probes=max_probes, max_matches=max_matches)
    cnt_ref[...] = count
    slot_ref[...] = slots


@functools.partial(jax.jit, static_argnames=("table_size", "empty_key"))
def build_table(keys, vals, table_size: int, empty_key: int = -1, valid=None):
    """Linear-probing insert of (key, val) pairs -> (tkeys, tvals).

    Vectorized cooperative insertion (the GPU build idiom, no atomics):
    every unplaced key attempts slot ``(hash(key) + round) & mask`` each
    round; ties on a slot resolve by scatter-min on key index, winners are
    placed, losers advance. Occupied slots never vacate, so the resulting
    table satisfies the linear-probe invariant (a key at distance ``d``
    from its home slot has no empty slot in between) regardless of the
    placement order. Rows with ``valid`` False (or key == ``empty_key``,
    which is indistinguishable from an empty slot) are never placed;
    callers detect the latter by comparing occupied-slot and valid-row
    counts. Pure jnp: runs the same on host, device, and under ``vmap``.
    """
    n = keys.shape[0]
    mask = table_size - 1
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    tk0 = jnp.full((table_size,), empty_key, jnp.int32)
    tv0 = jnp.zeros((table_size,), jnp.int32)
    if n == 0:
        return tk0, tv0
    keys = keys.astype(jnp.int32)
    vals = vals.astype(jnp.int32)
    home = _hash(keys) & mask
    idxs = jnp.arange(n, dtype=jnp.int32)

    def cond(state):
        _, _, placed, i = state
        return jnp.any(~placed) & (i < table_size)

    def body(state):
        tk, tv, placed, i = state
        slot = (home + i) & mask
        want = (~placed) & (jnp.take(tk, slot) == empty_key)
        cand = jnp.where(want, idxs, n)
        winner = jnp.full((table_size,), n, jnp.int32).at[slot].min(
            cand, mode="drop")
        won = want & (jnp.take(winner, slot) == idxs)
        dst = jnp.where(won, slot, table_size)      # losers scatter OOB
        tk = tk.at[dst].set(keys, mode="drop")
        tv = tv.at[dst].set(vals, mode="drop")
        return tk, tv, placed | won, i + 1

    tk, tv, _, _ = jax.lax.while_loop(
        cond, body, (tk0, tv0, ~valid, jnp.int32(0)))
    return tk, tv


@functools.partial(jax.jit, static_argnames=("empty_key", "max_probes",
                                             "probe_block", "interpret"))
def hash_probe(table_keys, table_vals, probe_keys, empty_key: int = -1,
               max_probes: int = MAX_PROBES_DEFAULT,
               probe_block: int = PROBE_BLOCK, interpret: bool = False):
    """-> (found [N] bool, vals [N] int32)."""
    n = probe_keys.shape[0]
    t = table_keys.shape[0]
    assert t & (t - 1) == 0, "table size must be a power of two"
    if n == 0:
        return (jnp.zeros((0,), jnp.bool_), jnp.zeros((0,), jnp.int32))
    probe_block = min(probe_block, n)
    pad = (-n) % probe_block
    if pad:
        probe_keys = jnp.pad(probe_keys, (0, pad), constant_values=empty_key)
    grid = (probe_keys.shape[0] // probe_block,)
    planes = table_planes(table_keys, table_vals)
    found, vals = pl.pallas_call(
        functools.partial(_kernel, table_size=t, empty_key=empty_key,
                          max_probes=min(max_probes, t)),
        grid=grid,
        in_specs=[
            pl.BlockSpec(planes.shape, lambda i: (0, 0)),   # VMEM-resident
            pl.BlockSpec((probe_block,), lambda i: (i,)),
        ],
        out_specs=[pl.BlockSpec((probe_block,), lambda i: (i,)),
                   pl.BlockSpec((probe_block,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((probe_keys.shape[0],), jnp.bool_),
                   jax.ShapeDtypeStruct((probe_keys.shape[0],), jnp.int32)],
        compiler_params=PROBE_COMPILER_PARAMS,
        interpret=interpret,
    )(planes, probe_keys)
    return found[:n], vals[:n]


@functools.partial(jax.jit, static_argnames=("max_matches", "empty_key",
                                             "max_probes", "probe_block",
                                             "interpret"))
def hash_probe_multi(table_keys, table_vals, probe_keys, max_matches: int,
                     empty_key: int = -1,
                     max_probes: int = MAX_PROBES_DEFAULT,
                     probe_block: int = PROBE_BLOCK, interpret: bool = False):
    """Expansion probe -> (count int32[N], slots int32[N, max_matches]).

    ``slots[i, :count[i]]`` are the table values (build row indices) of
    every slot whose key equals ``probe_keys[i]``, in run order; entries
    past the count are garbage. Probe keys equal to ``empty_key`` report a
    bogus match (an empty slot compares equal) and must be masked by the
    caller, exactly as with ``hash_probe``.
    """
    n = probe_keys.shape[0]
    t = table_keys.shape[0]
    assert t & (t - 1) == 0, "table size must be a power of two"
    m = max_matches
    if n == 0:
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0, m), jnp.int32))
    probe_block = min(probe_block, n)
    pad = (-n) % probe_block
    if pad:
        probe_keys = jnp.pad(probe_keys, (0, pad), constant_values=empty_key)
    n_pad = probe_keys.shape[0]
    grid = (n_pad // probe_block,)
    planes = table_planes(table_keys, table_vals)
    count, slots = pl.pallas_call(
        functools.partial(_expand_kernel, table_size=t, empty_key=empty_key,
                          max_probes=min(max_probes, t), max_matches=m),
        grid=grid,
        in_specs=[
            pl.BlockSpec(planes.shape, lambda i: (0, 0)),   # VMEM-resident
            pl.BlockSpec((probe_block,), lambda i: (i,)),
        ],
        out_specs=[pl.BlockSpec((probe_block,), lambda i: (i,)),
                   pl.BlockSpec((probe_block, m), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_pad,), jnp.int32),
                   jax.ShapeDtypeStruct((n_pad, m), jnp.int32)],
        compiler_params=PROBE_COMPILER_PARAMS,
        interpret=interpret,
    )(planes, probe_keys)
    return count[:n], slots[:n]
