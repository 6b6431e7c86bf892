"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each function is the semantic ground truth the kernels are validated
against in tests/test_kernels.py (interpret mode, shape/dtype sweeps).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def segmented_agg(gids, values, num_groups: int, kind: str = "sum"):
    """gids [N] int32 (>= num_groups means dropped), values [N] f32."""
    valid = gids < num_groups
    seg = jnp.where(valid, gids, num_groups)
    if kind == "sum":
        vals = jnp.where(valid, values, 0.0)
        return jax.ops.segment_sum(vals, seg, num_groups + 1)[:num_groups]
    if kind == "count":
        return jax.ops.segment_sum(valid.astype(jnp.float32), seg,
                                   num_groups + 1)[:num_groups]
    raise ValueError(kind)


def radix_histogram(pids, num_partitions: int):
    """pids [N] int32 -> counts [num_partitions] int32 (the exchange's
    metadata phase)."""
    onehot = jax.nn.one_hot(pids, num_partitions, dtype=jnp.int32)
    return jnp.sum(onehot, axis=0)


def hash_probe(table_keys, table_vals, probe_keys, empty_key: int):
    """Open-addressing (linear probe) lookup.

    table_keys [T] int32 (power-of-two T, empty slots = empty_key),
    probe_keys [N] -> (found [N] bool, vals [N] int32)."""
    t = table_keys.shape[0]
    mask = t - 1

    def lookup(key):
        h = _hash(key) & mask

        def body(i, carry):
            found, val, done = carry
            idx = (h + i) & mask
            slot = table_keys[idx]
            hit = (slot == key) & (~done)
            miss = (slot == empty_key) & (~done)
            return (found | hit,
                    jnp.where(hit, table_vals[idx], val),
                    done | hit | miss)

        found, val, _ = jax.lax.fori_loop(
            0, t, body, (jnp.bool_(False), jnp.int32(0), jnp.bool_(False)))
        return found, val

    return jax.vmap(lookup)(probe_keys)


def _hash(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    return x.astype(jnp.int32)


def block_prefix_sum(mask):
    """mask [N] bool/int -> (exclusive positions [N] int32, total int32):
    the stream-compaction address computation."""
    m = mask.astype(jnp.int32)
    inclusive = jnp.cumsum(m)
    return inclusive - m, inclusive[-1] if m.size else jnp.int32(0)
