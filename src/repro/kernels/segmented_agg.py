"""Segmented aggregation via one-hot MXU scatter-add (Pallas).

cuDF's hash-aggregation scatter is warp-atomic on GPU; the TPU has no
atomics, so the adaptation turns scatter-add into a matmul:
for each row block, one_hot(gids)ᵀ @ values accumulates onto the group
vector using the MXU — the systolic array does the reduction. The grid is
sequential on TPU, so output-block accumulation across row blocks is safe.

Group counts beyond the block width accumulate in slabs of GROUP_BLOCK.

VMEM sizing: each (group slab, row block) grid step materializes a
[ROW_BLOCK, GROUP_BLOCK] one-hot (4 MiB at the 1024x1024 defaults) next to
the in/out blocks, well inside a ~16 MiB core. The slab loop makes the
kernels correct for any group count; the engine's dispatch cap
(``relational.PALLAS_AGG_GROUP_LIMIT``) is an *inclusive* bound — exactly
``1 << 16`` groups (64 slabs) still dispatches here, ``(1 << 16) + 1``
takes the jnp fallback — chosen where slab-loop trace time starts to beat
the kernel's win. All three accumulators (float sum, int sum, min/max)
share the bound.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_BLOCK = 1024
GROUP_BLOCK = 1024

# Inclusive group-count dispatch bound, kept in sync by hand with
# ``core.relational.PALLAS_AGG_GROUP_LIMIT`` (the kernels package cannot
# import core — core imports kernels). A regression test pins the two.
STACKED_GROUP_LIMIT = 1 << 16


def stacked_group_capacity(max_groups: int, limit: int = STACKED_GROUP_LIMIT
                           ) -> int:
    """How many queries can stack into one segmented-aggregation dispatch.

    Inter-query batching (``core.batch``) fuses B compatible aggregations
    by remapping ``group_id = query_id * max_groups + local_group``, so
    the kernels see one segmented problem with ``B * max_groups`` groups.
    The slab loop is correct for any count, but past ``limit`` (inclusive,
    matching the solo dispatch bound) trace time beats the kernel's win
    and the engine takes the jnp fallback — so the scheduler caps batches
    at the largest power of two B with ``B * max_groups <= limit``
    (power of two because member lanes pad up to one; a query whose solo
    ``max_groups`` already exceeds ``limit`` gets capacity 1: solo
    execution, never a wrong result).
    """
    if max_groups <= 0:
        raise ValueError("max_groups must be positive")
    cap = limit // max_groups
    if cap <= 1:
        return 1
    return 1 << (cap.bit_length() - 1)


def _kernel(gid_ref, val_ref, out_ref, *, group_block: int):
    rows = gid_ref.shape[0]
    gids = gid_ref[...]
    vals = val_ref[...].astype(jnp.float32)
    local = gids - pl.program_id(0) * group_block  # [R], this group slab
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (rows, group_block), 1)
              == local[:, None]).astype(jnp.float32)
    # [G_blk, 1] via MXU; HIGHEST keeps the f32 values whole (the default
    # TPU matmul rounds operands to bfloat16)
    contrib = jax.lax.dot(onehot.T, vals[:, None],
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += contrib[:, 0]


@functools.partial(jax.jit, static_argnames=("num_groups", "row_block",
                                             "interpret"))
def segmented_sum(gids, values, num_groups: int, row_block: int = ROW_BLOCK,
                  interpret: bool = False):
    """gids [N] int32 (>= num_groups dropped), values [N] -> [num_groups]."""
    n = gids.shape[0]
    if n == 0:
        return jnp.zeros((num_groups,), jnp.float32)
    row_block = min(row_block, n)
    pad = (-n) % row_block
    if pad:
        gids = jnp.pad(gids, (0, pad), constant_values=num_groups)
        values = jnp.pad(values, (0, pad))
    n_pad = gids.shape[0]
    g_pad = ((num_groups + GROUP_BLOCK - 1) // GROUP_BLOCK) * GROUP_BLOCK

    grid = (g_pad // GROUP_BLOCK, n_pad // row_block)
    out = pl.pallas_call(
        functools.partial(_kernel, group_block=GROUP_BLOCK),
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_block,), lambda g, r: (r,)),
            pl.BlockSpec((row_block,), lambda g, r: (r,)),
        ],
        out_specs=pl.BlockSpec((GROUP_BLOCK,), lambda g, r: (g,)),
        out_shape=jax.ShapeDtypeStruct((g_pad,), jnp.float32),
        interpret=interpret,
    )(gids, values.astype(jnp.float32))
    return out[:num_groups]


def _int_kernel(gid_ref, val_ref, out_ref, *, group_block: int):
    """Integer scatter-add: mask each row's value onto its group lane and
    sum the row block on the VPU with an int32 accumulator, so sums stay
    exact past 2^24 (float32's integer range) and wrap at 2^31 exactly
    like the int32 ``jax.ops.segment_sum`` oracle. (The MXU has no int32
    matmul on TPU v5e, so this kernel cannot share the float one-hot
    matmul.)"""
    rows = gid_ref.shape[0]
    gids = gid_ref[...]
    vals = val_ref[...].astype(jnp.int32)
    local = gids - pl.program_id(0) * group_block
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (rows, group_block), 1)
              == local[:, None])
    contrib = jnp.sum(jnp.where(onehot, vals[:, None], 0), axis=0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += contrib


@functools.partial(jax.jit, static_argnames=("num_groups", "row_block",
                                             "interpret"))
def segmented_int_sum(gids, values, num_groups: int,
                      row_block: int = ROW_BLOCK, interpret: bool = False):
    """gids [N] int32 (>= num_groups dropped), values [N] int ->
    int32[num_groups] (exact; overflow wraps like the int32 oracle)."""
    n = gids.shape[0]
    if n == 0:
        return jnp.zeros((num_groups,), jnp.int32)
    row_block = min(row_block, n)
    pad = (-n) % row_block
    if pad:
        gids = jnp.pad(gids, (0, pad), constant_values=num_groups)
        values = jnp.pad(values, (0, pad))
    n_pad = gids.shape[0]
    g_pad = ((num_groups + GROUP_BLOCK - 1) // GROUP_BLOCK) * GROUP_BLOCK

    grid = (g_pad // GROUP_BLOCK, n_pad // row_block)
    out = pl.pallas_call(
        functools.partial(_int_kernel, group_block=GROUP_BLOCK),
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_block,), lambda g, r: (r,)),
            pl.BlockSpec((row_block,), lambda g, r: (r,)),
        ],
        out_specs=pl.BlockSpec((GROUP_BLOCK,), lambda g, r: (g,)),
        out_shape=jax.ShapeDtypeStruct((g_pad,), jnp.int32),
        interpret=interpret,
    )(gids, values.astype(jnp.int32))
    return out[:num_groups]


def _minmax_kernel(gid_ref, val_ref, out_ref, *, group_block: int,
                   is_min: bool, init):
    """Segmented min/max: mask each row's value onto its group lane (the
    identity everywhere else) and reduce the row block with a plain
    min/max — no MXU, but the same slab/accumulate structure as the sums.
    Empty groups keep the identity, matching ``jax.ops.segment_min/max``."""
    rows = gid_ref.shape[0]
    gids = gid_ref[...]
    vals = val_ref[...]
    local = gids - pl.program_id(0) * group_block
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (rows, group_block), 1)
              == local[:, None])
    ident = jnp.asarray(init, vals.dtype)
    masked = jnp.where(onehot, vals[:, None], ident)    # [R, G_blk]
    reduce = jnp.min if is_min else jnp.max
    contrib = reduce(masked, axis=0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, ident)

    merge = jnp.minimum if is_min else jnp.maximum
    out_ref[...] = merge(out_ref[...], contrib)


@functools.partial(jax.jit, static_argnames=("num_groups", "kind",
                                             "row_block", "interpret"))
def segmented_minmax(gids, values, num_groups: int, kind: str,
                     row_block: int = ROW_BLOCK, interpret: bool = False):
    """gids [N] int32 (>= num_groups dropped), values [N] ->
    [num_groups] of values.dtype; kind in ('min', 'max'). Empty groups
    hold the reduction identity (+/-inf for floats, iinfo extremes for
    ints), exactly like ``jax.ops.segment_min/max``."""
    assert kind in ("min", "max")
    is_min = kind == "min"
    if jnp.issubdtype(values.dtype, jnp.floating):
        init = float("inf") if is_min else float("-inf")
    else:
        info = jnp.iinfo(values.dtype)
        init = info.max if is_min else info.min
    n = gids.shape[0]
    if n == 0:
        return jnp.full((num_groups,), init, values.dtype)
    row_block = min(row_block, n)
    pad = (-n) % row_block
    if pad:
        gids = jnp.pad(gids, (0, pad), constant_values=num_groups)
        values = jnp.pad(values, (0, pad))
    n_pad = gids.shape[0]
    g_pad = ((num_groups + GROUP_BLOCK - 1) // GROUP_BLOCK) * GROUP_BLOCK

    grid = (g_pad // GROUP_BLOCK, n_pad // row_block)
    out = pl.pallas_call(
        functools.partial(_minmax_kernel, group_block=GROUP_BLOCK,
                          is_min=is_min, init=init),
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_block,), lambda g, r: (r,)),
            pl.BlockSpec((row_block,), lambda g, r: (r,)),
        ],
        out_specs=pl.BlockSpec((GROUP_BLOCK,), lambda g, r: (g,)),
        out_shape=jax.ShapeDtypeStruct((g_pad,), values.dtype),
        interpret=interpret,
    )(gids, values)
    return out[:num_groups]
