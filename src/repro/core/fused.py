"""Fused per-morsel pipeline kernel (filter → project → probe in one
Pallas dispatch).

The paper's GPU wins come from keeping whole operator pipelines on-device
with no intermediate materialization; "Rethinking Analytical Processing in
the GPU Era" (PAPERS.md) makes the sharper point that per-operator kernel
launches dominate once scans are fast. This module is the TPU analogue:
instead of one ``table_op`` dispatch per FilterProject/HashJoin probe, the
driver's ``StreamingScan`` collapses a run of non-compacting
FilterProjects (optionally ending in an eligible open-addressing probe)
into a single ``pallas_call`` per morsel. Expressions evaluate on VMEM
blocks — each row block flows filter → project → probe without touching
HBM in between.

Only single-match probes fuse (semi/anti/unique-build inner/outer): their
output capacity equals the morsel capacity, so the fused kernel keeps the
block-per-block shape contract. Expansion probes keep their standalone
kernel. Off-TPU the kernel runs in interpret mode like every other kernel
in ``kernels/``.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..kernels import ops as kernel_ops
from ..kernels.hash_probe import (PROBE_COMPILER_PARAMS, probe_loop,
                                  table_planes)
from . import relational as rel
from .expr import in_kernel
from .table import DeviceTable

ROW_BLOCK = 1024

# one fused stage = one FilterProject's (filter_expr, projections)
Stage = Tuple[object, Optional[Tuple[Tuple[str, object], ...]]]


def apply_stages(table: DeviceTable, stages: Sequence[Stage]) -> DeviceTable:
    """Replay a run of FilterProject stages on ``table`` — the exact
    per-stage semantics of ``operators._filter_project`` without compact.
    Runs both under ``jax.eval_shape`` (to size the kernel outputs) and
    inside the kernel body on block-shaped tables."""
    for filter_expr, projections in stages:
        if filter_expr is not None:
            table = table.filter(filter_expr.evaluate(table))
        if projections is not None:
            cols, schema = {}, {}
            for out_name, e in projections:
                v = e.evaluate(table)
                if v.ndim == 0:   # literal: broadcast to rows
                    v = jnp.broadcast_to(v, (table.capacity,))
                cols[out_name] = v
                schema[out_name] = e.out_dtype(table.schema)
            table = DeviceTable(cols, table.validity, schema)
    return table


def stages_in_kernel(stages: Sequence[Stage]) -> bool:
    """Whether every filter and projection of ``stages`` can run inside a
    compiled kernel body (``expr.in_kernel``). LIKE patterns, EXTRACT and
    the like cannot: their runs stay unfused on the XLA path."""
    exprs = [f for f, _ in stages if f is not None]
    exprs += [e for _, proj in stages if proj is not None for _, e in proj]
    return all(in_kernel(e) for e in exprs)


def probe_key(table: DeviceTable, key_names, pack, empty_key: int):
    """Single-lane join key: ``relational.packed_key`` when ``pack`` is
    set, else ``relational.join_key`` (the raw int column, or a hash)."""
    cols = [table.columns[k] for k in key_names]
    if pack is not None:
        return rel.packed_key(cols, pack, empty_key=empty_key)
    return rel.join_key(cols)


def _block_spec(shape, row_block):
    if len(shape) == 1:
        return pl.BlockSpec((row_block,), lambda i: (i,))
    w = shape[1]
    return pl.BlockSpec((row_block, w), lambda i: (i, 0))


def fused_morsel_program(table: DeviceTable, stages: Sequence[Stage],
                         probe: Optional[dict] = None,
                         row_block: int = ROW_BLOCK,
                         interpret: Optional[bool] = None):
    """Run ``stages`` (and optionally a single-match hash probe) over
    ``table`` in one Pallas dispatch.

    ``probe``, when given, is a dict with keys ``tk``/``tv`` (the
    open-addressing table arrays, VMEM-resident across row blocks),
    ``probe_keys`` (post-stage column names), ``pack`` (composite-key
    windows or None), ``empty_key`` and ``max_probes``.

    Returns ``(out_table, found, bidx)``; ``found``/``bidx`` are None
    without a probe. ``found`` already masks invalid rows and probe keys
    equal to the empty sentinel (the PR-5 regression), so callers consume
    it directly.
    """
    if interpret is None:
        interpret = not kernel_ops.on_tpu()
    kernel_ops.mark_kernel("fused")

    cap = int(table.validity.shape[0])
    names = tuple(table.column_names)
    in_schema = dict(table.schema)
    row_block = min(row_block, cap)
    pad = (-cap) % row_block
    in_arrays = [table.columns[n] for n in names] + [table.validity]
    if pad:   # padded rows carry validity False, so stages/probe drop them
        in_arrays = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                     for a in in_arrays]
    n_pad = cap + pad

    out_struct = jax.eval_shape(lambda t: apply_stages(t, tuple(stages)),
                                table)
    out_names = tuple(out_struct.column_names)
    out_schema = dict(out_struct.schema)
    n_in = len(names)

    def kernel(*refs):
        col_refs, valid_ref = refs[:n_in], refs[n_in]
        pos = n_in + 1
        if probe is not None:
            planes_ref = refs[pos]
            pos += 1
        out_refs = refs[pos:]
        t = DeviceTable({n: r[...] for n, r in zip(names, col_refs)},
                        valid_ref[...], dict(in_schema))
        t = apply_stages(t, tuple(stages))
        for k, n in enumerate(out_names):
            out_refs[k][...] = t.columns[n]
        out_refs[len(out_names)][...] = t.validity
        if probe is not None:
            key = probe_key(t, probe["probe_keys"], probe["pack"],
                            probe["empty_key"])
            found, bidx = probe_loop(
                planes_ref, key,
                table_size=probe["tk"].shape[0],
                empty_key=probe["empty_key"],
                max_probes=probe["max_probes"])
            # a probe key equal to the empty sentinel reads an empty slot
            # as a hit; no such key occupies the table (seal_build falls
            # back otherwise), so masking it is exact
            found = found & t.validity & (key != probe["empty_key"])
            out_refs[len(out_names) + 1][...] = found
            out_refs[len(out_names) + 2][...] = bidx

    in_specs = [_block_spec(a.shape, row_block) for a in in_arrays]
    operands = list(in_arrays)
    if probe is not None:
        planes = table_planes(probe["tk"], probe["tv"])
        in_specs.append(pl.BlockSpec(planes.shape, lambda i: (0, 0)))
        operands.append(planes)

    out_shapes, out_specs = [], []
    for n in out_names:
        s = out_struct.columns[n]
        shape = (n_pad,) + s.shape[1:]
        out_shapes.append(jax.ShapeDtypeStruct(shape, s.dtype))
        out_specs.append(_block_spec(shape, row_block))
    # validity, then (found, bidx) when probing
    for dtype in ([jnp.bool_] if probe is None
                  else [jnp.bool_, jnp.bool_, jnp.int32]):
        out_shapes.append(jax.ShapeDtypeStruct((n_pad,), dtype))
        out_specs.append(pl.BlockSpec((row_block,), lambda i: (i,)))

    outs = pl.pallas_call(
        kernel, grid=(n_pad // row_block,),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shapes,
        compiler_params=None if probe is None else PROBE_COMPILER_PARAMS,
        interpret=interpret,
    )(*operands)
    outs = [o[:cap] for o in outs]

    out_table = DeviceTable(dict(zip(out_names, outs)),
                            outs[len(out_names)], out_schema)
    if probe is None:
        return out_table, None, None
    return out_table, outs[len(out_names) + 1], outs[len(out_names) + 2]


def fused_batch_program(table: DeviceTable, params: Tuple,
                        eval_fn, n_members: int,
                        row_block: int = ROW_BLOCK,
                        interpret: Optional[bool] = None):
    """Inter-query batched variant of ``fused_morsel_program``: evaluate
    ``n_members`` stacked queries' predicate lanes plus their shared
    projections over one morsel in ONE Pallas dispatch.

    ``eval_fn(table, params) -> (out_table, masks[n_members, capacity])``
    is the batched stage walk (``core.batch.apply_batched_stages`` bound
    to a program — injected as a callable so this module stays free of a
    circular import on ``core.batch``). ``params`` is a tuple of
    ``[n_members]`` scalar arrays, one per parameter slot; each lane's
    scalars are broadcast whole into every row block.
    """
    if interpret is None:
        interpret = not kernel_ops.on_tpu()
    kernel_ops.mark_kernel("fused_batch")

    cap = int(table.validity.shape[0])
    names = tuple(table.column_names)
    in_schema = dict(table.schema)
    row_block = min(row_block, cap)
    pad = (-cap) % row_block
    in_arrays = [table.columns[n] for n in names] + [table.validity]
    if pad:   # padded rows carry validity False → masked in every lane
        in_arrays = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                     for a in in_arrays]
    n_pad = cap + pad

    out_struct, mask_struct = jax.eval_shape(eval_fn, table, params)
    del mask_struct
    out_names = tuple(out_struct.column_names)
    out_schema = dict(out_struct.schema)
    n_in = len(names)
    n_par = len(params)

    def kernel(*refs):
        col_refs, valid_ref = refs[:n_in], refs[n_in]
        par_refs = refs[n_in + 1:n_in + 1 + n_par]
        out_refs = refs[n_in + 1 + n_par:]
        t = DeviceTable({n: r[...] for n, r in zip(names, col_refs)},
                        valid_ref[...], dict(in_schema))
        block, masks = eval_fn(t, tuple(r[...] for r in par_refs))
        for k, n in enumerate(out_names):
            out_refs[k][...] = block.columns[n]
        out_refs[len(out_names)][...] = block.validity
        # int32 lanes: Mosaic cannot relayout a stacked i1 mask
        out_refs[len(out_names) + 1][...] = masks.astype(jnp.int32)

    in_specs = [_block_spec(a.shape, row_block) for a in in_arrays]
    # every parameter lane rides whole into each grid step
    in_specs += [pl.BlockSpec((n_members,), lambda i: (0,))
                 for _ in params]
    operands = list(in_arrays) + list(params)

    out_shapes, out_specs = [], []
    for n in out_names:
        s = out_struct.columns[n]
        shape = (n_pad,) + s.shape[1:]
        out_shapes.append(jax.ShapeDtypeStruct(shape, s.dtype))
        out_specs.append(_block_spec(shape, row_block))
    out_shapes.append(jax.ShapeDtypeStruct((n_pad,), jnp.bool_))
    out_specs.append(pl.BlockSpec((row_block,), lambda i: (i,)))
    out_shapes.append(jax.ShapeDtypeStruct((n_members, n_pad), jnp.int32))
    out_specs.append(
        pl.BlockSpec((n_members, row_block), lambda i: (0, i)))

    outs = pl.pallas_call(
        kernel, grid=(n_pad // row_block,),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shapes,
        interpret=interpret,
    )(*operands)

    cols = [o[:cap] for o in outs[:len(out_names)]]
    validity = outs[len(out_names)][:cap]
    masks = outs[len(out_names) + 1][:, :cap] != 0
    out_table = DeviceTable(dict(zip(out_names, cols)), validity,
                            out_schema)
    return out_table, masks
