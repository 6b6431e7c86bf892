"""Inter-query batching: stack compatible small queries into one launch.

The paper's cost wins come from keeping the accelerator busy; a serving
workload of thousands of concurrent *small* point-lookup/filter/agg
queries is the regime where fixed per-query dispatch cost dwarfs compute
("Rethinking Analytical Processing in the GPU Era", PAPERS.md). This
module is the engine's answer — the same trick request-batching serving
systems play, applied to whole queries:

* ``extract_shape`` inspects an optimized single-table plan
  (scan → filter/project chain → optional aggregation → trailing
  stages) and, when eligible, lifts it into a shared ``BatchProgram``
  with the filter literals replaced by ``ParamRef`` placeholders. Two
  queries that differ only in those literals produce the *same interned
  program object*, which is what makes the stacked execution compile
  once and the scheduler's compatibility grouping a dict-key check.

* ``run_batch`` executes B member queries as ONE scan: every morsel is
  evaluated once for the shared projections plus a ``[B]``-indexed
  predicate lane per member (one fused Pallas dispatch per morsel under
  the 'pallas' backend, see ``fused.fused_batch_program``), aggregations
  stack into a single segmented-aggregation dispatch via
  ``group_id = query_id * max_groups + local_group`` (see
  ``kernels.segmented_agg.stacked_group_capacity``), and results are
  split per member on the way out.

Correctness contract: a member's batched result is identical to its solo
execution — row sets, row order (morsel order for row queries, ascending
group order for aggregates) and integer values bitwise, float sums up to
reduction order. The scheduler's property tests and the batched DuckDB
oracle sweep (``tests/test_batching.py``) enforce exactly this.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kernel_ops
from . import dtypes as dt
from . import fused
from . import plan as P
from . import relational as rel
from . import tracing
from .expr import (BinaryOp, BytesMatch, ColumnRef, Expr, IsIn, Literal,
                   PrefixCode, UnaryOp, Year)
from .operators import _OP_CACHES, _table_spec, lower_aggs, timed
from .streaming import ScanStats
from .table import DeviceTable, concat_tables

_AGG_KINDS = ("sum", "count", "min", "max", "avg")

_tls = threading.local()


class Ineligible(Exception):
    """Plan shape the batching layer cannot stack (internal signal)."""


# ---------------------------------------------------------------------------
# parameterized predicates
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)
class ParamRef(Expr):
    """Placeholder for a filter literal in a shared batch program.

    Evaluates to the current member's scalar from the thread-local
    parameter environment that the batched evaluator installs per query
    lane at trace time — so one traced program serves every member (and
    every future batch of the same shape) regardless of literal values.
    """

    KERNEL_SAFE = True

    idx: int
    dtype: dt.DType

    def evaluate(self, table):
        values = getattr(_tls, "param_values", None)
        if values is None:
            raise RuntimeError(
                "ParamRef evaluated outside a batched program body")
        return jnp.asarray(values[self.idx], dtype=self.dtype.jnp_dtype())

    def out_dtype(self, schema):
        return self.dtype

    def references(self):
        return set()

    def __repr__(self):
        return f"par({self.idx}:{self.dtype.name})"


def _parameterize(e: Expr, dtypes: list, values: list) -> Expr:
    """Copy a filter predicate with every ``Literal`` replaced by a
    ``ParamRef`` (walk order assigns indices, so structurally identical
    predicates parameterize identically). Literal dtypes join the program
    signature: ``x < 5`` (int32) and ``x < 5.5`` (float32) trace different
    programs and must not group."""
    if isinstance(e, Literal):
        idx = len(dtypes)
        dtypes.append(e.dtype)
        values.append(e.value)
        return ParamRef(idx, e.dtype)
    if isinstance(e, ColumnRef):
        return e
    if isinstance(e, BinaryOp):
        return BinaryOp(e.op, _parameterize(e.lhs, dtypes, values),
                        _parameterize(e.rhs, dtypes, values))
    if isinstance(e, UnaryOp):
        return UnaryOp(e.op, _parameterize(e.operand, dtypes, values))
    if isinstance(e, IsIn):
        # membership sets stay literal (they shape the traced program)
        return IsIn(_parameterize(e.operand, dtypes, values), e.values)
    if isinstance(e, BytesMatch):
        return BytesMatch(_parameterize(e.operand, dtypes, values),
                          e.parts, e.mode)
    if isinstance(e, Year):
        return Year(_parameterize(e.operand, dtypes, values))
    if isinstance(e, PrefixCode):
        return PrefixCode(_parameterize(e.operand, dtypes, values), e.n)
    raise Ineligible(f"unsupported expression {type(e).__name__}")


def _sig(e: Expr) -> str:
    """Canonical structural signature of an expression (literal *values*
    included except where a ``ParamRef`` already abstracted them)."""
    if isinstance(e, ParamRef):
        return f"par{e.idx}:{e.dtype.name}"
    if isinstance(e, ColumnRef):
        return f"col({e.name})"
    if isinstance(e, Literal):
        return f"lit({e.value!r}:{e.dtype.name})"
    if isinstance(e, BinaryOp):
        return f"({_sig(e.lhs)} {e.op} {_sig(e.rhs)})"
    if isinstance(e, UnaryOp):
        return f"{e.op}({_sig(e.operand)})"
    if isinstance(e, IsIn):
        return f"isin({_sig(e.operand)},{e.values!r})"
    if isinstance(e, BytesMatch):
        return f"match({_sig(e.operand)},{e.parts!r},{e.mode})"
    if isinstance(e, Year):
        return f"year({_sig(e.operand)})"
    if isinstance(e, PrefixCode):
        return f"pfx({_sig(e.operand)},{e.n})"
    raise Ineligible(f"unsupported expression {type(e).__name__}")


# ---------------------------------------------------------------------------
# shape extraction + program interning
# ---------------------------------------------------------------------------

class BatchProgram:
    """One interned stacked-execution template, shared by every query whose
    optimized plan has the same structural signature. Hashes by identity —
    the interning table guarantees signature-equal queries get the *same*
    object, so jit compile caches keyed on it hit across members, batches,
    and submissions."""

    def __init__(self, sig: str, table: str, columns, pre_stages,
                 param_dtypes, group_keys, user_specs, max_groups,
                 post_stages):
        self.sig = sig
        self.table = table
        self.columns = tuple(columns) if columns is not None else None
        # pre-aggregation stages in ``fused.Stage`` form; filter exprs are
        # parameterized templates, projections are shared verbatim
        self.pre_stages: Tuple[fused.Stage, ...] = tuple(pre_stages)
        self.param_dtypes: Tuple[dt.DType, ...] = tuple(param_dtypes)
        self.group_keys: Tuple[str, ...] = tuple(group_keys)
        self.user_specs = tuple(user_specs)      # as written (avg intact)
        self.lowered_specs = lower_aggs(self.user_specs)  # avg -> sum+cnt
        self.max_groups = int(max_groups)
        self.has_agg = bool(user_specs) or bool(group_keys)
        # stages above the aggregation (final SQL projection, HAVING);
        # applied per member on its [max_groups]-row result slice
        self.post_stages: Tuple[fused.Stage, ...] = tuple(post_stages)

    def __repr__(self):
        return f"BatchProgram({self.table}, {self.sig[:60]}...)"


@dataclasses.dataclass(eq=False)
class BatchShape:
    """One query's membership ticket: the interned program plus the
    member's literal values for the program's parameter slots."""

    program: BatchProgram
    params: Tuple


_PROGRAMS: Dict[str, BatchProgram] = {}
_PROGRAMS_LOCK = threading.Lock()


def clear_programs() -> None:
    """Drop the interned-program table (test isolation)."""
    with _PROGRAMS_LOCK:
        _PROGRAMS.clear()


def extract_shape(plan: P.PlanNode) -> Optional[BatchShape]:
    """Lift an optimized plan into a ``BatchShape``, or None if ineligible.

    Eligible plans are a linear single-table chain::

        TableScan[filter?] -> {Filter|Project}* -> Aggregation?
                           -> {Filter|Project}*   (post-agg stages)

    with at most one Aggregation (mode auto/single, kinds
    sum/count/min/max/avg) and expressions drawn from the core Expr
    algebra. Joins, sorts, limits, distinct, exchanges, and multi-phase
    aggregations stay on the solo path. Only *filter* literals below the
    aggregation are parameterized; projection and post-aggregation
    literals are shared computation and join the signature by value.
    """
    try:
        return _extract(plan)
    except Ineligible:
        return None


def _extract(plan: P.PlanNode) -> BatchShape:
    nodes: List[P.PlanNode] = []
    node = plan
    while not isinstance(node, P.TableScan):
        if isinstance(node, (P.Filter, P.Project, P.Aggregation)):
            nodes.append(node)
            node = node.child
        else:
            raise Ineligible(type(node).__name__)
    scan = node
    nodes.reverse()                       # scan-first order

    aggs = [n for n in nodes if isinstance(n, P.Aggregation)]
    if len(aggs) > 1:
        raise Ineligible("stacked aggregations")
    agg = aggs[0] if aggs else None
    if agg is not None:
        if agg.mode not in ("auto", "single"):
            raise Ineligible(f"aggregation mode {agg.mode}")
        for _out, kind, _col in agg.aggs:
            if kind not in _AGG_KINDS:
                raise Ineligible(f"aggregation kind {kind}")
    split = nodes.index(agg) if agg is not None else len(nodes)
    below = nodes[:split]
    above = nodes[split + 1:] if agg is not None else []

    param_dtypes: list = []
    param_values: list = []
    columns = tuple(scan.columns) if scan.columns is not None else None
    sig_parts = [f"scan({scan.table};{columns})"]
    pre: List[fused.Stage] = []
    # the pushed-down scan filter re-applies as the first parameterized
    # stage: the batched scan streams unfiltered (members' predicates
    # differ, so per-member zone-map skipping is off by construction)
    for filt in ([scan.filter] if scan.filter is not None else []):
        tmpl = _parameterize(filt, param_dtypes, param_values)
        pre.append((tmpl, None))
        sig_parts.append(f"f[{_sig(tmpl)}]")
    for n in below:
        if isinstance(n, P.Filter):
            tmpl = _parameterize(n.predicate, param_dtypes, param_values)
            pre.append((tmpl, None))
            sig_parts.append(f"f[{_sig(tmpl)}]")
        else:
            projs = tuple((name, e) for name, e in n.projections)
            pre.append((None, projs))
            sig_parts.append(
                "p[" + ",".join(f"{nm}={_sig(e)}" for nm, e in projs) + "]")

    group_keys: Tuple[str, ...] = ()
    user_specs: tuple = ()
    max_groups = 1
    if agg is not None:
        group_keys = tuple(agg.group_keys)
        user_specs = tuple((o, k, c) for o, k, c in agg.aggs)
        max_groups = int(agg.max_groups)
        sig_parts.append(
            f"agg[{group_keys};"
            + ",".join(f"{o}:{k}:{c}" for o, k, c in user_specs)
            + f";{max_groups}]")

    post: List[fused.Stage] = []
    for n in above:
        if isinstance(n, P.Filter):
            post.append((n.predicate, None))
            sig_parts.append(f"F[{_sig(n.predicate)}]")
        else:
            projs = tuple((name, e) for name, e in n.projections)
            post.append((None, projs))
            sig_parts.append(
                "P[" + ",".join(f"{nm}={_sig(e)}" for nm, e in projs) + "]")

    sig = "|".join(sig_parts)
    with _PROGRAMS_LOCK:
        program = _PROGRAMS.get(sig)
        if program is None:
            program = BatchProgram(sig, scan.table, columns, pre,
                                   param_dtypes, group_keys, user_specs,
                                   max_groups, post)
            _PROGRAMS[sig] = program
    return BatchShape(program, tuple(param_values))


# ---------------------------------------------------------------------------
# batched per-morsel evaluation
# ---------------------------------------------------------------------------

def apply_batched_stages(table: DeviceTable, stages: Sequence[fused.Stage],
                         params: Tuple, n_members: int):
    """Evaluate the shared stage chain once plus one predicate lane per
    member. Filters AND into per-member masks instead of narrowing the
    shared validity (``DeviceTable.filter`` only touches validity and
    projections are validity-blind, so the shared table stays correct for
    every member); projections run once for all members. Returns
    ``(projected table, bool masks [n_members, capacity])``. Runs both
    under ``jax.eval_shape`` and inside the fused Pallas kernel body."""
    masks = [table.validity] * n_members
    cur = table
    for filter_expr, projections in stages:
        if filter_expr is not None:
            for b in range(n_members):
                _tls.param_values = tuple(p[b] for p in params)
                try:
                    m = filter_expr.evaluate(cur)
                finally:
                    _tls.param_values = None
                masks[b] = masks[b] & m
        if projections is not None:
            cols, schema = {}, {}
            for out_name, e in projections:
                v = e.evaluate(cur)
                if v.ndim == 0:   # literal: broadcast to rows
                    v = jnp.broadcast_to(v, (cur.capacity,))
                cols[out_name] = v
                schema[out_name] = e.out_dtype(cur.schema)
            cur = DeviceTable(cols, cur.validity, schema)
    # stacked as int32: inside the Pallas kernel Mosaic cannot stack i1
    return cur, jnp.stack([m.astype(jnp.int32) for m in masks]) != 0


@functools.lru_cache(maxsize=None)
def _compiled_morsel(program: BatchProgram, n_members: int, spec, backend):
    """One jitted program per (interned program, padded member count,
    morsel spec, backend) — the compile-once property the whole layer is
    built for. Mirrors ``operators.table_op``'s record/replay dispatch
    accounting."""
    del spec

    def stacked_morsel(table, params):
        # morsels arrive worker-stacked [1, cap]: drop the worker axis
        # (batching is W=1 only; the scheduler enforces it at extraction)
        t = DeviceTable({n: a[0] for n, a in table.columns.items()},
                        table.validity[0], dict(table.schema))
        if backend == "pallas":
            if fused.stages_in_kernel(program.pre_stages):
                return fused.fused_batch_program(
                    t, params,
                    lambda tb, pr: apply_batched_stages(
                        tb, program.pre_stages, pr, n_members),
                    n_members)
            kernel_ops.mark_fallback("fused_batch")
        return apply_batched_stages(t, program.pre_stages, params, n_members)

    used: set = set()
    return jax.jit(tracing.program("batch", stacked_morsel)), used


_OP_CACHES.append(_compiled_morsel)


def batch_morsel_op(program: BatchProgram, n_members: int,
                    table: DeviceTable, params: Tuple):
    """Run one morsel through the batched stage program (jit + dispatch
    accounting)."""
    jitted, used = _compiled_morsel(program, n_members,
                                    _table_spec((table,) + tuple(params)),
                                    kernel_ops.current_backend())
    with kernel_ops.record_kernels(used):
        out = jitted(table, params)
    for kind in kernel_ops.kernel_snapshot(used):
        kernel_ops.count_dispatch(kind)
    return out


# ---------------------------------------------------------------------------
# stacked aggregation
# ---------------------------------------------------------------------------

def _stacked_segment_agg(vals, member_sorted, gids, max_groups: int,
                         n_members: int, kind: str):
    """All members' segmented aggregation in one dispatch.

    ``vals`` are the shared values in union-sorted row order,
    ``member_sorted`` the per-member validity in the same order, ``gids``
    the shared dense group ids (union-invalid rows carry ``max_groups``).
    Member ``b``'s group ``j`` maps to stacked segment
    ``b * max_groups + j``; rows dead for a member map to the
    ``n_members * max_groups`` sentinel — the only rows whose gid is the
    ``max_groups`` sentinel are union-invalid, hence dead for every
    member, so the per-member remap can never alias a neighbor lane's
    group 0. Unlike ``relational.segment_agg`` the segment ids are NOT
    sorted (a union-valid/member-dead row interrupts the run), so the jnp
    path drops ``indices_are_sorted``. Returns ``[n_members, max_groups]``
    (+ value trailing dims)."""
    total = n_members * max_groups
    n = member_sorted.shape[1]
    lane = max_groups * jnp.arange(n_members, dtype=gids.dtype)[:, None]
    seg = jnp.where(member_sorted, gids[None, :] + lane, total).reshape(-1)
    vflat = jnp.broadcast_to(
        vals[None], (n_members,) + vals.shape).reshape(
            (n_members * n,) + vals.shape[1:])
    mflat = member_sorted.reshape(-1)

    kernel_kind_ok = (vals.ndim == 1 and vals.dtype.itemsize <= 4
                      and (kind == "count"
                           or jnp.issubdtype(vals.dtype, jnp.floating)
                           or jnp.issubdtype(vals.dtype, jnp.integer)))
    pallas_ok = (kernel_ops.current_backend() == "pallas" and kernel_kind_ok
                 and total <= rel.PALLAS_AGG_GROUP_LIMIT)
    if pallas_ok:
        if kind == "count":
            out = kernel_ops.segmented_int_sum(
                seg, mflat.astype(jnp.int32), total)
        elif kind == "sum":
            acc = jnp.where(mflat, vflat, jnp.zeros((), vflat.dtype))
            if jnp.issubdtype(vflat.dtype, jnp.integer):
                out = kernel_ops.segmented_int_sum(
                    seg, acc, total).astype(vflat.dtype)
            else:
                out = kernel_ops.segmented_sum(
                    seg, acc.astype(jnp.float32), total).astype(vflat.dtype)
        else:
            ident = rel._extreme(vflat.dtype, +1 if kind == "min" else -1)
            out = kernel_ops.segmented_minmax(
                seg, jnp.where(mflat, vflat, ident), total, kind)
        return out.reshape((n_members, max_groups) + vals.shape[1:])

    if kernel_ops.current_backend() == "pallas" and kernel_kind_ok:
        # stacked capacity overflow: eligible shape, too many lanes
        kernel_ops.mark_fallback("agg")

    if kind in ("count", "sum") and vals.ndim == 1:
        # Unsorted segment-sum lowers to a serialized scatter on CPU XLA
        # (~25ms per spec at [16 x 30k]); the same reduction phrased as a
        # one-hot contraction is a dense [n_members, n] @ [n, max_groups]
        # matmul (~1ms), and XLA CSEs the shared one-hot across specs in
        # the same jitted body. Sentinel gids (== max_groups) match no
        # one-hot column, so union-invalid rows drop out exactly as they
        # did under the sentinel segment id. Integer inputs contract with
        # an integer accumulator (no float round-trip), so int sums and
        # counts stay exact; float inputs contract at HIGHEST precision
        # (a TPU's default matmul rounds them to bfloat16).
        onehot = (gids[:, None]
                  == jnp.arange(max_groups, dtype=gids.dtype)[None, :])
        if kind == "count":
            acc = member_sorted.astype(jnp.int32)
        else:
            acc = jnp.where(member_sorted, vals[None, :],
                            jnp.zeros((), vals.dtype))
        out = jax.lax.dot_general(
            acc, onehot.astype(acc.dtype), (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=acc.dtype)
        return out.reshape(n_members, max_groups)

    nseg = total + 1
    mask = mflat.reshape((-1,) + (1,) * (vflat.ndim - 1))
    if kind == "count":
        out = jax.ops.segment_sum(mflat.astype(jnp.int32), seg, nseg)
    elif kind == "sum":
        acc = jnp.where(mask, vflat, jnp.zeros((), vflat.dtype))
        out = jax.ops.segment_sum(acc, seg, nseg)
    elif kind == "min":
        out = jax.ops.segment_min(
            jnp.where(mask, vflat, rel._extreme(vflat.dtype, +1)), seg, nseg)
    elif kind == "max":
        out = jax.ops.segment_max(
            jnp.where(mask, vflat, rel._extreme(vflat.dtype, -1)), seg, nseg)
    else:
        raise ValueError(kind)
    return out[:total].reshape((n_members, max_groups) + vals.shape[1:])


def _stacked_aggregate(table: DeviceTable, masks, program: BatchProgram,
                       n_members: int):
    """All members' aggregation over the materialized batched output.

    Keyed: ONE ``group_rows`` over the union of member masks (members
    share key columns, so their groups are a subsequence of the union's
    ascending group order — matching solo output order), then every spec
    through the stacked segmented aggregation. Global: masked reductions
    per member lane. avg finalizes as sum/max(count,1) exactly like
    ``operators._finalize_avg``. Returns ``(key columns [max_groups],
    agg columns [n_members, max_groups], emission mask)``."""
    G = program.max_groups
    key_vals: Dict[str, jax.Array] = {}
    agg_cols: Dict[str, jax.Array] = {}
    if program.group_keys:
        key_cols = [table.columns[k] for k in program.group_keys]
        union = jnp.any(masks, axis=0)
        g = rel.group_rows(key_cols, union, G)
        member_sorted = jnp.take(masks, g.order, axis=1)
        for k in program.group_keys:
            key_vals[k] = jnp.take(table.columns[k], g.key_rows, axis=0)
        rows = _stacked_segment_agg(
            jnp.zeros((table.capacity,), jnp.int32), member_sorted, g.gids,
            G, n_members, "count")
        emit = g.group_valid[None, :] & (rows > 0)
        for out, kind, col_ in program.lowered_specs:
            vals = (jnp.zeros((table.capacity,), jnp.int32) if col_ is None
                    else table.columns[col_])
            vals_sorted = jnp.take(vals, g.order, axis=0)
            agg_cols[out] = _stacked_segment_agg(
                vals_sorted, member_sorted, g.gids, G, n_members, kind)
    else:
        # global aggregation: one row per member, masked jnp reductions
        # (identities match operators._aggregate's keyless branch)
        emit = jnp.ones((n_members, 1), dtype=bool)
        for out, kind, col_ in program.lowered_specs:
            vals = (jnp.zeros((table.capacity,), jnp.int32) if col_ is None
                    else table.columns[col_])
            axes = tuple(range(1, vals.ndim + 1))
            mask = masks.reshape(masks.shape + (1,) * (vals.ndim - 1))
            if kind == "count":
                agg_cols[out] = jnp.sum(masks.astype(jnp.int32), axis=1,
                                        keepdims=True)
            elif kind == "sum":
                agg_cols[out] = jnp.sum(
                    jnp.where(mask, vals[None], jnp.zeros((), vals.dtype)),
                    axis=axes).reshape(n_members, 1)
            elif kind == "min":
                agg_cols[out] = jnp.min(
                    jnp.where(mask, vals[None], rel._extreme(vals.dtype, +1)),
                    axis=axes).reshape(n_members, 1)
            elif kind == "max":
                agg_cols[out] = jnp.max(
                    jnp.where(mask, vals[None], rel._extreme(vals.dtype, -1)),
                    axis=axes).reshape(n_members, 1)
            else:
                raise ValueError(kind)
    # finalize avg lanes (same arithmetic as operators._finalize_avg)
    for out, kind, _col in program.user_specs:
        if kind == "avg":
            s = agg_cols.pop(f"{out}__sum")
            c = agg_cols.pop(f"{out}__cnt")
            agg_cols[out] = (s.astype(jnp.float32)
                             / jnp.maximum(c, 1).astype(jnp.float32))
    return key_vals, agg_cols, emit


@functools.lru_cache(maxsize=None)
def _compiled_agg(program: BatchProgram, n_members: int, spec, backend):
    del spec, backend   # one entry (and used-set) per specialization

    def stacked_agg(table, masks):
        return _stacked_aggregate(table, masks, program, n_members)

    used: set = set()
    return jax.jit(tracing.program("batch", stacked_agg)), used


_OP_CACHES.append(_compiled_agg)


@functools.lru_cache(maxsize=None)
def _compiled_post(program: BatchProgram, spec, backend):
    del spec, backend

    def post_stages(table):
        return fused.apply_stages(table, program.post_stages)

    used: set = set()
    return jax.jit(tracing.program("batch", post_stages)), used


_OP_CACHES.append(_compiled_post)


def _record_replay(cache_entry, *args):
    jitted, used = cache_entry
    with kernel_ops.record_kernels(used):
        out = jitted(*args)
    for kind in kernel_ops.kernel_snapshot(used):
        kernel_ops.count_dispatch(kind)
    return out


def _agg_schema(program: BatchProgram, in_schema) -> Dict[str, dt.DType]:
    """Host-side output schema of the stacked aggregation (same rules as
    ``operators._aggregate`` + avg finalize)."""
    schema: Dict[str, dt.DType] = {}
    for k in program.group_keys:
        schema[k] = in_schema[k]
    for out, kind, col_ in program.user_specs:
        if kind == "avg":
            schema[out] = dt.FLOAT32
        elif kind == "count":
            schema[out] = dt.INT32
        else:
            schema[out] = in_schema[col_]
    return schema


# ---------------------------------------------------------------------------
# batched execution loop (called from Driver.collect_batch)
# ---------------------------------------------------------------------------

_NAME = "BatchedPipeline"
_SPAN_ADD = f"repro.op.{_NAME}.add_input"
_SPAN_FINISH = f"repro.op.{_NAME}.finish"


def padded_members(n: int) -> int:
    """Member-lane count rounded up to a power of two: dummy lanes reuse
    member 0's parameters and have their outputs dropped, so one compiled
    program per (program, lane count) serves every batch size beneath it
    — the amortization the >=2x serving throughput win comes from."""
    return 1 << max(0, (n - 1).bit_length())


def run_batch(driver, shapes: Sequence[BatchShape],
              lanes: Optional[int] = None) -> List[Dict[str, np.ndarray]]:
    """Execute ``shapes`` (all sharing one interned program) as a single
    stacked scan; returns one host-numpy result dict per member, in
    order. Caller (``Driver.collect_batch``) provides the kernel scope.
    ``lanes`` pins the stacked lane count (must cover the group); the
    scheduler passes its per-program cap so one compiled executable
    serves every launch of the program.
    """
    program = shapes[0].program
    assert all(s.program is program for s in shapes), \
        "run_batch members must share one interned BatchProgram"
    n = len(shapes)
    lanes = padded_members(max(n, lanes or 0))
    params = tuple(
        jnp.asarray(np.asarray(
            [s.params[i] for s in shapes]
            + [shapes[0].params[i]] * (lanes - n),
            dtype=program.param_dtypes[i].np_dtype()))
        for i in range(len(program.param_dtypes)))

    ctx = driver.ctx
    src = ctx.catalog.get(program.table)
    stats = driver.scan_stats.setdefault(program.table, ScanStats())
    columns = list(program.columns) if program.columns is not None else None
    # the scan streams unfiltered: member predicates differ, so zone-map
    # skipping is off and each pushed-down filter re-applies as that
    # member's first parameterized stage (a superset scan is always safe)
    if ctx.streaming and hasattr(src, "stream"):
        kwargs = {}
        if "host_budget" in inspect.signature(src.stream).parameters:
            kwargs["host_budget"] = ctx.host_budget()
        morsels = src.stream(1, columns, ctx.batch_rows, filter_expr=None,
                             prefetch_depth=ctx.prefetch_depth,
                             sharding=ctx.worker_sharding(), stats=stats,
                             **kwargs)
    else:
        kwargs = {}
        if "stats" in inspect.signature(src.scan).parameters:
            kwargs["stats"] = stats
        morsels = src.scan(1, columns, ctx.batch_rows, filter_expr=None,
                           **kwargs)

    # one name for the stacked stage program's calls in op_host_seconds,
    # spanned like an operator's (repro.op.BatchedPipeline.*)
    secs = driver.op_host_seconds
    if program.has_agg:
        tables: List[DeviceTable] = []
        mask_parts: List[jax.Array] = []
        for morsel in morsels:
            out_table, masks = timed(_SPAN_ADD, _NAME, secs, batch_morsel_op,
                                     program, lanes, morsel, params)
            tables.append(out_table)
            mask_parts.append(masks)
        members = timed(_SPAN_FINISH, _NAME, secs, _finish_agg, program,
                        lanes, n, tables, mask_parts)
        with tracing.span("repro.driver.to_host"):
            return [m.to_numpy() for m in members]

    # row queries: per-morsel host scatter in morsel order — identical row
    # order to the solo path's flat[validity] collection
    acc: List[Dict[str, List[np.ndarray]]] = [
        {} for _ in range(n)]
    out_names: List[str] = []
    for morsel in morsels:
        out_table, masks = timed(_SPAN_ADD, _NAME, secs, batch_morsel_op,
                                 program, lanes, morsel, params)
        out_names = list(out_table.column_names)
        with tracing.span("repro.driver.to_host"):
            masks_np = np.asarray(masks)
            cols_np = {c: np.asarray(out_table.columns[c])
                       for c in out_names}
        for b in range(n):
            sel = masks_np[b]
            for c in out_names:
                acc[b].setdefault(c, []).append(cols_np[c][sel])
    return [
        {c: np.concatenate(parts[c]) for c in out_names}
        for parts in acc]


def _finish_agg(program: BatchProgram, lanes: int, n: int,
                tables: List[DeviceTable],
                mask_parts: List[jax.Array]) -> List[DeviceTable]:
    """The stacked aggregation over every morsel's output, split into one
    device table per member (post stages applied)."""
    # small-query contract: the projected scan output materializes on
    # device (like any blocking aggregation input) and aggregates once
    table = concat_tables(tables)
    masks = (mask_parts[0] if len(mask_parts) == 1
             else jnp.concatenate(mask_parts, axis=1))
    key_vals, agg_cols, emit = _record_replay(
        _compiled_agg(program, lanes, _table_spec((table, masks)),
                      kernel_ops.current_backend()),
        table, masks)
    schema = _agg_schema(program, table.schema)
    members: List[DeviceTable] = []
    for b in range(n):
        cols = {k: key_vals[k] for k in program.group_keys}
        for out, _kind, _col in program.user_specs:
            cols[out] = agg_cols[out][b]
        member = DeviceTable(cols, emit[b], dict(schema))
        if program.post_stages:
            member = _record_replay(
                _compiled_post(program, _table_spec((member,)),
                               kernel_ops.current_backend()),
                member)
        members.append(member)
    return members
