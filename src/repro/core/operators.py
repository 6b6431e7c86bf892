"""Physical operators: device-resident analogues of the Velox operators the
paper replaces with cuDF versions (TableScan, FilterProject, HashJoin,
HashAggregation, OrderBy, Limit, ...).

Operators follow Velox's streaming contract:

    op.open()                       # acquire state
    out = op.add_input(batch)       # 0..n output batches, never blocks
    out = op.finish()               # flush blocking state at end of input

Per-batch device work is jitted; the operator object holds host-side state
between batches (the "driver thread" of Velox). Blocking operators (OrderBy,
final aggregation, join build) accumulate DeviceTables in *device* memory --
the paper's working-set-stays-on-device discipline.

Tables come in two layouts: local ``[cap, ...]`` and worker-stacked
``[W, cap, ...]`` (distributed execution; axis 0 = worker, sharded over the
mesh). The ``table_op`` decorator dispatches: stacked tables run the same
program per worker via vmap, so one operator implementation serves both the
single-GPU and the distributed paths (one Velox worker per GPU in the paper;
one vmap lane per mesh worker here).
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import math
import threading
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kernel_ops
from . import dtypes as dt
from . import fused
from . import relational as rel
from . import tracing
from .expr import Expr
from .table import DeviceTable, concat_tables


# every table_op's compile cache, so long-lived processes (full-suite
# test sweeps, benchmark harnesses) can release accumulated executables
_OP_CACHES: list = []


def clear_compile_caches() -> None:
    """Drop every ``table_op`` compiled-program cache.

    The caches are unbounded by design (steady-state serving re-uses a
    small working set), but a process that runs many differently-shaped
    workloads back to back — e.g. a full TPC-H sweep at several scale
    factors — accumulates thousands of live XLA executables. Pair with
    ``jax.clear_caches()`` to actually release them."""
    for cache in _OP_CACHES:
        cache.cache_clear()


def table_op(n_tables: int = 1, *, family: str):
    """Wrap fn(*tables, *statics) with jit + optional worker-axis vmap.

    ``family`` (one of ``tracing.FAMILIES``) names the program: its XLA
    module reads ``jit_<family>__<fn>`` in a profiler trace.

    The compile cache additionally keys on the active kernel backend
    (``kernels.ops.current_backend``) and on the input tables' leaf
    shapes/dtypes: the traced program embeds the backend's dispatch
    decisions (Pallas kernels vs jnp, which can differ per dtype), so
    'jnp' and 'pallas' sessions never share a compilation and every cache
    entry corresponds to exactly one trace. Each entry remembers which
    kernels its trace used and replays them through
    ``kernels.ops.count_dispatch`` on every call, which is how the driver
    reports per-query ``kernel_dispatch`` counts.
    """

    def deco(fn):
        @functools.lru_cache(maxsize=None)
        def compiled(statics, stacked, spec, backend):
            del spec  # one cache entry (and used-set) per specialization

            def body(*tabs):
                return fn(*tabs, *statics)

            body.__name__ = fn.__name__
            body = tracing.program(family, body)
            used: set = set()
            return jax.jit(jax.vmap(body) if stacked else body), used

        _OP_CACHES.append(compiled)

        def program_for(*args):
            """The jitted program (and its kernel set) a call with
            ``args`` runs; it takes the tables alone."""
            tables, statics = args[:n_tables], args[n_tables:]
            return compiled(tuple(statics), _is_stacked(tables[0]),
                            _table_spec(tables), kernel_ops.current_backend())

        @functools.wraps(fn)
        def wrapper(*args):
            jitted, used = program_for(*args)
            with kernel_ops.record_kernels(used):
                out = jitted(*args[:n_tables])
            for kind in kernel_ops.kernel_snapshot(used):
                kernel_ops.count_dispatch(kind)
            return out

        wrapper.raw = fn
        wrapper.program = program_for
        return wrapper

    return deco


def _table_spec(tables) -> tuple:
    """Hashable (structure, leaf shape/dtype) description of the inputs —
    the same things jax.jit specializes a trace on, so each ``compiled``
    entry's recorded kernel set describes exactly the program that runs."""
    leaves, treedef = jax.tree.flatten(tables)
    return treedef, tuple((l.shape, str(l.dtype)) for l in leaves)


def _is_stacked(obj) -> bool:
    if isinstance(obj, DeviceTable):
        return obj.validity.ndim == 2
    # pytree containing tables (join build state)
    leaves = jax.tree.leaves(obj)
    return any(isinstance(t, DeviceTable) and t.validity.ndim == 2
               for t in jax.tree.leaves(obj, is_leaf=lambda x: isinstance(x, DeviceTable)))


# ---------------------------------------------------------------------------

class Operator:
    """Velox streaming-operator contract (see module docstring): ``open``,
    then ``add_input`` per batch, then ``finish`` to flush blocking state."""

    name = "operator"
    is_device = True     # has a "cuDF version" (device implementation)

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        # span names built once per class: calls format nothing
        cls.spans = {phase: f"repro.op.{cls.name}.{phase}"
                     for phase in ("add_input", "finish", "seal_build")}

    def open(self) -> None:
        """Acquire per-query state; called once before any input."""
        pass

    def add_input(self, batch: DeviceTable) -> List[DeviceTable]:
        """Consume one batch, return 0..n output batches (never blocks)."""
        raise NotImplementedError

    def finish(self) -> List[DeviceTable]:
        """Flush accumulated state at end of input (blocking operators)."""
        return []


def timed(span: str, name: str, seconds: dict, fn, *args):
    """``fn(*args)`` under the span ``span``; its host seconds are added to
    ``seconds[name]``."""
    t0 = time.perf_counter()
    with tracing.span(span):
        out = fn(*args)
    seconds[name] = seconds.get(name, 0.0) + time.perf_counter() - t0
    return out


def call_op(op: Operator, phase: str, seconds: dict, *args):
    """``op.<phase>(*args)`` under the span ``repro.op.<name>.<phase>``,
    timed into ``seconds[op.name]``: host self time of the operator's own
    call, i.e. dispatch, not device time."""
    return timed(op.spans[phase], op.name, seconds, getattr(op, phase),
                 *args)


# ---------------------------------------------------------------------------
# Pipeline: compose operators into one stage
# ---------------------------------------------------------------------------

class Pipeline(Operator):
    """Compose a list of operators into a single streaming stage.

    Used by the driver's ``StreamingScan`` to run the scan-fused chain
    (pushed-down filter, projections, ...) per morsel as each chunk arrives
    from the prefetch queue. ``finish`` flushes each operator in order,
    threading its flushed output through the operators downstream of it.
    """

    name = "Pipeline"

    def __init__(self, ops_: Sequence[Operator] = (),
                 seconds: Optional[dict] = None):
        self.ops: List[Operator] = list(ops_)
        # per operator name, host seconds of its calls (``call_op``)
        self.seconds = seconds if seconds is not None else {}

    def open(self):
        for op in self.ops:
            op.open()

    def add_input(self, batch):
        outs = [batch]
        for op in self.ops:
            outs = [o for b in outs
                    for o in call_op(op, "add_input", self.seconds, b)]
        return outs

    def finish(self):
        carry: List[DeviceTable] = []
        for op in self.ops:
            fed: List[DeviceTable] = []
            for b in carry:
                fed.extend(call_op(op, "add_input", self.seconds, b))
            fed.extend(call_op(op, "finish", self.seconds))
            carry = fed
        return carry


# ---------------------------------------------------------------------------
# FilterProject
# ---------------------------------------------------------------------------

@table_op(family="scan")
def _filter_project(table: DeviceTable, filter_expr, projections, compact: bool):
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
    if compact:
        table = table.compact()
    return table


class FilterProject(Operator):
    """Fused filter + projection: one traced program = cuDF's AST path."""

    name = "FilterProject"

    def __init__(self, filter_expr: Optional[Expr] = None,
                 projections: Optional[Sequence[Tuple[str, Expr]]] = None,
                 compact: bool = False):
        self.filter_expr = filter_expr
        self.projections = tuple(projections) if projections is not None else None
        self.compact = compact

    def add_input(self, batch):
        return [_filter_project(batch, self.filter_expr, self.projections,
                                self.compact)]


# ---------------------------------------------------------------------------
# HashAggregation (partial / final / single) -- paper §3.2
# ---------------------------------------------------------------------------

AggSpec = Tuple[str, str, Optional[str]]   # (out_name, kind, in_column)
_MERGE_KIND = {"sum": "sum", "count": "sum", "min": "min", "max": "max",
               "first": "first"}


def lower_aggs(specs: Sequence[AggSpec]) -> Tuple[AggSpec, ...]:
    """avg -> sum+count for partial phases."""
    lowered: List[AggSpec] = []
    for out, kind, col_ in specs:
        if kind == "avg":
            lowered.append((f"{out}__sum", "sum", col_))
            lowered.append((f"{out}__cnt", "count", col_))
        else:
            lowered.append((out, kind, col_))
    return tuple(lowered)


def merge_specs(specs: Sequence[AggSpec]) -> Tuple[AggSpec, ...]:
    """Specs that merge partial outputs (count -> sum of counts, ...)."""
    return tuple((out, _MERGE_KIND[kind], out) for out, kind, _ in specs)


@table_op(family="agg")
def _aggregate(table: DeviceTable, group_keys, specs, max_groups: int):
    key_cols = [table.columns[k] for k in group_keys]
    cols, schema = {}, {}
    if key_cols:
        g = rel.group_rows(key_cols, table.validity, max_groups)
        for k in group_keys:
            cols[k] = jnp.take(table.columns[k], g.key_rows, axis=0)
            schema[k] = table.schema[k]
        validity = g.group_valid
    else:
        validity = jnp.ones((1,), dtype=bool)
    for out, kind, col_ in specs:
        vals = (jnp.zeros(table.capacity, dtype=jnp.int32) if col_ is None
                else table.columns[col_])
        if kind == "first":
            # carry column: representative value per group (for functionally
            # dependent columns, e.g. group by custkey carrying c_name)
            if key_cols:
                cols[out] = jnp.take(vals, g.key_rows, axis=0)
            else:
                cols[out] = jnp.take(vals, jnp.argmax(table.validity), axis=0)[None]
            schema[out] = table.schema[col_]
            continue
        if key_cols:
            cols[out] = rel.segment_agg(vals, g.gids, g.order, table.validity,
                                        max_groups, kind)
        else:
            v = table.validity
            if kind == "count":
                cols[out] = jnp.sum(v.astype(jnp.int32))[None]
            elif kind == "sum":
                cols[out] = jnp.sum(jnp.where(v, vals, jnp.zeros((), vals.dtype)))[None]
            elif kind == "min":
                cols[out] = jnp.min(jnp.where(v, vals, rel._extreme(vals.dtype, 1)))[None]
            elif kind == "max":
                cols[out] = jnp.max(jnp.where(v, vals, rel._extreme(vals.dtype, -1)))[None]
            else:
                raise ValueError(kind)
        schema[out] = dt.INT32 if kind == "count" else table.schema[col_]
    return DeviceTable(cols, validity, schema)


@table_op(family="agg")
def _finalize_avg(table: DeviceTable, user_specs):
    cols = dict(table.columns)
    schema = dict(table.schema)
    for out, kind, _ in user_specs:
        if kind == "avg":
            s = cols.pop(f"{out}__sum")
            c = cols.pop(f"{out}__cnt")
            cols[out] = s.astype(jnp.float32) / jnp.maximum(c, 1).astype(jnp.float32)
            schema.pop(f"{out}__sum"), schema.pop(f"{out}__cnt")
            schema[out] = dt.FLOAT32
    return DeviceTable(cols, table.validity, schema)


# per-query operator counters (``executor_stats()["agg"]``, ``["join"]``):
# the driver opens a scope around each query, as it does for kernel
# dispatches, and the operators report into it from host-side state
AGG_COUNTERS = ("partials", "merges", "merge_rows", "compacted_merges")
JOIN_COUNTERS = ("dense_probes", "sorted_probes", "index_slots",
                 "hashed_builds")
_stats_tls = threading.local()


@contextlib.contextmanager
def _collect(group: str, counts: Dict[str, int]) -> Iterator[None]:
    stack = getattr(_stats_tls, group, None)
    if stack is None:
        stack = []
        setattr(_stats_tls, group, stack)
    stack.append(counts)
    try:
        yield
    finally:
        stack.pop()


def collect_agg_stats(counts: Dict[str, int]):
    """Add HashAggregation's counters (``AGG_COUNTERS``) into ``counts``
    for the duration of the scope, on this thread."""
    return _collect("agg", counts)


def collect_join_stats(counts: Dict[str, int]):
    """Add HashJoin's counters (``JOIN_COUNTERS``) into ``counts`` for
    the duration of the scope, on this thread: jnp-path probe calls by
    lookup (index or binary search), the largest index built, and the
    sealed builds whose key is hashed."""
    return _collect("join", counts)


def _count(group: str, name: str, n: int = 1, combine=int.__add__) -> None:
    for counts in getattr(_stats_tls, group, ()):
        counts[name] = combine(counts.get(name, 0), n)


def _rows(table: DeviceTable) -> int:
    """Static row slots of a local or worker-stacked table (per worker)."""
    return int(table.validity.shape[-1])


@jax.jit
@functools.partial(tracing.program, "agg")
def _valid_counts(validities):
    """Valid rows of each table (``[n]``, or ``[n, W]`` for stacked)."""
    return jnp.stack([jnp.sum(v.astype(jnp.int32), axis=-1)
                      for v in validities])


@table_op(family="agg")
def _prefix(table: DeviceTable, rows: int):
    """The first ``rows`` slots of ``table``."""
    return DeviceTable({n: a[:rows] for n, a in table.columns.items()},
                       table.validity[:rows], table.schema)


class HashAggregation(Operator):
    """Concatenation-based streaming aggregation (paper §3.2).

    cuDF has no streaming groupby, so the paper aggregates each batch,
    concatenates the partial results and re-aggregates them once their
    size reaches a threshold. Reproduced so: each batch is aggregated
    (sort-based on TPU) into ``min(max_groups, batch rows)`` slots, since
    a batch holds no more groups than rows, and held. The held tables, the
    accumulator and the pending partials, are concatenated and
    re-aggregated only once their slots reach ``2 * max_groups``, the size
    of one accumulator-plus-partial merge; ``finish`` merges what is left
    (a lone table needs no merge). Where ``emit_rows`` (partial mode) or a
    spill flush point is set, the threshold drops to the smallest of them:
    a merge has no more groups than the slots it reads, so every emission
    and flush happens at the batch it would if each batch were merged.

    A table's static slots bound its groups, but ``rel.group_rows`` packs
    the groups it holds into a prefix. ``finish`` reads the valid counts
    of the held tables (one host read) and merges only each table's first
    ``B`` slots, ``B`` the power of two at or above the largest count:
    exact, and one program shape however the counts fall below ``B``.

    Counted per query (``collect_agg_stats``): ``partials`` (batches
    aggregated), ``merges`` (re-aggregations), ``merge_rows`` (slots fed to
    merges), ``compacted_merges`` (merges of occupied prefixes).

    mode: 'partial'  emits partial columns (avg -> sum+cnt) for an exchange
          'final'    merges partial columns after an exchange
          'single'   complete aggregation in one operator
    """

    name = "HashAggregation"

    _spill_seq = itertools.count()

    def __init__(self, group_keys: Sequence[str], aggs: Sequence[AggSpec],
                 mode: str = "single", max_groups: int = 4096,
                 emit_rows: Optional[int] = None, spill=None,
                 spill_flush_groups: Optional[int] = None):
        assert mode in ("partial", "final", "single")
        self.group_keys = tuple(group_keys)
        self.user_specs = tuple(aggs)
        self.mode = mode
        lowered = lower_aggs(self.user_specs)
        self.specs = merge_specs(lowered) if mode == "final" else lowered
        self.max_groups = max_groups
        self.emit_rows = emit_rows
        # spill-aware mode (core.spill): with a SpillManager and a flush
        # threshold, the accumulator is flushed to the host tier whenever
        # its occupied groups reach the threshold (max_groups pressure);
        # finish() merges the flushed runs back in a final pass
        self.spill = spill
        self.spill_flush_groups = spill_flush_groups
        self._merge_at = 2 * max_groups
        if self._flushes():
            self._merge_at = min(self._merge_at, spill_flush_groups)
        if self._emits():
            self._merge_at = min(self._merge_at, emit_rows)
        self._skey = f"agg{next(self._spill_seq)}"
        self._flushed: List[object] = []
        # the accumulator (first, once a merge has run) and the pending
        # partials
        self._held: List[DeviceTable] = []

    def open(self):
        self._held = []
        self._flushed = []

    def _flushes(self) -> bool:
        return self.spill is not None and self.spill_flush_groups is not None

    def _emits(self) -> bool:
        return self.emit_rows is not None and self.mode == "partial"

    def _merge(self, tables: List[DeviceTable]) -> DeviceTable:
        """The groups of ``tables`` in one table: concatenated and
        re-aggregated, unless there is only one."""
        if len(tables) == 1:
            return tables[0]
        merged = concat_tables(tables)
        rows = _rows(merged)
        _count("agg", "merges")
        _count("agg", "merge_rows", rows)
        return _aggregate(merged, self.group_keys, merge_specs(self.specs),
                          min(self.max_groups, rows))

    @staticmethod
    def _occupied(tables: List[DeviceTable]) -> List[DeviceTable]:
        """Each of ``tables`` cut to its first ``B`` slots, where ``B`` is
        the power of two at or above the most valid rows any table (or
        worker) holds. Exact: an aggregated table's groups fill a prefix."""
        counts = np.asarray(_valid_counts([t.validity for t in tables]))
        b = _pow2(counts.max())
        _count("agg", "compacted_merges")
        return [_prefix(t, b) if _rows(t) > b else t for t in tables]

    def add_input(self, batch):
        self._held.append(_aggregate(batch, self.group_keys, self.specs,
                                     min(self.max_groups, _rows(batch))))
        _count("agg", "partials")
        if sum(_rows(t) for t in self._held) < self._merge_at:
            return []
        acc = self._merge(self._held)
        self._held = []
        if (self._flushes()
                and int(acc.num_valid()) >= self.spill_flush_groups):
            key = (self._skey, len(self._flushed))
            self.spill.spill_table(key, acc)
            self._flushed.append(key)
            return []
        if self._emits() and int(acc.num_valid()) >= self.emit_rows:
            return [acc]
        self._held = [acc]
        return []

    def finish(self):
        held = self._held
        if len(held) > 1:
            held = self._occupied(held)
        acc = self._merge(held) if held else None
        self._held = []
        # restore the flushed runs one at a time and merge each into the
        # accumulator (device working set stays at two max_groups tables
        # regardless of how many runs spilled)
        for key in self._flushed:
            run = self.spill.restore(key)
            acc = run if acc is None else self._merge([acc, run])
        self._flushed = []
        if acc is None:
            return []
        if self.mode in ("final", "single"):
            acc = _finalize_avg(acc, self.user_specs)
        return [acc]


class Distinct(Operator):
    """Row dedup on key columns (count(distinct ...) rewrites)."""

    name = "Distinct"

    def __init__(self, keys: Sequence[str], max_groups: int = 4096):
        self.keys = tuple(keys)
        self.max_groups = max_groups
        self.agg = HashAggregation(keys, [], "single", max_groups)

    def open(self):
        self.agg.open()

    def add_input(self, batch):
        return self.agg.add_input(batch.select(list(self.keys)))

    def finish(self):
        return self.agg.finish()


# ---------------------------------------------------------------------------
# HashJoin
# ---------------------------------------------------------------------------

# pallas probe eligibility: the open-addressing table must stay
# VMEM-resident (2^18 slots x 8 B = 2 MiB of a ~16 MiB core, leaving room
# for the probe blocks); larger builds fall back to the sorted-key path
MAX_HASH_TABLE_SLOTS = 1 << 18
EMPTY_KEY = -1


# the sorted build's direct-address index may take up to this many slots
# per build row slot (64 B of int32 counts per row): past it the probe
# keeps the binary search
MAX_INDEX_SLOTS_PER_ROW = 16

# int-like key columns: raw when alone, packable when composite
INT_KEY_DTYPES = ("int32", "date32", "dict32")


def _join_probe_key(table: DeviceTable, key_names, pack):
    """The join key as ``HashJoin.seal_build`` encoded it, for build and
    probe on both backends: one int-like column raw, a composite packed
    into its ``pack`` windows (tuples outside them give ``EMPTY_KEY``),
    anything else hashed by ``relational.join_key``."""
    return fused.probe_key(table, key_names, pack, EMPTY_KEY)


@table_op(family="join")
def _key_ranges(build: DeviceTable, build_keys):
    """Per key column, (min, max) over the valid build rows (``[n, 2]``);
    (INT32_MAX, INT32_MIN) where no row is valid."""
    cols = jnp.stack([build.columns[k].astype(jnp.int32) for k in build_keys])
    lo = jnp.min(jnp.where(build.validity, cols, rel.INT32_MAX), axis=-1)
    hi = jnp.max(jnp.where(build.validity, cols, -rel.INT32_MAX - 1), axis=-1)
    return jnp.stack([lo, hi], axis=-1)


def _read_ranges(build: DeviceTable, build_keys) -> List[Tuple[int, int]]:
    """``_key_ranges`` read to the host in one transfer, over all workers
    of a stacked build (so every worker shares one encoding)."""
    rng = np.asarray(_key_ranges(build, build_keys)).reshape(
        -1, len(build_keys), 2)
    return list(zip(rng[..., 0].min(0).tolist(), rng[..., 1].max(0).tolist()))


def _pack_windows(ranges):
    """``((lo, span), ...)`` per key column for ``relational.packed_key``,
    or None when the spans' product passes the int32 key lane. A build
    with no valid row gets the window (0, 1) per column."""
    spans = [max(hi - lo, 0) + 1 for lo, hi in ranges]
    if math.prod(spans) > rel.INT32_MAX:
        return None
    return tuple((lo if hi >= lo else 0, s) for (lo, hi), s in zip(ranges, spans))


def _index_slots(lo: int, hi: int, rows: int) -> int:
    """Slots S of the direct-address index over the valid keys' range
    ``[lo, hi]``: the power of two at or above ``hi - lo + 2`` while it is
    within ``MAX_INDEX_SLOTS_PER_ROW`` times the per-worker row slots, else
    0 (binary search), as for an empty build or a valid INT32_MAX key (its
    sorted run would take in the padded invalid rows, which S omits)."""
    if hi < lo or hi == rel.INT32_MAX:
        return 0
    slots = _pow2(hi - lo + 2)
    return slots if slots <= MAX_INDEX_SLOTS_PER_ROW * _pow2(rows) else 0


@table_op(family="join")
def _build_join_table(build: DeviceTable, build_keys, pack=None,
                      slots: int = 0, runs: bool = False):
    """The sorted build (``relational.join_build``); with ``runs``, also
    its longest run of equal keys among valid rows."""
    key = _join_probe_key(build, build_keys, pack)
    bt = rel.join_build(key, build.validity, slots)
    return (bt, rel.longest_run(bt)) if runs else bt


@table_op(family="join")
def _build_hash_table(build: DeviceTable, build_keys, table_size: int, pack):
    key = _join_probe_key(build, build_keys, pack)
    rows = jnp.arange(key.shape[0], dtype=jnp.int32)
    return kernel_ops.build_table(key, rows, table_size,
                                  empty_key=EMPTY_KEY, valid=build.validity)


def _probe_bound(table_keys: np.ndarray) -> int:
    """Sound ``max_probes`` for a built table: the longest circular run of
    occupied slots + 1 (a linear probe terminates at the first empty slot),
    rounded up to a power of two so the static argument stays stable
    across similarly loaded tables."""
    occ = (np.asarray(table_keys) != EMPTY_KEY).reshape(
        -1, table_keys.shape[-1])
    t = occ.shape[-1]
    longest = 0
    for row in occ:
        if row.all():
            longest = max(longest, t)
            continue
        if not row.any():
            continue
        # rotate a free slot to the end so runs never wrap the boundary
        row = np.roll(row, t - 1 - int(np.where(~row)[0][-1]))
        edges = np.diff(np.concatenate(([0], row.astype(np.int8), [0])))
        starts, ends = np.where(edges == 1)[0], np.where(edges == -1)[0]
        longest = max(longest, int((ends - starts).max()))
    return min(int(2 ** np.ceil(np.log2(max(longest + 1, 2)))), t)


def _attach_build_payload(probe: DeviceTable, build: DeviceTable, found,
                          bidx, build_payload, join_type: str) -> DeviceTable:
    """Single-match output assembly (output row i is probe row i), shared
    by the standalone ``hash_probe`` path and the fused morsel kernel:
    semi/anti filter on membership, inner/left_outer gather the build
    payload by matched row (left_outer zero-fills unmatched rows and
    carries ``__matched``, matching the jnp path)."""
    if join_type == "left_semi":
        return probe.filter(found)
    if join_type == "left_anti":
        return probe.filter(probe.validity & ~found)

    safe = jnp.where(found, bidx, 0)
    cols = dict(probe.columns)
    schema = dict(probe.schema)
    for n in build_payload:
        v = jnp.take(build.columns[n], safe, axis=0)
        if join_type == "left_outer":
            mask = found.reshape(found.shape + (1,) * (v.ndim - 1))
            v = jnp.where(mask, v, jnp.zeros((), v.dtype))
        cols[n] = v
        schema[n] = build.schema[n]
    if join_type == "left_outer":
        cols["__matched"] = found
        schema["__matched"] = dt.BOOL
        return DeviceTable(cols, probe.validity, schema)
    return DeviceTable(cols, found, schema)


@table_op(n_tables=2, family="join")
def _probe_join_pallas(probe: DeviceTable, hash_state, probe_keys,
                       build_payload, join_type: str, max_probes: int, pack):
    """Open-addressing probe (Pallas ``hash_probe``): one table lookup per
    probe row. Reached for exact int-like keys (single, or composite via
    the injective ``pack``) against a build side the planner proved unique
    (``max_matches == 1``) or for semi/anti joins, where membership alone
    decides; output row i is probe row i."""
    build, tk, tv = hash_state
    key = _join_probe_key(probe, probe_keys, pack)
    found, bidx = kernel_ops.hash_probe(tk, tv, key, empty_key=EMPTY_KEY,
                                        max_probes=max_probes)
    # a probe key equal to the empty sentinel reads an empty slot as a hit;
    # no such key occupies the table (seal_build falls back if a valid
    # build key is EMPTY_KEY, and packed keys are nonnegative), so masking
    # it is exact
    found = found & probe.validity & (key != EMPTY_KEY)
    return _attach_build_payload(probe, build, found, bidx, build_payload,
                                 join_type)


@table_op(n_tables=2, family="join")
def _probe_join_pallas_multi(probe: DeviceTable, hash_state, probe_keys,
                             build_payload, join_type: str, max_probes: int,
                             max_matches: int, pack):
    """Expansion probe (Pallas ``hash_probe_multi``): probe row i owns
    output rows [i*m, (i+1)*m), the same static-capacity layout as the jnp
    ``relational.join_probe`` path, so downstream compaction and the
    oracle agree bit-for-bit. Matches surface in build-row order (the
    cooperative build places duplicate keys along the run in ascending row
    index), mirroring the sorted-key oracle's emission order."""
    build, tk, tv = hash_state
    key = _join_probe_key(probe, probe_keys, pack)
    count, slots = kernel_ops.hash_probe_multi(
        tk, tv, key, max_matches, empty_key=EMPTY_KEY, max_probes=max_probes)
    # sentinel mask, as in the single-match probe: an empty slot compares
    # equal to a sentinel probe key and would report one bogus match
    live = probe.validity & (key != EMPTY_KEY)
    count = jnp.where(live, count, 0)
    p = key.shape[0]
    j = jnp.arange(p * max_matches, dtype=jnp.int32)
    probe_idx = j // max_matches
    valid = (j % max_matches) < jnp.take(count, probe_idx)
    build_idx = slots.reshape(-1)        # garbage past count; masked by valid
    return _expand_join_output(probe, build, probe_idx, build_idx, valid,
                               build_payload, join_type)


def _expand_join_output(probe: DeviceTable, build_table: DeviceTable,
                        probe_idx, build_idx, valid, build_payload,
                        join_type: str) -> DeviceTable:
    """Expansion-layout output assembly shared by the jnp ``_probe_join``
    tail and the Pallas expansion probe: scatter-max membership for
    semi/anti, gather both sides for inner, append unmatched probe rows
    for left_outer."""
    if join_type in ("left_semi", "left_anti"):
        hit = jnp.zeros(probe.capacity, dtype=jnp.int32)
        hit = hit.at[probe_idx].max(valid.astype(jnp.int32))
        mask = probe.validity & (hit > 0)
        if join_type == "left_anti":
            mask = probe.validity & ~mask
        return probe.filter(mask)

    cols, schema = {}, {}
    for n in probe.column_names:
        cols[n] = jnp.take(probe.columns[n], probe_idx, axis=0)
        schema[n] = probe.schema[n]
    for n in build_payload:
        cols[n] = jnp.take(build_table.columns[n], build_idx, axis=0)
        schema[n] = build_table.schema[n]
    out_valid = valid

    if join_type == "left_outer":
        # append unmatched probe rows with zeroed build payload + match flag
        hit = jnp.zeros(probe.capacity, dtype=jnp.int32)
        hit = hit.at[probe_idx].max(valid.astype(jnp.int32))
        unmatched = probe.validity & (hit == 0)
        for n in probe.column_names:
            cols[n] = jnp.concatenate([cols[n], probe.columns[n]], axis=0)
        for n in build_payload:
            shape = (probe.capacity,) + cols[n].shape[1:]
            cols[n] = jnp.concatenate([cols[n], jnp.zeros(shape, cols[n].dtype)],
                                      axis=0)
        out_valid = jnp.concatenate([out_valid, unmatched], axis=0)
        cols["__matched"] = jnp.concatenate(
            [valid, jnp.zeros(probe.capacity, bool)])
        schema["__matched"] = dt.BOOL
    return DeviceTable(cols, out_valid, schema)


@table_op(n_tables=2, family="join")
def _probe_join(probe: DeviceTable, build_state, probe_keys, build_keys,
                build_payload, join_type: str, max_matches: int, pack,
                exact: bool):
    """Sorted-key probe: exact semi/anti joins by ``semi_mask``, else up to
    ``max_matches`` candidates per probe row, which a hashed key keeps only
    where the original columns are equal (bucket-then-verify)."""
    build_table, bt = build_state
    key = _join_probe_key(probe, probe_keys, pack)

    if join_type in ("left_semi", "left_anti") and exact:
        mask = rel.semi_mask(bt, key, probe.validity)
        if join_type == "left_anti":
            mask = probe.validity & ~mask
        return probe.filter(mask)

    res = rel.join_probe(bt, key, probe.validity, max_matches)
    valid = res.valid
    if not exact:
        for pk, bk in zip(probe_keys, build_keys):
            pv = jnp.take(probe.columns[pk], res.probe_idx, axis=0)
            bv = jnp.take(build_table.columns[bk], res.build_idx, axis=0)
            eq = jnp.all(pv == bv, axis=-1) if pv.ndim > 1 else (pv == bv)
            valid = valid & eq
    return _expand_join_output(probe, build_table, res.probe_idx,
                               res.build_idx, valid, build_payload, join_type)


class HashJoin(Operator):
    """Streaming probe against a fully materialized build side.

    TPU adaptation of cuDF's hash join. ``seal_build`` decides the key's
    encoding once for both backends (``_join_probe_key``): one int-like
    column stays raw; a composite int-like key whose value windows (one
    host read of each column's valid min and max, ``_key_ranges``) have a
    product within int32 packs injectively; both are exact. Anything else
    is hashed and verified after the probe, and a semi/anti join on it
    reads every candidate of its hash run (the build's longest, measured
    once by ``relational.longest_run``, rounded up to a power of two).
    The probe state follows the backend (``kernels.ops.current_backend()``
    at ``seal_build``):

    * 'pallas' -- an exact key builds an open-addressing table
                  (``kernels.build_table``, power-of-two slots sized 2x
                  the planner's ``build_rows`` bound). Semi/anti and
                  ``max_matches == 1`` joins probe with ``hash_probe``;
                  expansion joins with ``hash_probe_multi`` (static
                  ``P x max_matches`` output, the jnp path's layout).
                  Hashed keys, build keys equal to the empty sentinel (-1)
                  and builds past ``MAX_HASH_TABLE_SLOTS`` take the jnp
                  path; probe keys equal to the sentinel never match;
    * 'jnp'    -- the build side becomes a sorted key array (doubles as
                  the oracle), with a direct-address prefix count over an
                  exact key's range (a packed key's: its windows' product)
                  where that spans at most ``MAX_INDEX_SLOTS_PER_ROW``
                  slots per build row; other builds are probed with
                  searchsorted.

    ``max_matches`` is the planner's expansion-capacity hint; the oracle
    tests assert it is never exceeded.
    """

    name = "HashJoin"

    def __init__(self, build_keys: Sequence[str], probe_keys: Sequence[str],
                 build_payload: Sequence[str] = (), join_type: str = "inner",
                 max_matches: int = 1, compact: bool = True,
                 build_rows: Optional[int] = None):
        assert join_type in ("inner", "left_semi", "left_anti", "left_outer")
        self.build_keys = tuple(build_keys)
        self.probe_keys = tuple(probe_keys)
        self.build_payload = tuple(build_payload)
        self.join_type = join_type
        self.max_matches = max_matches
        self.compact = compact
        self.build_rows = build_rows     # planner's build-side row bound
        self._build_batches: List[DeviceTable] = []
        self._state = None
        self._hash_state = None          # (build, table_keys, table_vals)
        self._max_probes = 0
        self._exact = True
        self._pack = None                # composite-key windows, or None
        self._matches = max_matches      # jnp path: candidates per probe row
        self._index_slots = 0            # jnp path: direct-address index
        self._multi = False              # expansion probe (hash_probe_multi)

    # build side is fed by the driver before probing starts
    def add_build(self, batch: DeviceTable):
        """Accumulate one build-side batch (device-resident)."""
        self._build_batches.append(batch)

    def _try_pallas_build(self, build: DeviceTable) -> bool:
        """Build the open-addressing table; False -> jnp fallback."""
        cap = int(build.validity.shape[-1])
        bound = min(self.build_rows or cap, cap)
        table_size = max(int(2 ** np.ceil(np.log2(max(2 * bound, 2)))), 2)
        if table_size > MAX_HASH_TABLE_SLOTS:
            return False
        tk, tv = _build_hash_table(build, self.build_keys, table_size,
                                   self._pack)
        tk_host = np.asarray(tk)
        # every valid build row must occupy a slot: a shortfall means a key
        # collided with the empty sentinel (e.g. a -1 key) -- probing that
        # table would silently drop its matches
        if int((tk_host != EMPTY_KEY).sum()) != int(
                np.asarray(build.validity).sum()):
            return False
        self._hash_state = (build, tk, tv)
        self._max_probes = _probe_bound(tk_host)
        return True

    def seal_build(self):
        """Concatenate the build side, decide the key's encoding and build
        the probe state (the open-addressing table under the pallas
        backend, else sorted keys); probing may start after."""
        assert self._build_batches, "join build side is empty"
        build = concat_tables(self._build_batches)
        self._build_batches = []
        keys = self.build_keys
        ints = all(build.schema[k].name in INT_KEY_DTYPES for k in keys)
        ranges = None
        if ints and len(keys) > 1:
            ranges = _read_ranges(build, keys)
            self._pack = _pack_windows(ranges)
        self._exact = ints and (len(keys) == 1 or self._pack is not None)
        _count("join", "hashed_builds", int(not self._exact))
        semi = self.join_type in ("left_semi", "left_anti")
        if kernel_ops.current_backend() == "pallas":
            if self._exact and self._try_pallas_build(build):
                self._multi = not (semi or self.max_matches == 1)
                return
            # the probe wanted a hash kernel but could not take it (hashed
            # key, sentinel-colliding key, or a build_rows bound past the
            # table's slot budget); counted once per sealed build
            kernel_ops.count_dispatch("fallback_probe")
        if self._exact:
            lo, hi = (ranges or _read_ranges(build, keys))[0]
            if self._pack is not None and hi >= lo:
                lo, hi = 0, math.prod(span for _, span in self._pack) - 1
            self._index_slots = _index_slots(lo, hi, _rows(build))
        _count("join", "index_slots", self._index_slots, max)
        runs = semi and not self._exact
        bt = _build_join_table(build, keys, self._pack, self._index_slots,
                               runs)
        if runs:
            bt, longest = bt
            self._matches = _pow2(int(np.max(np.asarray(longest))))
        self._state = (build, bt)

    def add_input(self, batch):
        if self._hash_state is not None:
            if self._multi:
                out = _probe_join_pallas_multi(
                    batch, self._hash_state, self.probe_keys,
                    self.build_payload, self.join_type, self._max_probes,
                    self.max_matches, self._pack)
                if (self.compact
                        and self.join_type in ("inner", "left_outer")):
                    out = compact_table(out)
                return [out]
            return [_probe_join_pallas(batch, self._hash_state,
                                       self.probe_keys, self.build_payload,
                                       self.join_type, self._max_probes,
                                       self._pack)]
        assert self._state is not None, "probe before build sealed"
        _count("join", "dense_probes" if self._index_slots
               else "sorted_probes")
        out = _probe_join(batch, self._state, self.probe_keys, self.build_keys,
                          self.build_payload, self.join_type, self._matches,
                          self._pack, self._exact)
        if (self.compact and self.join_type in ("inner", "left_outer")
                and self.max_matches > 1):
            out = compact_table(out)
        return [out]


# ---------------------------------------------------------------------------
# GraceHashJoin (spill-aware out-of-core join over core.spill)
# ---------------------------------------------------------------------------

def _pow2(n: int) -> int:
    return 1 << max(int(np.ceil(np.log2(max(int(n), 1)))), 0)


@table_op(family="join")
def _grace_pids(table: DeviceTable, keys, num_parts: int):
    """Radix-partition ids for grace-join fan-out — the exchange's
    partitioner (``rel.partition_ids``) with its metadata histogram
    (``radix_histogram`` under the pallas backend, one-hot sum as the jnp
    oracle)."""
    pids = rel.partition_ids([table.columns[k] for k in keys],
                             table.validity, num_parts)
    if kernel_ops.current_backend() == "pallas":
        masked = jnp.where(table.validity, pids,
                           jnp.asarray(num_parts, jnp.int32))
        counts = kernel_ops.radix_histogram(masked, num_parts)
    else:
        onehot = jax.nn.one_hot(pids, num_parts, dtype=jnp.int32)
        counts = jnp.sum(onehot * table.validity[..., None].astype(jnp.int32),
                         axis=-2)
    return pids, counts


def _split_host_partitions(table: DeviceTable, pids, num_parts: int):
    """Pull a (possibly worker-stacked) table to host and slice it into
    ``num_parts`` compacted partitions. Returns ``(columns, validity,
    valid_rows)`` per partition; capacities round up to powers of two so
    similarly sized partitions share compiled probe programs."""
    cols = {n: np.asarray(a) for n, a in table.columns.items()}
    valid = np.asarray(table.validity)
    pid = np.asarray(pids)
    stacked = valid.ndim == 2
    if not stacked:
        valid, pid = valid[None], pid[None]
        cols = {n: a[None] for n, a in cols.items()}
    w = valid.shape[0]
    parts = []
    for p in range(num_parts):
        sel = [np.nonzero(valid[i] & (pid[i] == p))[0] for i in range(w)]
        cap = _pow2(max(max((len(s) for s in sel), default=0), 1))
        validity = np.zeros((w, cap), dtype=bool)
        out = {}
        for n, a in cols.items():
            buf = np.zeros((w, cap) + a.shape[2:], dtype=a.dtype)
            for i, s in enumerate(sel):
                buf[i, : len(s)] = a[i][s]
            out[n] = buf
        for i, s in enumerate(sel):
            validity[i, : len(s)] = True
        if not stacked:
            out = {n: b[0] for n, b in out.items()}
            validity = validity[0]
        parts.append((out, validity, int(sum(len(s) for s in sel))))
    return parts


def _one_row_invalid(table: DeviceTable) -> DeviceTable:
    """A capacity-1, zero-valid-rows table with ``table``'s schema and
    layout (worker-stacked or local)."""
    cols = {n: a[..., :1, :] if table.schema[n].name == "bytes"
            else a[..., :1] for n, a in table.columns.items()}
    return DeviceTable({n: jnp.asarray(a) for n, a in cols.items()},
                       jnp.zeros_like(table.validity[..., :1]),
                       dict(table.schema))


class GraceHashJoin(Operator):
    """Grace-style partitioned hash join over the spill hierarchy.

    Used by the driver when a join's build side does not fit its device
    reservation (``core.spill.SpillManager``). Both sides are
    radix-partitioned on the join-key hash — the same partitioner the
    exchange uses for its metadata phase — so matching rows always land in
    the same partition and each pair joins independently:

    * ``seal_build`` partitions the materialized build side; partitions
      stay device-resident until the reservation is half used, the rest
      spill (host buffers, then paged disk pages as the host tier fills).
    * ``add_input`` partitions each probe batch and stages every slice in
      the spill store (fully blocking — like the classic grace join's
      pass 1).
    * ``finish`` processes partition pairs one at a time: restore one
      build partition, build its hash table (inheriting ``HashJoin``'s
      backend dispatch, so the pallas open-addressing path still applies
      per partition), replay its staged probe slices, emit the outputs.

    Inner/semi/anti/outer joins all stay correct per partition because a
    probe row's matches can only live in its own hash partition.
    """

    name = "GraceHashJoin"
    _seq = itertools.count()

    def __init__(self, build_keys: Sequence[str], probe_keys: Sequence[str],
                 build_payload: Sequence[str] = (), join_type: str = "inner",
                 max_matches: int = 1, compact: bool = True,
                 build_rows: Optional[int] = None, *, spill,
                 reservation: int, num_partitions: Optional[int] = None):
        self.build_keys = tuple(build_keys)
        self.probe_keys = tuple(probe_keys)
        self.build_payload = tuple(build_payload)
        self.join_type = join_type
        self.max_matches = max_matches
        self.compact = compact
        self.build_rows = build_rows
        self.spill = spill
        self.reservation = max(int(reservation), 1)
        self.num_partitions = num_partitions
        self._skey = f"grace{next(self._seq)}"
        self._build_batches: List[DeviceTable] = []
        self._resident: dict = {}        # partition -> DeviceTable (device tier)
        self._spilled_build: set = set()
        self._build_rows_by_part: dict = {}
        self._probe_chunks: dict = {}    # partition -> staged chunk count
        self._build_schema: Optional[dict] = None
        # one-row all-invalid prototypes: when every staged slice is empty
        # (or nothing matches), finish() still emits one correctly-shaped
        # output batch so downstream operators see the join's schema
        self._build_proto: Optional[DeviceTable] = None
        self._probe_proto: Optional[DeviceTable] = None

    def add_build(self, batch: DeviceTable):
        """Accumulate one build-side batch (device-resident until seal)."""
        self._build_batches.append(batch)

    def seal_build(self):
        """Radix-partition the build side; spill partitions past the
        reservation. Probing may start after."""
        assert self._build_batches, "join build side is empty"
        build = concat_tables(self._build_batches)
        self._build_batches = []
        self._build_schema = dict(build.schema)
        self._build_proto = _one_row_invalid(build)
        if self.num_partitions is None:
            # fan out until one partition (+ its probe slice and hash
            # state) fits about half the reservation
            want = -(-2 * build.nbytes() // self.reservation)
            self.num_partitions = max(min(_pow2(want), 64), 2)
        pids, _ = _grace_pids(build, self.build_keys, self.num_partitions)
        parts = _split_host_partitions(build, pids, self.num_partitions)
        resident_budget = self.reservation // 2
        used = 0
        for p, (cols, validity, rows) in enumerate(parts):
            self._build_rows_by_part[p] = rows
            nbytes = validity.nbytes + sum(a.nbytes for a in cols.values())
            if used + nbytes <= resident_budget:
                used += nbytes
                self._resident[p] = DeviceTable(
                    {n: jnp.asarray(a) for n, a in cols.items()},
                    jnp.asarray(validity), dict(self._build_schema))
            else:
                self.spill.put_host((self._skey, "build", p), cols, validity,
                                    self._build_schema)
                self._spilled_build.add(p)

    def add_input(self, batch):
        assert self._build_schema is not None, "probe before build sealed"
        if self._probe_proto is None:
            self._probe_proto = _one_row_invalid(batch)
        pids, _ = _grace_pids(batch, self.probe_keys, self.num_partitions)
        for p, (cols, validity, rows) in enumerate(
                _split_host_partitions(batch, pids, self.num_partitions)):
            if rows == 0:
                continue
            i = self._probe_chunks.get(p, 0)
            self.spill.put_host((self._skey, "probe", p, i), cols, validity,
                                dict(batch.schema))
            self._probe_chunks[p] = i + 1
        return []

    def finish(self):
        outs: List[DeviceTable] = []
        for p in range(self.num_partitions):
            chunks = self._probe_chunks.pop(p, 0)
            if chunks == 0:
                # no probe rows hashed here: nothing can match; discard
                self._resident.pop(p, None)
                if p in self._spilled_build:
                    self.spill.drop((self._skey, "build", p))
                continue
            if p in self._resident:
                build = self._resident.pop(p)
            else:
                build = self.spill.restore((self._skey, "build", p))
            inner = HashJoin(self.build_keys, self.probe_keys,
                             self.build_payload, self.join_type,
                             self.max_matches, compact=self.compact,
                             build_rows=max(self._build_rows_by_part[p], 1))
            inner.open()
            inner.add_build(build)
            inner.seal_build()
            for i in range(chunks):
                chunk = self.spill.restore((self._skey, "probe", p, i))
                outs.extend(inner.add_input(chunk))
            outs.extend(inner.finish())
        if not outs and self._probe_proto is not None:
            # every probe slice was empty (e.g. a selective build filter
            # upstream): emit one all-invalid batch with the join's output
            # schema so the stream stays alive for downstream operators
            inner = HashJoin(self.build_keys, self.probe_keys,
                             self.build_payload, self.join_type,
                             self.max_matches, compact=self.compact,
                             build_rows=1)
            inner.open()
            inner.add_build(self._build_proto)
            inner.seal_build()
            outs.extend(inner.add_input(self._probe_proto))
            outs.extend(inner.finish())
        return outs


@table_op(family="scan")
def _compact(table: DeviceTable):
    return table.compact()


def compact_table(table: DeviceTable) -> DeviceTable:
    """Stream-compact a (possibly worker-stacked) table (paper 3.3.2)."""
    return _compact(table)


# ---------------------------------------------------------------------------
# FusedMorsel: one Pallas dispatch per morsel (filter -> project -> probe)
# ---------------------------------------------------------------------------

@table_op(family="scan")
def _fused_morsel(table: DeviceTable, stages):
    out, _, _ = fused.fused_morsel_program(table, stages)
    return out


@table_op(n_tables=2, family="join")
def _fused_morsel_probe(table: DeviceTable, hash_state, stages, probe_keys,
                        build_payload, join_type: str, max_probes: int, pack):
    build, tk, tv = hash_state
    out, found, bidx = fused.fused_morsel_program(
        table, stages,
        probe=dict(tk=tk, tv=tv, probe_keys=probe_keys, pack=pack,
                   empty_key=EMPTY_KEY, max_probes=max_probes))
    return _attach_build_payload(out, build, found, bidx, build_payload,
                                 join_type)


class FusedMorsel(Operator):
    """A collapsed run of FilterProject stages — optionally ending in a
    single-match open-addressing probe — executed as one Pallas kernel per
    morsel (``core.fused``). Created by ``fuse_morsel_pipeline``; never
    built by the planner directly."""

    name = "FusedMorsel"

    def __init__(self, stages, join: Optional[HashJoin] = None):
        self.stages = tuple(stages)
        self.join = join

    def add_input(self, batch):
        if self.join is None:
            return [_fused_morsel(batch, self.stages)]
        j = self.join
        return [_fused_morsel_probe(batch, j._hash_state, self.stages,
                                    j.probe_keys, j.build_payload,
                                    j.join_type, j._max_probes, j._pack)]


def fuse_morsel_pipeline(pipe: Pipeline) -> None:
    """Collapse the scan pipeline's runs of non-compacting FilterProjects
    (optionally ending in an eligible single-match pallas HashJoin probe)
    into ``FusedMorsel`` operators — one Pallas dispatch per morsel
    instead of one per primitive, with no intermediate morsel
    materialization. Called by the driver's ``StreamingScan`` at iteration
    start, inside the query's backend scope; no-op under the jnp backend.

    A lone FilterProject stays unfused (same dispatch count either way);
    expansion probes, jnp-state joins and compacting stages keep their
    standalone operators. A run with an expression no compiled kernel can
    evaluate (``fused.stages_in_kernel``) stays unfused too, counted once
    as ``fallback_fused``.
    """
    if kernel_ops.current_backend() != "pallas":
        return
    new_ops: List[Operator] = []
    run: List[FilterProject] = []

    def stages():
        return [(fp.filter_expr, fp.projections) for fp in run]

    def fusible():
        if fused.stages_in_kernel(stages()):
            return True
        kernel_ops.count_dispatch("fallback_fused")
        return False

    def flush():
        if len(run) >= 2 and fusible():
            new_ops.append(FusedMorsel(stages()))
        else:
            new_ops.extend(run)
        run.clear()

    for op in pipe.ops:
        if isinstance(op, FilterProject) and not op.compact:
            run.append(op)
        elif (isinstance(op, HashJoin) and run
                and op._hash_state is not None and not op._multi):
            if fusible():
                new_ops.append(FusedMorsel(stages(), join=op))
            else:
                new_ops.extend(run)
                new_ops.append(op)
            run.clear()
        else:
            flush()
            new_ops.append(op)
    flush()
    pipe.ops = new_ops


@table_op(family="sort")
def _head(table: DeviceTable, n: int):
    c = table.compact()
    return c.filter(jnp.arange(c.capacity) < n)


# ---------------------------------------------------------------------------
# OrderBy / Limit
# ---------------------------------------------------------------------------

@table_op(family="sort")
def _order_by(table: DeviceTable, keys, descending, limit):
    order = rel.lexsort([table.columns[k] for k in keys], table.validity,
                        list(descending))
    n = table.capacity if limit is None else min(limit, table.capacity)
    idx = order[:n]
    nvalid = table.num_valid()
    keep = jnp.arange(n) < nvalid
    return table.gather(idx, keep)


class OrderBy(Operator):
    """Blocking global sort (optional top-``limit``); accumulates batches
    in device memory and sorts once at ``finish``."""

    name = "OrderBy"

    def __init__(self, keys: Sequence[str], descending: Sequence[bool] = None,
                 limit: Optional[int] = None):
        self.keys = tuple(keys)
        self.descending = tuple(descending or [False] * len(self.keys))
        self.limit = limit
        self._batches: List[DeviceTable] = []

    def open(self):
        self._batches = []

    def add_input(self, batch):
        self._batches.append(batch)     # device-resident accumulation
        return []

    def finish(self):
        table = concat_tables(self._batches)
        self._batches = []
        return [_order_by(table, self.keys, self.descending, self.limit)]


class Limit(Operator):
    """First ``n`` valid rows (blocking: concatenates, then truncates)."""

    name = "Limit"

    def __init__(self, n: int):
        self.n = n
        self._batches: List[DeviceTable] = []

    def open(self):
        self._batches = []

    def add_input(self, batch):
        self._batches.append(batch)
        return []

    def finish(self):
        table = concat_tables(self._batches)
        self._batches = []
        return [_head(table, self.n)]


# ---------------------------------------------------------------------------
# Scalar broadcast (uncorrelated scalar subqueries: Q11, Q15, Q22)
# ---------------------------------------------------------------------------

@table_op(n_tables=2, family="scan")
def _attach_scalar(batch: DeviceTable, scalar: DeviceTable, columns):
    s = scalar.compact()
    out = batch
    for n in columns:
        v = s.columns[n][0]
        out = out.with_column(n, jnp.broadcast_to(v, (batch.capacity,)),
                              s.schema[n])
    return out


class ScalarBroadcast(Operator):
    """Attach the single row of a materialized table to every input row."""

    name = "ScalarBroadcast"

    def __init__(self, columns: Sequence[str]):
        self.columns = tuple(columns)
        self._scalar: Optional[DeviceTable] = None

    def set_scalar(self, table: DeviceTable):
        """Provide the materialized 1-row table to attach."""
        self._scalar = table

    def add_input(self, batch):
        assert self._scalar is not None
        return [_attach_scalar(batch, self._scalar, self.columns)]


# ---------------------------------------------------------------------------
# Host/device conversions (CudfToVelox / CudfFromVelox analogues)
# ---------------------------------------------------------------------------

class HostRoundTrip(Operator):
    """D2H + H2D conversion pair around a host-only operator.

    The paper inserts CudfToVelox/CudfFromVelox when a pipeline contains an
    operator without a GPU version; this models that round trip so its cost
    is measurable. ``stats`` accumulates staged bytes.
    """

    name = "HostRoundTrip"
    is_device = False

    def __init__(self, stats: Optional[dict] = None):
        self.stats = stats if stats is not None else {}

    def add_input(self, batch):
        import numpy as np
        host_cols = {n: np.asarray(a) for n, a in batch.columns.items()}
        validity = np.asarray(batch.validity)          # device -> host
        nbytes = sum(a.nbytes for a in host_cols.values()) + validity.nbytes
        self.stats["bytes"] = self.stats.get("bytes", 0) + 2 * nbytes
        cols = {n: jnp.asarray(a) for n, a in host_cols.items()}   # host -> device
        return [DeviceTable(cols, jnp.asarray(validity), batch.schema)]
