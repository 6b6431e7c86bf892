"""Distributed query driver: plays the Presto coordinator + Velox drivers.

``Driver`` walks a logical plan, splits it into stages at exchange
boundaries (Aggregation auto-lowering, partitioned/broadcast joins, explicit
Exchange nodes), and executes each stage as a pipeline of device operators
over worker-stacked batches ([W, cap, ...] arrays; axis 0 sharded over the
mesh's worker axis).

Driver adaptation (paper §3.1): every operator here has a device
implementation, matching the paper's goal state ("all 22 TPC-H queries run
entirely on GPUs"). To *measure* the cost the paper eliminates,
``ExecutionContext.host_only_ops`` lists operator names whose device version
is declared unavailable -- the driver then inserts a HostRoundTrip
conversion around them, exactly like CudfToVelox/CudfFromVelox insertion.
"""

from __future__ import annotations

import contextlib
import dataclasses
import inspect
from typing import Dict, Iterator, Optional, Sequence

import numpy as np

from ..kernels import ops as kernel_ops
from . import operators as ops
from . import plan as P
from . import tracing
from .exchange import ExchangeProtocol, ICIExchange
from .streaming import ScanStats
from .table import DeviceTable, concat_tables

# smallest device reservation granted to a memory-hungry operator under
# pressure: enough to make progress (one partition / a few groups resident)
# without letting small operators monopolise the budget
_MIN_GRANT = 1 << 10


@dataclasses.dataclass
class ExecutionContext:
    """Immutable per-query execution config (worker count, exchange,
    batching, streaming knobs) snapshot from a ``Session``. One Driver is
    built per query; the scheduler additionally clones any explicitly
    configured exchange protocol so concurrent queries never share its
    mutable stats."""

    catalog: "object"                       # repro.core.session.Catalog
    num_workers: int = 1
    exchange: Optional[ExchangeProtocol] = None
    batch_rows: int = 8192
    # operators whose device version is "unavailable" (forces host round trip)
    host_only_ops: frozenset = frozenset()
    collect_stats: bool = True
    mesh: Optional[object] = None           # jax Mesh with a 'workers' axis
    # morsel-driven scans: async storage->device prefetch with a bounded
    # queue of `prefetch_depth` morsels (False = synchronous baseline)
    streaming: bool = True
    prefetch_depth: int = 2
    # physical kernel backend for the hot relational primitives:
    # 'jnp' | 'pallas'. None resolves at snapshot time to the calling
    # thread's kernels.ops.current_backend() — an enclosing use_pallas()
    # scope, else the REPRO_KERNEL_BACKEND env default
    kernel_backend: Optional[str] = None
    # tiered-memory spill manager (core.spill). None = in-memory-only
    # execution (the pre-spill contract); set, the memory-hungry operators
    # run spill-aware: joins whose build side exceeds its reservation go
    # grace-partitioned, aggregations flush accumulator runs to the host
    # tier, and oversized exchange send buffers stage through the store.
    spill: Optional[object] = None
    # runtime-feedback store (core.feedback.FeedbackStore). Set, the driver
    # counts each plan node's observed output cardinality while streaming
    # and harvests the counts (plus join build-key multiplicities and
    # zone-map skip fractions) into the store after the query completes,
    # so the next optimization of the same plan shape re-plans warm.
    feedback: Optional[object] = None

    def __post_init__(self):
        if self.exchange is None:
            self.exchange = ICIExchange(mesh=self.mesh)
        if self.kernel_backend is None:
            self.kernel_backend = kernel_ops.current_backend()

    def host_budget(self):
        """Shared host-memory budget (prefetch + spill host tier), if any."""
        return self.spill.host if self.spill is not None else None

    def worker_sharding(self):
        """NamedSharding over the mesh's 'workers' axis (None off-mesh)."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec("workers"))


@dataclasses.dataclass
class Stream:
    """A stage output: an iterator of worker-stacked batches + distribution.

    ``scan`` is set while the stream is still the raw output of a
    ``StreamingScan`` stage: downstream Filter/Project nodes fuse into the
    stage (per-morsel execution) instead of wrapping another pipeline.
    """
    batches: Iterator[DeviceTable]
    dist: str                               # 'partitioned' | 'replicated'
    scan: Optional["StreamingScan"] = None


class StreamingScan:
    """Morsel-driven scan stage (paper §2.2, challenge 1).

    Drains the bounded prefetch queue of a ``TableSource.stream`` and runs
    the scan-fused operator pipeline (pushed-down filter, projections, and
    any other fused stages) on each morsel *as it arrives* -- storage read
    and host->device transfer of morsel N+1 overlap the compute on morsel N,
    instead of the concat-then-run baseline where I/O, transfer and compute
    fully serialize.
    """

    def __init__(self, table: str, morsels: Iterator[DeviceTable],
                 stats: ScanStats,
                 op_host_seconds: Optional[Dict[str, float]] = None):
        self.table = table
        self.morsels = morsels
        self.stats = stats
        self.pipe = ops.Pipeline(seconds=op_host_seconds)

    def fuse(self, op: ops.Operator) -> None:
        """Append an operator to the per-morsel scan pipeline (must be
        called before iteration starts, i.e. during plan walking)."""
        self.pipe.ops.append(op)

    def batches(self) -> Iterator[DeviceTable]:
        """Drain the prefetch queue through the fused per-morsel pipeline
        (each fused operator's calls are spanned and timed by the
        ``Pipeline``)."""
        # collapse filter->project->probe runs into single-dispatch fused
        # kernels; runs here (not in fuse()) so every fused stage -- and
        # the query's backend scope -- is in place before the first morsel
        ops.fuse_morsel_pipeline(self.pipe)
        self.pipe.open()
        for morsel in self.morsels:
            yield from self.pipe.add_input(morsel)
        yield from self.pipe.finish()


def empty_executor_stats() -> Dict[str, object]:
    """The executor-stats dict shape before any query has run.

    ``Session.executor_stats()`` (no query yet) and
    ``QueryHandle.executor_stats`` (not yet executed) both return this, so
    callers can read ``stats['kernel_dispatch']`` etc. without guarding on
    which serving path produced the dict or whether anything ran.
    """
    return {
        "tables": {},
        "op_host_seconds": {},
        "conversions": {},
        "exchange_protocol": "",
        "exchanges": {},
        "kernel_backend": "",
        "kernel_dispatch": {},
        "agg": {},
        "join": {},
        "traces": {},
        "spill": {},
        "spill_staged_exchanges": 0,
        "feedback": {},
    }


class Driver:
    """Executes one logical plan as streaming operator pipelines.

    Plays the Presto coordinator + Velox drivers: walks the plan tree,
    splits it into stages at exchange boundaries, and streams batches
    through device operators. A Driver instance is single-query and
    single-use; the scheduler creates one per admitted query (its
    ``executor_stats`` are then reported on that query's handle).
    """

    def __init__(self, ctx: ExecutionContext):
        self.ctx = ctx
        # per operator name, host seconds of the operator's own calls (the
        # intervals of its repro.op.* spans): dispatch time, not device time
        self.op_host_seconds: Dict[str, float] = {}
        self.conversion_stats: Dict[str, int] = {}
        self.scan_stats: Dict[str, ScanStats] = {}
        # per-query kernel dispatch counts (kind -> executions of a pallas
        # kernel: 'probe', 'agg', 'compact', 'partition', 'build')
        self.kernel_dispatch: Dict[str, int] = {}
        # per-query HashAggregation counters: partials, merges, merge_rows
        self.agg_stats: Dict[str, int] = dict.fromkeys(ops.AGG_COUNTERS, 0)
        # per-query jnp-path HashJoin counters: probes by lookup, and the
        # largest direct-address index built
        self.join_stats: Dict[str, int] = dict.fromkeys(ops.JOIN_COUNTERS, 0)
        # per-query traces of device programs, per family (a warm query
        # traces nothing)
        self.trace_counts: Dict[str, int] = {}
        # per-fragment exchange stats: one entry per exchange executed, in
        # execution order ("#0 Repartition(l_orderkey)" -> counter deltas)
        self.exchange_stats: Dict[str, Dict[str, float]] = {}
        self._frag_seq = 0
        # exchanges whose send buffer was staged through the spill store
        self.spill_staged_exchanges = 0
        self._spill_seq = 0
        # runtime-feedback observation state: per-node valid-row counters
        # filled by the counting generators `_observe` wraps streams in,
        # plus exact-key build multiplicities sampled in `_exec_join`
        self._feedback_obs: list = []
        self._feedback_matches: Dict[int, int] = {}

    def executor_stats(self) -> Dict[str, object]:
        """Per-query executor stats: scan counters, operator host times,
        kernel backend + dispatch counts, aggregation merge counters
        (``agg``), join probes by lookup (``join``), program traces per
        family (``traces``), per-fragment exchange counters
        (rows/bytes moved, host staging), per-tier spill counters, and the
        feedback-store summary. Same key shape as
        ``empty_executor_stats()``."""
        return {
            "tables": {t: s.summary() for t, s in self.scan_stats.items()},
            "op_host_seconds": dict(self.op_host_seconds),
            "conversions": dict(self.conversion_stats),
            "exchange_protocol": self.ctx.exchange.name,
            "exchanges": {k: dict(v) for k, v in self.exchange_stats.items()},
            "kernel_backend": self.ctx.kernel_backend,
            "kernel_dispatch": dict(self.kernel_dispatch),
            "agg": dict(self.agg_stats),
            "join": dict(self.join_stats),
            "traces": dict(self.trace_counts),
            "spill": (self.ctx.spill.stats.summary()
                      if self.ctx.spill is not None else {}),
            "spill_staged_exchanges": self.spill_staged_exchanges,
            "feedback": (self.ctx.feedback.summary()
                         if self.ctx.feedback is not None else {}),
        }

    def _kernel_scope(self):
        """Backend + dispatch-, merge-, join- and trace-accounting scope
        one query runs under."""
        scope = contextlib.ExitStack()
        scope.enter_context(kernel_ops.use_backend(self.ctx.kernel_backend))
        scope.enter_context(
            kernel_ops.collect_dispatches(self.kernel_dispatch))
        scope.enter_context(ops.collect_agg_stats(self.agg_stats))
        scope.enter_context(ops.collect_join_stats(self.join_stats))
        scope.enter_context(tracing.collect_traces(self.trace_counts))
        return scope

    # -- public API ----------------------------------------------------------
    def execute(self, node: P.PlanNode) -> DeviceTable:
        """Run the plan; return the result as one device-resident table."""
        try:
            with tracing.span("repro.driver.execute"), self._kernel_scope():
                stream = self._stream(node)
                table = self._materialize(stream)
            self._harvest_feedback()
            return table
        finally:
            self._close_spill()

    def collect(self, node: P.PlanNode) -> Dict[str, np.ndarray]:
        """Run the plan; return valid rows as host numpy columns
        (deduplicated to worker 0 for replicated results)."""
        try:
            with tracing.span("repro.driver.execute"):
                with self._kernel_scope():
                    stream = self._stream(node)
                    table = self._materialize_table(stream.batches)
                # the blocking device-to-host copy of the result
                with tracing.span("repro.driver.to_host"):
                    out = self._collect_host(stream, table)
            self._harvest_feedback()
            return out
        finally:
            self._close_spill()

    def collect_batch(self, shapes, lanes=None) -> "list":
        """Run a group of compatible queries (``core.batch.BatchShape``
        sharing one interned program) as a single stacked execution;
        returns one host-numpy result dict per member, in order.
        ``lanes`` pins the member-lane count of the stacked program (the
        scheduler passes its per-program cap so every launch reuses one
        compiled executable); None sizes it to the group."""
        from . import batch   # lazy: batch imports operators/fused
        try:
            with tracing.span("repro.driver.execute"), self._kernel_scope():
                return batch.run_batch(self, shapes, lanes=lanes)
        finally:
            self._close_spill()

    def _close_spill(self) -> None:
        """Delete this query's spill files (counters survive in stats)."""
        if self.ctx.spill is not None:
            self.ctx.spill.close()

    def _collect_host(self, stream: "Stream",
                      table: DeviceTable) -> Dict[str, np.ndarray]:
        if stream.dist == "replicated":
            # all workers hold identical results; take worker 0
            one = DeviceTable(
                {n: a[0] for n, a in table.columns.items()},
                table.validity[0], table.schema)
            return one.to_numpy()
        # partitioned: concatenate every worker's valid rows
        out: Dict[str, np.ndarray] = {}
        validity = np.asarray(table.validity).reshape(-1)
        for n, a in table.columns.items():
            flat = np.asarray(a).reshape((-1,) + a.shape[2:])
            out[n] = flat[validity]
        return out

    # -- plumbing --------------------------------------------------------------
    def _materialize_table(self, batches: Iterator[DeviceTable]) -> DeviceTable:
        got = list(batches)
        assert got, "empty stream"
        return got[0] if len(got) == 1 else concat_tables(got)

    def _materialize(self, stream: Stream) -> DeviceTable:
        return self._materialize_table(stream.batches)

    def _rebatch(self, table: DeviceTable) -> Iterator[DeviceTable]:
        """Split a stacked table back into batch_rows-sized batches."""
        cap = table.validity.shape[1]
        step = self.ctx.batch_rows
        if cap <= step:
            yield table
            return
        for lo in range(0, cap, step):
            hi = min(lo + step, cap)
            cols = {n: a[:, lo:hi] for n, a in table.columns.items()}
            yield DeviceTable(cols, table.validity[:, lo:hi], table.schema)

    def _run_pipeline(self, op: ops.Operator, stream: Iterator[DeviceTable]
                      ) -> Iterator[DeviceTable]:
        # each operator call is spanned and timed on its own, never across
        # a yield: upstream pulls and the consumer's work stay outside
        wrapped = self._maybe_host_wrap(op)
        secs = self.op_host_seconds
        op.open()
        for batch in stream:
            for pre in wrapped["pre"]:
                batch = ops.call_op(pre, "add_input", secs, batch)[0]
            for out in ops.call_op(op, "add_input", secs, batch):
                for post in wrapped["post"]:
                    out = ops.call_op(post, "add_input", secs, out)[0]
                yield out
        for out in ops.call_op(op, "finish", secs):
            for post in wrapped["post"]:
                out = ops.call_op(post, "add_input", secs, out)[0]
            yield out

    def _maybe_host_wrap(self, op: ops.Operator):
        if op.name in self.ctx.host_only_ops:
            rt = ops.HostRoundTrip(self.conversion_stats)
            return {"pre": [rt], "post": []}
        return {"pre": [], "post": []}

    @property
    def _w(self) -> int:
        return self.ctx.num_workers

    def _maybe_stage(self, table: DeviceTable) -> DeviceTable:
        """Stage an oversized exchange send buffer through the spill store
        (device -> host -> paged disk as the tiers fill) instead of pinning
        it in device memory alongside the receive buffers."""
        spill = self.ctx.spill
        if spill is None or not spill.should_stage(table.nbytes()):
            return table
        key = ("exchange-stage", self._spill_seq)
        self._spill_seq += 1
        spill.spill_table(key, table)
        self.spill_staged_exchanges += 1
        return spill.restore(key)

    def _repartition(self, table: DeviceTable, keys: Sequence[str],
                     label: str = "repartition") -> DeviceTable:
        return self._tracked(
            "repro.exchange.repartition", f"{label}({','.join(keys)})",
            lambda: self.ctx.exchange.repartition(
                self._maybe_stage(table), tuple(keys), self._w))

    def _broadcast(self, table: DeviceTable,
                   label: str = "broadcast") -> DeviceTable:
        return self._tracked(
            "repro.exchange.broadcast", label,
            lambda: self.ctx.exchange.broadcast(
                self._maybe_stage(table), self._w))

    def _tracked(self, span: str, label: str, fn):
        """Run one exchange under the span ``span``, recording its stats
        delta as a fragment entry (surfaced through
        ``Session.explain(analyze=True)``)."""
        st = self.ctx.exchange.stats
        before = dataclasses.replace(st)
        with tracing.span(span, fragment=label):
            out = fn()
        self.exchange_stats[f"#{self._frag_seq} {label}"] = {
            "rounds": st.rounds - before.rounds,
            "rows_moved": st.rows_moved - before.rows_moved,
            "bytes_moved": st.bytes_moved - before.bytes_moved,
            "host_staged_bytes": (st.host_staged_bytes
                                  - before.host_staged_bytes),
            "seconds": st.seconds - before.seconds,
        }
        self._frag_seq += 1
        return out

    # -- recursive plan execution ----------------------------------------------
    def _stream(self, node: P.PlanNode) -> Stream:
        method = getattr(self, f"_exec_{type(node).__name__.lower()}")
        stream = method(node)
        if (self.ctx.feedback is None
                or isinstance(node, (P.Repartition, P.Broadcast, P.Exchange))):
            # exchange nodes are keyed through (plan.feedback_key looks at
            # their child), so counting them would double-observe the child
            return stream
        return self._observe(node, stream)

    def _observe(self, node: P.PlanNode, stream: Stream) -> Stream:
        """Wrap a stage output in a valid-row counting generator; counts
        are harvested into the feedback store after the query completes.
        Scans with fused Filter/Project stages count post-fusion rows (an
        under-count of the raw scan — safe, scan rows only feed memory
        pricing, never a correctness-critical capacity)."""
        box = {"rows": 0}

        def counted(src):
            for batch in src:
                box["rows"] += int(batch.num_valid())
                yield batch

        self._feedback_obs.append((node, box, stream.dist))
        return Stream(counted(stream.batches), stream.dist, scan=stream.scan)

    def _observe_join_build(self, node: P.Join, build: DeviceTable,
                            dist: str) -> None:
        """Record the exact-key build multiplicity for a join: the maximum
        number of valid build rows sharing one key value, which bounds
        matches per probe row. Only sampled for single int-like keys —
        equality there is exact (no hash collisions), so the bound is
        sound as a warm ``max_matches``; hashed composite keys are never
        tightened."""
        kt = [build.schema[k] for k in node.build_keys]
        if len(kt) != 1 or kt[0].name not in ops.INT_KEY_DTYPES:
            return
        keys = np.asarray(build.columns[node.build_keys[0]])
        valid = np.asarray(build.validity)
        if dist == "replicated" and self._w > 1:
            keys, valid = keys[0], valid[0]     # identical worker replicas
        vals = keys[valid]
        m = 1 if vals.size == 0 else int(
            np.max(np.unique(vals, return_counts=True)[1]))
        self._feedback_matches[id(node)] = m

    def _harvest_feedback(self) -> None:
        """Flush the per-node observations into the feedback store (called
        once, after the result materialized — both the direct-session and
        the scheduler path run through here)."""
        fb = self.ctx.feedback
        if fb is None or not self._feedback_obs:
            return
        from .optimizer import row_bound
        for node, box, dist in self._feedback_obs:
            rows = box["rows"]
            if dist == "replicated" and self._w > 1:
                rows //= self._w                # identical worker replicas
            try:
                est = row_bound(node, self.ctx.catalog)
            except Exception:
                est = None                      # exchange-wrapped subtree
            skip = None
            if isinstance(node, P.TableScan):
                stats = self.scan_stats.get(node.table)
                if stats is not None and stats.chunks_total:
                    skip = stats.chunks_skipped / stats.chunks_total
            fb.record(fb.key_for(node, self.ctx.catalog, self._w), rows,
                      estimated=est,
                      max_matches=self._feedback_matches.get(id(node)),
                      skip_fraction=skip)
        self._feedback_obs = []
        self._feedback_matches = {}

    def _place(self, batches: Iterator[DeviceTable]) -> Iterator[DeviceTable]:
        """Pin scan output to the worker mesh axis (one shard per worker,
        the paper's one-worker-per-GPU discipline)."""
        sharding = self.ctx.worker_sharding()
        if sharding is None:
            yield from batches
            return
        import jax
        for b in batches:
            yield jax.device_put(b, sharding)

    def _exec_tablescan(self, node: P.TableScan) -> Stream:
        src = self.ctx.catalog.get(node.table)
        stats = self.scan_stats.setdefault(node.table, ScanStats())
        if self.ctx.streaming and hasattr(src, "stream"):
            kwargs = {}
            if "host_budget" in inspect.signature(src.stream).parameters:
                # prefetch participates in the spill manager's host budget
                kwargs["host_budget"] = self.ctx.host_budget()
            morsels = src.stream(self._w, node.columns, self.ctx.batch_rows,
                                 filter_expr=node.filter,
                                 prefetch_depth=self.ctx.prefetch_depth,
                                 sharding=self.ctx.worker_sharding(),
                                 stats=stats, **kwargs)
            scan = StreamingScan(node.table, morsels, stats,
                                 self.op_host_seconds)
            if node.filter is not None:
                fp = ops.FilterProject(node.filter)
                if fp.name in self.ctx.host_only_ops:
                    return Stream(self._run_pipeline(fp, scan.batches()),
                                  "partitioned")
                scan.fuse(fp)
            return Stream(scan.batches(), "partitioned", scan=scan)
        # synchronous baseline: read + transfer inline with compute
        kwargs = {}
        if "stats" in inspect.signature(src.scan).parameters:
            kwargs["stats"] = stats
        batches = self._place(src.scan(self._w, node.columns,
                                       self.ctx.batch_rows,
                                       filter_expr=node.filter, **kwargs))
        if node.filter is not None:
            fp = ops.FilterProject(node.filter)
            return Stream(self._run_pipeline(fp, batches), "partitioned")
        return Stream(batches, "partitioned")

    def _exec_inmemorysource(self, node: P.InMemorySource) -> Stream:
        from .session import InMemoryTable
        src = InMemoryTable(node.name, node.data, node.schema)
        return Stream(src.scan(self._w, None, self.ctx.batch_rows), "partitioned")

    def _exec_filter(self, node: P.Filter) -> Stream:
        child = self._stream(node.child)
        fp = ops.FilterProject(node.predicate, None, node.compact)
        if child.scan is not None and fp.name not in self.ctx.host_only_ops:
            child.scan.fuse(fp)          # per-morsel, inside the scan stage
            return child
        return Stream(self._run_pipeline(fp, child.batches), child.dist)

    def _exec_project(self, node: P.Project) -> Stream:
        child = self._stream(node.child)
        fp = ops.FilterProject(None, node.projections)
        if child.scan is not None and fp.name not in self.ctx.host_only_ops:
            child.scan.fuse(fp)          # per-morsel, inside the scan stage
            return child
        return Stream(self._run_pipeline(fp, child.batches), child.dist)

    def _release_after(self, batches: Iterator[DeviceTable],
                       op_key: str) -> Iterator[DeviceTable]:
        """Yield through ``batches``; return the operator's device
        reservation to the spill manager when the stream is drained."""
        try:
            yield from batches
        finally:
            self.ctx.spill.release(op_key)

    def _agg_spill(self, node: P.Aggregation) -> dict:
        """Spill kwargs for one HashAggregation: reserve the accumulator's
        footprint; a shortfall runs the operator in flush-to-host mode with
        the flush point scaled to the granted fraction."""
        spill = self.ctx.spill
        if spill is None:
            return {}
        from .optimizer import infer_schema, row_width
        try:
            width = row_width(infer_schema(node, self.ctx.catalog))
        except (TypeError, KeyError):
            width = 64
        # accumulator + the concat-merge scratch copy, per worker
        want = 2 * width * node.max_groups * self._w
        op_key = f"agg{self._spill_seq}"
        self._spill_seq += 1
        granted = spill.reserve(op_key, want, minimum=min(want, _MIN_GRANT))
        if granted >= want:
            spill.release(op_key)
            return {}
        flush = max(1, (node.max_groups * granted) // max(want, 1))
        return {"spill": spill, "spill_flush_groups": flush,
                "op_key": op_key}

    def _exec_aggregation(self, node: P.Aggregation) -> Stream:
        child = self._stream(node.child)
        mode = node.mode
        if mode == "auto":
            mode = "single" if (self._w == 1 or child.dist == "replicated") \
                else "two_phase"

        def pipeline(agg_mode, batches):
            sk = self._agg_spill(node)
            op_key = sk.pop("op_key", None)
            agg = ops.HashAggregation(node.group_keys, node.aggs, agg_mode,
                                      node.max_groups, **sk)
            out = self._run_pipeline(agg, batches)
            return self._release_after(out, op_key) if op_key else out

        if mode in ("single", "partial", "final"):
            return Stream(pipeline(mode, child.batches), child.dist)

        # two-phase: partial -> exchange on keys -> final  (Velox's
        # Partial/Final modes with a Presto exchange between the stages)
        partial_out = list(pipeline("partial", child.batches))
        table = self._materialize_table(iter(partial_out))
        if node.group_keys:
            exchanged = self._repartition(table, node.group_keys, "agg")
            dist = "partitioned"
        else:
            # global agg: replicate partials
            exchanged = self._broadcast(table, "agg-broadcast")
            dist = "replicated"
        return Stream(pipeline("final", self._rebatch(exchanged)), dist)

    def _exec_distinct(self, node: P.Distinct) -> Stream:
        child = self._stream(node.child)
        d1 = ops.Distinct(node.keys, node.max_groups)
        out = list(self._run_pipeline(d1, child.batches))
        # explicit partial/final fragments (planner-placed exchange between
        # them) run the local dedup only; 'auto' keeps the runtime exchange
        if (node.mode in ("partial", "final") or self._w == 1
                or child.dist == "replicated"):
            return Stream(iter(out), child.dist)
        table = self._materialize_table(iter(out))
        exchanged = self._repartition(table, node.keys, "distinct")
        d2 = ops.Distinct(node.keys, node.max_groups)
        return Stream(self._run_pipeline(d2, self._rebatch(exchanged)),
                      "partitioned")

    def _exec_join(self, node: P.Join) -> Stream:
        build_stream = self._stream(node.build)
        build = self._materialize(build_stream)
        if self.ctx.feedback is not None:
            self._observe_join_build(node, build, build_stream.dist)

        probe_stream = self._stream(node.probe)
        dist = probe_stream.dist
        probe_batches = probe_stream.batches
        probe_scan = probe_stream.scan

        if self._w > 1:
            if node.distribution == "broadcast":
                if build_stream.dist != "replicated":
                    build = self._broadcast(build, "join-build-broadcast")
            elif node.distribution == "partitioned":
                if build_stream.dist != "replicated":
                    build = self._repartition(build, node.build_keys,
                                              "join-build")
                probe_tab = self._materialize_table(probe_batches)
                probe_tab = self._repartition(probe_tab, node.probe_keys,
                                              "join-probe")
                probe_batches = self._rebatch(probe_tab)
                probe_scan = None       # the scan is already drained
                dist = "partitioned"
            # 'local': co-partitioned already, no movement

        spill = self.ctx.spill
        op_key = None
        if spill is not None:
            # reserve the build side + hash state + probe headroom; a
            # shortfall routes the join through the grace-partitioned path
            want = 2 * build.nbytes()
            op_key = f"join{self._spill_seq}"
            self._spill_seq += 1
            granted = spill.reserve(op_key, want, minimum=min(want, _MIN_GRANT))
            if granted < want:
                join = ops.GraceHashJoin(
                    node.build_keys, node.probe_keys, node.build_payload,
                    node.join_type, node.max_matches,
                    build_rows=node.build_rows, spill=spill,
                    reservation=granted)
                join.open()
                join.add_build(build)
                ops.call_op(join, "seal_build", self.op_host_seconds)
                del build   # partitioned into the spill hierarchy
                out = self._run_pipeline(join, probe_batches)
                return Stream(self._release_after(out, op_key), dist)

        join = ops.HashJoin(node.build_keys, node.probe_keys,
                            node.build_payload, node.join_type,
                            node.max_matches, build_rows=node.build_rows)
        join.open()
        join.add_build(build)
        ops.call_op(join, "seal_build", self.op_host_seconds)
        if (probe_scan is not None and join._hash_state is not None
                and not join._multi
                and join.name not in self.ctx.host_only_ops):
            # fuse the single-match probe into the scan's per-morsel
            # pipeline: the iteration-start collapse folds it (plus any
            # preceding fused filter/project stages) into one Pallas
            # dispatch per morsel, timed under the fused operator's name
            # in op_host_seconds; the returned stream drops
            # the scan so downstream stages keep their own dispatches
            # (fusing past a join would also skew its feedback counts).
            probe_scan.fuse(join)
            out = probe_batches
        else:
            out = self._run_pipeline(join, probe_batches)
        if op_key is not None:
            out = self._release_after(out, op_key)
        return Stream(out, dist)

    def _exec_orderby(self, node: P.OrderBy) -> Stream:
        from .exchange import maybe_compact
        child = self._stream(node.child)
        # compact away dead padding (e.g. max_groups slots) before sorting
        table = maybe_compact(self._materialize_table(child.batches))
        ob = ops.OrderBy(node.keys, node.descending, node.limit)
        if node.local:
            # distributed top-N partial: each worker sorts/truncates its own
            # slice; the planner's Broadcast above gathers the candidates
            return Stream(self._run_pipeline(ob, iter([table])), child.dist)
        if self._w > 1 and child.dist != "replicated":
            # final ordering is global
            table = self._broadcast(table, "orderby-gather")
        return Stream(self._run_pipeline(ob, iter([table])), "replicated")

    def _exec_limit(self, node: P.Limit) -> Stream:
        child = self._stream(node.child)
        table = self._materialize_table(child.batches)
        if self._w > 1 and child.dist != "replicated":
            table = self._broadcast(table, "limit-gather")
        lim = ops.Limit(node.n)
        return Stream(self._run_pipeline(lim, iter([table])), "replicated")

    def _exec_scalarbroadcast(self, node: P.ScalarBroadcast) -> Stream:
        scalar_stream = self._stream(node.scalar)
        scalar = self._materialize(scalar_stream)
        if self._w > 1 and scalar_stream.dist != "replicated":
            scalar = self._broadcast(scalar, "scalar-broadcast")
        child = self._stream(node.child)
        sb = ops.ScalarBroadcast(node.columns)
        sb.set_scalar(scalar)
        return Stream(self._run_pipeline(sb, child.batches), child.dist)

    def _exec_exchange(self, node, label: str = "exchange") -> Stream:
        child = self._stream(node.child)
        table = self._materialize_table(child.batches)
        exchanged = self._repartition(table, node.keys, label)
        return Stream(self._rebatch(exchanged), "partitioned")

    def _exec_repartition(self, node: P.Repartition) -> Stream:
        """Planner-placed hash exchange: same execution as the legacy
        Exchange node, under its fragment label."""
        return self._exec_exchange(node, label="Repartition")

    def _exec_broadcast(self, node: P.Broadcast) -> Stream:
        """Planner-placed replication: every worker receives all valid rows
        of the child fragment (no-op when the stream is already replicated,
        which would otherwise multiply rows)."""
        child = self._stream(node.child)
        table = self._materialize_table(child.batches)
        if child.dist == "replicated":
            return Stream(self._rebatch(table), "replicated")
        out = self._broadcast(table, "Broadcast")
        return Stream(self._rebatch(out), "replicated")
