"""Session & catalog: the engine's public entry point.

A Session binds a catalog of tables to an execution configuration (worker
count, exchange protocol, batch size) and runs logical plans through the
Driver. Mirrors a Presto cluster: catalog -> connector, session -> query
submission, ExecutionContext -> worker fleet config.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import threading
from typing import Dict, Iterator, Optional

import numpy as np

from . import tracing
from .driver import Driver, ExecutionContext, empty_executor_stats
from .exchange import ExchangeProtocol, ICIExchange
from .plan import PlanNode
from .streaming import HostMorsel, MorselPrefetcher, ScanStats, morsel_to_device
from .table import DeviceTable

_log = logging.getLogger(__name__)


class TableSource:
    """Abstract storage backend for one catalog table.

    Backends implement ``_host_morsels`` (pure host-side reads) and
    ``num_rows``; the shared ``scan``/``stream`` wrappers handle device
    placement. Implementations: ``InMemoryTable`` (numpy), and the chunked
    file formats ``storage.colchunk.ColumnChunkTable`` /
    ``storage.paged.PagedTableSource`` (both with zone-map skipping).
    """

    name: str
    schema: dict
    # catalog statistics for the optimizer: column sets that uniquely
    # identify a row (primary/candidate keys), e.g. (("o_orderkey",),)
    unique_keys: tuple = ()

    def _host_morsels(self, num_workers: int, columns, batch_rows: int,
                      filter_expr=None,
                      stats: Optional[ScanStats] = None
                      ) -> Iterator[HostMorsel]:
        """Host-side scan units (storage reads only, no device transfer).
        Backends implement this once; ``scan``/``stream`` wrap it."""
        raise NotImplementedError

    def scan(self, num_workers: int, columns, batch_rows: int,
             filter_expr=None,
             stats: Optional[ScanStats] = None) -> Iterator[DeviceTable]:
        """Synchronous scan: read + device-put inline on the caller's thread
        (the materialize-then-run baseline the paper starts from).

        Yields worker-stacked ``DeviceTable`` batches::

            src = session.catalog.get("lineitem")
            for batch in src.scan(num_workers=1, columns=["l_quantity"],
                                  batch_rows=4096):
                print(batch.validity.shape)     # [W, cap]
        """
        for morsel in self._host_morsels(num_workers, columns, batch_rows,
                                         filter_expr, stats=stats):
            if stats is not None:
                stats.morsels += 1
                stats.bytes_transferred += morsel.nbytes()
            yield morsel_to_device(morsel)

    def stream(self, num_workers: int, columns, batch_rows: int,
               filter_expr=None, prefetch_depth: int = 2, sharding=None,
               stats: Optional[ScanStats] = None,
               host_budget=None) -> MorselPrefetcher:
        """Asynchronous scan: a background thread reads morsel N+1 from
        storage and transfers it to the device while morsel N computes
        (double-buffered at ``prefetch_depth``). Returns an iterator of
        device morsels; counters accumulate into ``stats``::

            from repro.core.streaming import ScanStats
            stats = ScanStats()
            src = session.catalog.get("lineitem")
            for batch in src.stream(num_workers=1, columns=None,
                                    batch_rows=4096, stats=stats):
                pass                            # compute overlaps next read
            print(stats.prefetch_overlap)       # fraction of I/O hidden

        Sources that predate the morsel API (override ``scan`` only, not
        ``_host_morsels``) are still prefetched: their device batches feed
        the same bounded queue."""
        if (type(self)._host_morsels is TableSource._host_morsels
                and type(self).scan is not TableSource.scan):
            gen = self.scan(num_workers, columns, batch_rows, filter_expr)
        else:
            gen = self._host_morsels(num_workers, columns, batch_rows,
                                     filter_expr, stats=stats)
        return MorselPrefetcher(gen, depth=prefetch_depth, sharding=sharding,
                                stats=stats, host_budget=host_budget)

    def num_rows(self) -> int:
        """Total rows in the table (catalog statistic the optimizer uses)."""
        raise NotImplementedError


def _worker_view(arr: np.ndarray, lo: int, w: int, per_worker: int,
                 cap: int) -> np.ndarray:
    """Rows ``[k * per_worker + lo, k * per_worker + lo + cap)`` of ``arr``
    for each worker ``k < w``, as a read-only ``[w, cap, ...]`` view."""
    s0 = arr.strides[0]
    return np.lib.stride_tricks.as_strided(
        arr[lo:], shape=(w, cap) + arr.shape[1:],
        strides=(per_worker * s0,) + arr.strides, writeable=False)


class InMemoryTable(TableSource):
    """Numpy-backed table; rows are range-partitioned across workers.

    Each worker's rows of a morsel are one contiguous run of every column,
    so a morsel is a read-only strided view of the table and a scan makes
    no host copy. Where the last worker runs out of rows, its view reads
    zero rows kept past the column's end (invalid); that padded copy of a
    scanned column is made once, on the first such scan, and kept."""

    def __init__(self, name: str, data: Dict[str, np.ndarray], schema: dict,
                 unique_keys: tuple = ()):
        self.name = name
        self.data = {k: np.asarray(v, dtype=schema[k].np_dtype())
                     for k, v in data.items()}
        self.schema = dict(schema)
        self.unique_keys = tuple(tuple(u) for u in unique_keys)
        self._n = len(next(iter(self.data.values()))) if self.data else 0
        self._padded: Dict[str, np.ndarray] = {}

    def num_rows(self) -> int:
        return self._n

    def _with_tail(self, name: str, rows: int) -> np.ndarray:
        """Column ``name`` followed by at least ``rows`` zero rows."""
        buf = self._padded.get(name)
        if buf is None or len(buf) - self._n < rows:
            col = self.data[name]
            buf = np.zeros((self._n + rows,) + col.shape[1:], col.dtype)
            buf[:self._n] = col
            self._padded[name] = buf
        return buf

    def _host_morsels(self, num_workers: int, columns, batch_rows: int,
                      filter_expr=None,
                      stats: Optional[ScanStats] = None
                      ) -> Iterator[HostMorsel]:
        cols = list(columns) if columns else list(self.data.keys())
        w = num_workers
        per_worker = math.ceil(self._n / w) if self._n else 1
        n_batches = max(1, math.ceil(per_worker / batch_rows))
        schema = {c: self.schema[c] for c in cols}
        first = per_worker * np.arange(w)
        for b in range(n_batches):
            lo = b * batch_rows
            cap = min(lo + batch_rows, per_worker) - lo
            # rows the last worker's view reads past the table's end
            past = first[-1] + lo + cap - self._n
            valid = np.zeros((w, cap), dtype=bool)
            for k, r in enumerate(np.clip(self._n - first - lo, 0, cap)):
                valid[k, :r] = True
            stacked = {}
            for name in cols:
                arr = (self.data[name] if past <= 0
                       else self._with_tail(name, past))
                stacked[name] = _worker_view(arr, lo, w, per_worker, cap)
                if stats is not None:
                    stats.bytes_read += stacked[name].nbytes
            yield HostMorsel(stacked, valid, schema)


class Catalog:
    """Named ``TableSource`` registry (a Presto connector catalog).

    Every (re-)registration bumps the table's *version*; the scheduler's
    plan/result caches snapshot versions at insert time and treat any bump
    as invalidation, so re-registering a table (new data under the same
    name) can never serve stale cached results.
    """

    def __init__(self):
        self._tables: Dict[str, TableSource] = {}
        self._versions: Dict[str, int] = {}

    def register(self, source: TableSource):
        """Add or replace a table; bumps its version."""
        self._tables[source.name] = source
        self._versions[source.name] = self._versions.get(source.name, 0) + 1

    def register_numpy(self, name: str, data: Dict[str, np.ndarray], schema,
                       unique_keys: tuple = ()):
        """Register a dict of numpy arrays as an ``InMemoryTable``."""
        self.register(InMemoryTable(name, data, schema, unique_keys))

    def get(self, name: str) -> TableSource:
        """Look up a table source; raises ``KeyError`` if unknown."""
        return self._tables[name]

    def tables(self):
        """Names of all registered tables."""
        return list(self._tables)

    def version(self, name: str) -> int:
        """Monotonic registration counter for ``name`` (0 = never seen)."""
        return self._versions.get(name, 0)

    def versions(self, names) -> tuple:
        """Sorted ``(name, version)`` snapshot for cache-validity checks."""
        return tuple(sorted((n, self.version(n)) for n in names))


@dataclasses.dataclass(frozen=True)
class ExecutionOptions:
    """Per-query execution options, accepted uniformly by every entry
    point: ``Session.run``/``submit``/``execute``/``sql``,
    ``QueryBuilder.collect``/``submit``. ``None`` fields inherit the
    session (or call-site) defaults, so ``ExecutionOptions()`` is always a
    no-op::

        opts = ExecutionOptions(num_workers=2, kernel_backend="pallas")
        session.sql("SELECT count(*) AS n FROM orders", options=opts).collect()
        session.run(query, options=ExecutionOptions(priority=2))

    The legacy per-method keywords (``run(query, priority=...)``,
    ``collect(optimize=...)``, ``submit(priority=...)``) remain as thin
    shims; an explicit field here wins over them.
    """

    # scheduler queue priority (submit/run path; higher dequeues first)
    priority: Optional[int] = None
    # worker count for this query only (optimizer exchange placement and
    # the execution context both honor it)
    num_workers: Optional[int] = None
    # kernel backend ('jnp' | 'pallas') for this query only
    kernel_backend: Optional[str] = None
    # run the logical optimizer before execution (default True)
    optimize: Optional[bool] = None
    # runtime-feedback override for this query only: ``True`` enables an
    # ephemeral ``core.feedback.FeedbackStore``, ``False`` disables the
    # session's store, or pass a ``FeedbackStore`` to share across queries
    feedback: Optional[object] = None
    # inter-query batching opt-out for this query only: ``False`` keeps it
    # out of stacked launches even when ``SchedulerConfig.batching`` is on
    # (``True``/``None`` defer to the scheduler config — batching never
    # activates from here alone)
    batching: Optional[bool] = None


@dataclasses.dataclass
class Session:
    """The engine's public entry point: a catalog bound to an execution
    configuration, with both batch and serving entry points.

    Batch path (one query, this thread)::

        from repro.core import Session
        from repro.core.expr import col
        from repro.tpch import dbgen

        session = Session(dbgen.load_catalog(sf=0.002), num_workers=2)
        out = (session.table("lineitem")
               .filter(col("l_quantity") < 10.0)
               .group_by("l_returnflag")
               .agg(n=("count", None))
               .collect())

    Serving path (many queries, scheduled concurrently under a
    device-memory budget, with plan + result caching)::

        from repro.tpch import queries
        h1 = session.submit(queries.build_query(1, session.catalog))
        h6 = session.submit(queries.build_query(6, session.catalog))
        q1, q6 = session.gather(h1, h6)       # morsel pipelines interleave
        out = session.run(queries.build_query(14, session.catalog))

    ``submit``/``gather``/``run`` route through a lazily created
    ``QueryScheduler`` (see ``core.scheduler``); configure it by assigning
    ``session.scheduler_config = SchedulerConfig(...)`` before first use.
    """

    catalog: Catalog
    num_workers: int = 1
    exchange: Optional[ExchangeProtocol] = None
    batch_rows: int = 8192
    host_only_ops: frozenset = frozenset()
    # Mesh with a 'workers' axis. None places one worker per device where
    # the process has a device for every worker (``worker_mesh``), else
    # the workers share one device
    mesh: Optional[object] = None
    # morsel-driven scan pipeline: async storage->device prefetch with a
    # bounded queue of `prefetch_depth` in-flight morsels (False = the
    # synchronous materialize-then-run baseline)
    streaming: bool = True
    prefetch_depth: int = 2
    # physical kernel backend for the hot relational primitives (join
    # probe, segmented aggregation, stream compaction, exchange
    # histogram): 'jnp' (sort-based, the oracle) or 'pallas' (the
    # repro.kernels Pallas kernels; interpret mode off-TPU). None defers
    # to the REPRO_KERNEL_BACKEND env var, defaulting to 'jnp'.
    kernel_backend: Optional[str] = None
    # tiered-memory spill (core.spill): a device-memory budget in bytes
    # turns on out-of-core execution — every query gets a SpillManager and
    # the memory-hungry operators degrade through host buffers and paged
    # disk files instead of exceeding the budget. None = in-memory only.
    device_budget: Optional[int] = None
    # host-tier cap shared by spilled partitions and prefetched morsels
    host_budget: int = 1 << 31
    # directory for paged spill files (None = per-query temp dirs)
    spill_dir: Optional[str] = None
    # hard ceiling for the disk tier (the only tier that rejects work)
    disk_ceiling: int = 1 << 38
    # scheduler knobs (core.scheduler.SchedulerConfig); None = defaults.
    # Assign before the first submit()/run() — the scheduler is built lazily.
    scheduler_config: Optional[object] = None
    # adaptive execution (core.feedback): ``True`` gives the session a
    # ``FeedbackStore`` recording observed per-node cardinalities after
    # every query; the optimizer then re-plans warm runs from those
    # observations (tighter kernel capacities, feedback-driven build-side
    # selection) and the scheduler invalidates cached plans whose
    # estimates drifted. Pass an existing ``FeedbackStore`` to share one
    # across sessions; ``None`` disables adaptivity entirely.
    feedback: Optional[object] = None

    def feedback_store(self):
        """The session's ``core.feedback.FeedbackStore``, or ``None`` when
        adaptivity is off. Normalizes ``feedback=True`` into a concrete
        store on first use (thread-safe; all later calls share it)."""
        fb = self.feedback
        if fb is True:
            with Session._scheduler_lock:
                if self.feedback is True:
                    from .feedback import FeedbackStore
                    self.feedback = FeedbackStore()
                fb = self.feedback
        return fb if fb is not None and fb is not False else None

    def worker_mesh(self):
        """The mesh this session's queries run on: ``mesh`` if given;
        else, for ``num_workers > 1`` in a process with at least that many
        devices, a 1-D ``workers`` mesh of one worker per device, built
        once per session; else ``None``: the workers then share one
        device, and the session logs a warning once."""
        if self.mesh is not None or self.num_workers <= 1:
            return self.mesh
        if "_worker_mesh" not in self.__dict__:
            with Session._scheduler_lock:
                if "_worker_mesh" not in self.__dict__:
                    self._worker_mesh = self._build_worker_mesh()
        return self._worker_mesh

    def _build_worker_mesh(self):
        import jax

        from ..launch.mesh import make_engine_mesh
        n = jax.device_count()
        if n >= self.num_workers:
            return make_engine_mesh(self.num_workers)
        _log.warning("Session(num_workers=%d) found %d device(s): its "
                     "workers share one device", self.num_workers, n)
        return None

    def context(self) -> ExecutionContext:
        """Snapshot this session's execution config for one Driver run
        (each context gets its own per-query ``SpillManager``)."""
        spill = None
        if self.device_budget is not None:
            from .spill import SpillManager
            spill = SpillManager(self.device_budget, self.host_budget,
                                 spill_dir=self.spill_dir,
                                 disk_ceiling=self.disk_ceiling)
        mesh = self.worker_mesh()
        return ExecutionContext(
            catalog=self.catalog,
            num_workers=self.num_workers,
            exchange=self.exchange or ICIExchange(mesh=mesh),
            batch_rows=self.batch_rows,
            host_only_ops=self.host_only_ops,
            mesh=mesh,
            streaming=self.streaming,
            prefetch_depth=self.prefetch_depth,
            kernel_backend=self.kernel_backend,
            spill=spill,
            feedback=self.feedback_store(),
        )

    def _with_options(self, options: Optional[ExecutionOptions]) -> "Session":
        """Session view with per-query overrides applied (direct path)."""
        if options is None:
            return self
        repl = {}
        if options.num_workers is not None:
            repl["num_workers"] = options.num_workers
        if options.kernel_backend is not None:
            repl["kernel_backend"] = options.kernel_backend
        if options.feedback is not None:
            repl["feedback"] = options.feedback
        return dataclasses.replace(self, **repl) if repl else self

    def execute(self, plan: PlanNode,
                options: Optional[ExecutionOptions] = None
                ) -> Dict[str, np.ndarray]:
        """Execute one plan on this thread; returns name -> numpy column.

        This is the direct batch path: no admission control, no caches.
        Serving workloads should prefer ``run``/``submit``, which route
        through the scheduler. ``options`` applies per-query
        ``num_workers``/``kernel_backend`` overrides (``priority`` is
        meaningless here; ``optimize`` is the caller's job — ``execute``
        runs the plan exactly as given).
        """
        driver = Driver(self._with_options(options).context())
        self.last_driver = driver
        return driver.collect(plan)

    # -- serving entry points (core.scheduler) ------------------------------
    # guards lazy scheduler creation: N client threads whose first call is
    # submit() must all get the same scheduler (one budget, one cache)
    _scheduler_lock = threading.Lock()

    def scheduler(self):
        """The session's ``QueryScheduler`` (created on first use).

        Configure with ``session.scheduler_config = SchedulerConfig(...)``
        before the first call; later assignments require ``reset_scheduler``.
        """
        sched = getattr(self, "_scheduler", None)
        if sched is None:
            with Session._scheduler_lock:
                sched = getattr(self, "_scheduler", None)
                if sched is None:
                    from .scheduler import QueryScheduler
                    sched = QueryScheduler(self, self.scheduler_config)
                    self._scheduler = sched
        return sched

    def reset_scheduler(self) -> None:
        """Drop the current scheduler (and its caches/queue) if any."""
        sched = getattr(self, "_scheduler", None)
        if sched is not None:
            sched.close(wait=False)
            self._scheduler = None

    def submit(self, query, priority: int = 0,
               options: Optional[ExecutionOptions] = None):
        """Submit a query for scheduled execution; returns a ``QueryHandle``.

        ``query`` is a ``PlanNode`` or a ``QueryBuilder`` (its plan is
        taken as-built; the scheduler optimizes through the plan cache —
        for SQL-frontend builders the originating SQL text prefixes the
        cache keys). ``options`` carries per-query overrides
        (``ExecutionOptions``); a builder from ``session.sql(...,
        options=...)`` brings its own unless overridden here. Raises
        ``QueryRejected`` when admission control refuses it::

            h = session.submit(session.table("lineitem").limit(5), priority=1)
            rows = h.result()
        """
        plan = query.plan if hasattr(query, "plan") else query
        if options is None:
            options = getattr(query, "_options", None)
        sql = getattr(query, "sql_text", None)
        opts = options or ExecutionOptions()
        if opts.priority is not None:
            priority = opts.priority
        return self.scheduler().submit(
            plan, priority=priority, sql=sql,
            num_workers=opts.num_workers,
            kernel_backend=opts.kernel_backend,
            optimize=opts.optimize,
            feedback=opts.feedback,
            batching=opts.batching)

    def gather(self, *handles) -> list:
        """Wait for ``submit`` handles; results in argument order."""
        return self.scheduler().gather(*handles)

    def run(self, query, priority: int = 0,
            options: Optional[ExecutionOptions] = None
            ) -> Dict[str, np.ndarray]:
        """Synchronous scheduled execution: ``submit`` + ``result``.

        Unlike ``execute``, this path gets admission control and the
        plan/result caches — repeated identical queries are served from
        cache until a referenced table is re-registered. ``options``
        applies per-query ``ExecutionOptions`` overrides.
        """
        return self.submit(query, priority=priority,
                           options=options).result()

    def executor_stats(self) -> Dict[str, object]:
        """Stats from the most recent ``execute`` (scan counters, operator
        host times, kernel dispatch counts).

        Before any query has run this returns the same *shape* a Driver
        reports — every key present, empty values — so callers can index
        ``stats['kernel_dispatch']``/``stats['feedback']`` unconditionally
        on both the direct and the scheduler path. The ``feedback`` entry
        always reflects the session's live store (it accumulates across
        queries, unlike the per-query driver stats).
        """
        driver = getattr(self, "last_driver", None)
        stats = (driver.executor_stats() if driver is not None
                 else empty_executor_stats())
        fb = self.feedback_store()
        if fb is not None:
            stats["feedback"] = fb.summary()
        return stats

    # -- fluent frontend + optimizer entry points ---------------------------
    def table(self, name: str, columns=None):
        """Start a fluent query on a catalog table; ``.collect()`` runs it
        through the logical optimizer and this session's driver."""
        from .builder import QueryBuilder
        return QueryBuilder.scan(self.catalog, name, columns, session=self)

    def sql(self, text: str, options: Optional[ExecutionOptions] = None,
            dialect: Optional[str] = None):
        """Parse SQL text into a session-bound ``QueryBuilder``.

        The returned builder is indistinguishable from a hand-built one —
        ``.collect()``, ``.submit()``, ``.explain(analyze=True)`` all work,
        and the optimizer/scheduler treat it identically (the SQL text
        additionally prefixes the scheduler's plan/result cache keys)::

            out = session.sql(
                "SELECT l_returnflag, count(*) AS n FROM lineitem "
                "GROUP BY l_returnflag ORDER BY l_returnflag").collect()

        Unsupported constructs raise ``SqlUnsupportedError`` naming the
        offending node; syntax errors raise ``SqlParseError``; unknown
        tables/columns raise ``SchemaError``. ``dialect`` transpiles
        foreign dialects via the optional ``sqlglot`` dependency (the
        ``[sql]`` extra). ``options`` attaches per-query
        ``ExecutionOptions`` that ``collect``/``submit`` pick up.
        """
        from .sql import lower_sql
        with tracing.span("repro.plan.sql"):
            qb = lower_sql(text, self.catalog, session=self, dialect=dialect)
        qb._options = options
        return qb

    def optimizer_config(self):
        """This session's ``OptimizerConfig`` (worker count threaded in so
        exchange placement plans for the session's cluster size)."""
        from .optimizer import DEFAULT_CONFIG
        return dataclasses.replace(DEFAULT_CONFIG,
                                   num_workers=self.num_workers,
                                   feedback=self.feedback_store())

    def optimize(self, plan: PlanNode) -> PlanNode:
        """Run the rule-based logical optimizer over a plan tree. With
        ``num_workers > 1`` this includes physical exchange placement: the
        returned tree is a distributed fragment plan with explicit
        ``Repartition``/``Broadcast`` nodes."""
        from .optimizer import optimize
        return optimize(plan, self.catalog, config=self.optimizer_config())

    def explain(self, plan: PlanNode, analyze: bool = False) -> str:
        """Pretty-print a plan before and after optimization.

        .. deprecated::
            Prefer ``QueryBuilder.explain(analyze=...)`` — builder and SQL
            queries share that one explain surface and delegate here. This
            plan-first form is kept for callers holding a bare ``PlanNode``.

        With ``analyze=True`` the (optimized) plan is also executed and the
        executor's per-table scan stats -- bytes read, bytes transferred,
        chunks skipped by zone maps, prefetch-overlap fraction -- plus
        each operator's host dispatch seconds (``op_host_seconds``: host
        time of its own calls, not device time), the streaming
        aggregation's partials, merges and rows merged (``agg``), the
        jnp join's probes by lookup and largest index (``join``), the
        device programs traced per family (``traces``; none once warm),
        per-fragment exchange stats (rows/bytes moved,
        host-staged bytes per Repartition/Broadcast), the per-operator
        memory-footprint breakdown, and -- when a ``device_budget`` is set
        -- the spill-cost estimate and observed per-tier spill counters
        are appended (EXPLAIN ANALYZE)."""
        from .optimizer import (estimate_memory_breakdown,
                                explain_before_after)
        text = explain_before_after(plan, self.catalog,
                                    config=self.optimizer_config())
        if not analyze:
            return text
        optimized = self.optimize(plan)
        breakdown = estimate_memory_breakdown(
            optimized, self.catalog, num_workers=self.num_workers,
            batch_rows=self.batch_rows, prefetch_depth=self.prefetch_depth)
        self.execute(optimized)
        lines = ["== executor stats =="]
        stats = self.executor_stats()
        for tname, s in sorted(stats.get("tables", {}).items()):
            lines.append(
                f"scan {tname}: morsels={s['morsels']} "
                f"chunks={s['chunks_total']} "
                f"chunks_skipped={s['chunks_skipped']} "
                f"bytes_read={s['bytes_read']} "
                f"bytes_transferred={s['bytes_transferred']} "
                f"prefetch_overlap={s['prefetch_overlap']:.2f}")
        for op, sec in sorted(stats.get("op_host_seconds", {}).items()):
            lines.append(f"op_host_seconds {op}: {sec:.4f}s")
        kd = stats.get("kernel_dispatch") or {}
        if kd:
            lines.append(
                f"kernels [{stats.get('kernel_backend')}]: "
                + " ".join(f"{k}={v}" for k, v in sorted(kd.items())))
        agg = stats.get("agg") or {}
        if agg.get("partials"):
            lines.append("agg: " + " ".join(f"{k}={v}"
                                            for k, v in agg.items()))
        join = stats.get("join") or {}
        if join.get("dense_probes") or join.get("sorted_probes"):
            lines.append("join: " + " ".join(f"{k}={v}"
                                             for k, v in join.items()))
        traces = stats.get("traces") or {}
        lines.append("traces: " + (" ".join(
            f"{k}={v}" for k, v in sorted(traces.items())) or "none"))
        for frag, ex in stats.get("exchanges", {}).items():
            lines.append(
                f"exchange {frag} [{stats.get('exchange_protocol')}]: "
                f"rounds={ex['rounds']} rows_moved={ex['rows_moved']} "
                f"bytes_moved={ex['bytes_moved']} "
                f"host_staged_bytes={ex['host_staged_bytes']} "
                f"{ex['seconds']:.4f}s")
        lines.append("== memory ==")
        lines.extend(breakdown.describe(self.device_budget,
                                        self.host_budget).splitlines())
        spill = stats.get("spill") or {}
        if spill:
            lines.append(
                f"spill: reserved_peak={spill['reserved_peak']} "
                f"reserve_denials={spill['reserve_denials']} "
                f"staged_exchanges={stats.get('spill_staged_exchanges', 0)}")
            for tier in ("host", "disk"):
                t = spill[tier]
                lines.append(
                    f"spill {tier} tier: spilled_bytes={t['spilled_bytes']} "
                    f"restored_bytes={t['restored_bytes']} "
                    f"spills={t['spills']} restores={t['restores']}")
        return text + "\n" + "\n".join(lines)
