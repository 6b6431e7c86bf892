"""Concurrent query scheduler: admission control + plan/result caching.

The paper's Presto integration is a *serving* system: the coordinator admits
many concurrent queries and the GPU workers multiplex them under a fixed
device-memory budget. This module is that layer for the repro engine — it
turns the one-query-at-a-time ``Session`` into a serving engine:

* **Admission control** — every query's peak device-memory footprint is
  estimated from its optimized plan (``optimizer.estimate_memory_breakdown``:
  scan prefetch windows, ``max_groups``/``max_matches`` capacities, join
  build sides). Queries are admitted only while the sum of in-flight
  estimates fits ``SchedulerConfig.memory_budget``; the rest wait in a
  bounded priority queue. A query whose footprint exceeds the whole budget
  is **admitted with spilling** instead of rejected: it runs under a
  per-query ``core.spill.SpillManager`` (the tiered-memory hierarchy:
  device reservations -> pinned host buffers -> paged disk files) and pays
  a priced slowdown (``QueryHandle.spill_plan``). Only a footprint past
  ``SchedulerConfig.spill_disk_ceiling`` — beyond what even the disk tier
  absorbs — or a full wait queue is rejected (``QueryRejected``), so
  callers get backpressure instead of unbounded latency; the rejection
  message carries the per-operator footprint breakdown and spill-cost
  estimate so it is explainable from the exception alone.

* **Interleaved execution** — admitted queries run on a pool of
  ``max_concurrency`` worker threads, each driving its own ``Driver``.
  Because every scan goes through ``MorselPrefetcher`` (a background
  storage-read + device-put thread per scan), the morsel pipelines of
  different queries overlap: query A's operators compute while query B's
  scan reads from storage.

* **Plan cache** — optimization is skipped for repeated query *shapes*:
  the canonicalized logical plan (``plan.fingerprint``) maps to its
  optimized tree. Entries snapshot the versions of every referenced table
  (optimizer decisions depend on catalog stats) and are invalidated when a
  table is re-registered.

* **Result cache** — a bounded LRU from plan fingerprint to collected
  result, also version-snapshotted: re-registering any referenced table
  invalidates the entry (the tests cover exactly this). Hits complete
  without reserving memory or occupying a worker. Identical queries
  submitted *while one is still in flight* coalesce onto the running
  handle instead of executing twice.

Entry points live on ``Session``: ``submit()`` returns a ``QueryHandle``
future, ``gather()`` awaits many, ``run()`` is the synchronous wrapper.
``examples/serve_queries.py`` demonstrates N concurrent TPC-H clients;
the benchmark cells under ``bench/`` measure throughput on the chip.
"""

from __future__ import annotations

import dataclasses
import hashlib
import heapq
import itertools
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

from ..kernels import ops as kernel_ops
from . import batch as _batch
from . import plan as P
from . import tracing
from ..kernels import segmented_agg as _segagg
from .driver import Driver, empty_executor_stats
from .feedback import qerror
from .optimizer import estimate_memory_breakdown, feedback_estimates, optimize


class QueryRejected(RuntimeError):
    """Admission control refused the query (footprint beyond even the
    spill disk ceiling, or queue full). The message carries the
    per-operator footprint breakdown and spill-cost estimate."""


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Knobs for admission control and the two caches.

    The defaults suit a CPU-JAX dev box; a real deployment sets
    ``memory_budget`` to the device's free HBM and ``max_concurrency`` to
    the number of independent query pipelines the device can overlap.
    """

    # total device-memory budget admitted queries may collectively pin
    memory_budget: int = 1 << 30
    # worker threads driving admitted queries (concurrent pipelines)
    max_concurrency: int = 8
    # bounded wait queue: submits beyond this are rejected (backpressure)
    max_queue: int = 64
    # LRU capacities for the two caches (entries, not bytes)
    plan_cache_size: int = 64
    result_cache_size: int = 64
    # serve repeated identical queries from the result cache
    cache_results: bool = True
    # anti-starvation: after the queue head has been passed over this many
    # times for smaller queries, backfilling stops until the head fits
    max_head_skips: int = 16
    # tiered-memory spill for over-budget queries (core.spill): host-tier
    # cap, the only hard limit (footprint past the disk ceiling rejects),
    # and where the paged spill files go (None = per-query temp dirs)
    spill_host_budget: int = 1 << 31
    spill_disk_ceiling: int = 1 << 38
    spill_dir: Optional[str] = None
    # inter-query batching (core.batch): when True, a worker that dequeues
    # a batchable query (single-table filter/project/agg shape, W=1, no
    # feedback store, no spill) waits up to batch_window_ms for compatible
    # pending queries — same interned program, kernel backend, and catalog
    # snapshot — and launches up to max_batch of them as ONE stacked
    # execution, splitting results per handle on the way out. Strictly
    # opt-in: when False no query grows batch state and the dispatch path
    # is byte-for-byte the solo one.
    batching: bool = False
    batch_window_ms: float = 2.0
    max_batch: int = 16
    # adaptive re-planning: a cached plan whose believed cardinalities
    # (static bounds, or the feedback observations it was planned from)
    # miss the fresh post-execution observations by more than this q-error
    # is evicted from the plan cache, so the next identical submit
    # re-optimizes against the updated feedback store. Feedback-planned
    # entries converge (estimate == observation) and stay cached.
    feedback_qerror_limit: float = 4.0


class QueryHandle:
    """Future-style handle for one submitted query.

    ``result()`` blocks until the query finishes and returns the collected
    numpy dict (or re-raises the query's error / rejection). Timing fields
    (``submitted_at``/``started_at``/``finished_at``, absent until reached)
    let callers derive queue wait and run time; ``cache_hit`` says the
    result came from the result cache.
    """

    def __init__(self, query_id: int, plan: P.PlanNode, priority: int,
                 estimate: int):
        self.query_id = query_id
        self.plan = plan
        self.priority = priority
        self.estimate = estimate       # bytes charged against the budget
        self.footprint = estimate      # un-capped estimated peak footprint
        # optimizer.MemoryEstimate per-operator breakdown (None for
        # result-cache hits, which never reach estimation)
        self.memory_breakdown = None
        # admit-with-spill pricing (spill_cost dict) when the footprint
        # exceeded the memory budget; None for in-budget queries
        self.spill_plan: Optional[Dict] = None
        self.cache_hit = False
        self.plan_cache_hit = False
        # kernel backend pinned at submit time (None until admitted)
        self.kernel_backend: Optional[str] = None
        # worker count pinned at submit time (exchange placement and the
        # plan/result cache keys depend on it)
        self.num_workers: int = 1
        self._queue_skips = 0          # times passed over by backfilling
        self._versions: tuple = ()     # admission-time catalog snapshot
        # adaptive execution: the feedback store resolved at submit time,
        # the plan-cache key of the optimized entry, and the cardinalities
        # the plan was optimized under (store key -> believed rows) — the
        # post-execution q-error check compares these against the fresh
        # observations and evicts the cached plan when they drifted
        self._feedback = None
        self._plan_key: str = ""
        self._est_map: Dict[str, int] = {}
        # inter-query batching: the extracted stacked-program membership
        # (core.batch.BatchShape) and the compatibility key the worker
        # groups on — (interned program identity, kernel backend); both
        # None when batching is off or the plan is ineligible
        self._batch_shape = None
        self._batch_key: Optional[tuple] = None
        self.submitted_at = time.perf_counter()
        self.started_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        # same key shape as driver.empty_executor_stats() until the query
        # runs, so callers can index the dict without a done() check
        self.executor_stats: Dict[str, object] = empty_executor_stats()
        self._done = threading.Event()
        self._result: Optional[Dict] = None
        self._error: Optional[BaseException] = None

    # -- completion (scheduler side) ----------------------------------------
    def _complete(self, result=None, error=None) -> None:
        self._result, self._error = result, error
        self.finished_at = time.perf_counter()
        self._done.set()

    # -- consumption (client side) ------------------------------------------
    def done(self) -> bool:
        """True once the query finished (successfully or not)."""
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> Dict:
        """Block until finished; return the collected columns dict.

        Re-raises the query's exception on failure; raises ``TimeoutError``
        if ``timeout`` (seconds) elapses first. The returned arrays may be
        shared with the result cache and coalesced handles — treat them as
        read-only.
        """
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"query {self.query_id} still running after {timeout}s")
        if self._error is not None:
            raise self._error
        return self._result

    @property
    def latency(self) -> Optional[float]:
        """Submit-to-finish seconds (None while still running)."""
        if self.finished_at is None:
            return None
        return self.finished_at - self.submitted_at


class _VersionedLRU:
    """Bounded LRU whose entries carry a catalog-version snapshot.

    A lookup re-validates the snapshot against the live catalog; any bumped
    table version evicts the entry (re-registered table == new data).
    Internally locked: client threads get/put concurrently with workers.
    """

    def __init__(self, capacity: int):
        self.capacity = max(int(capacity), 0)
        self._od: "OrderedDict[str, Tuple[tuple, object]]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key: str, catalog):
        with self._lock:
            entry = self._od.get(key)
            if entry is not None:
                versions, value = entry
                if catalog.versions([n for n, _ in versions]) == versions:
                    self._od.move_to_end(key)
                    self.hits += 1
                    return value
                del self._od[key]       # stale: a table was re-registered
            self.misses += 1
            return None

    def put(self, key: str, versions: tuple, value) -> None:
        if self.capacity == 0:
            return
        with self._lock:
            self._od[key] = (versions, value)
            self._od.move_to_end(key)
            while len(self._od) > self.capacity:
                self._od.popitem(last=False)

    def invalidate(self, key: str) -> None:
        """Drop ``key`` if present (the adaptive q-error eviction path)."""
        with self._lock:
            self._od.pop(key, None)

    def __len__(self) -> int:
        with self._lock:
            return len(self._od)


def referenced_tables(plan: P.PlanNode) -> List[str]:
    """Catalog tables a plan reads (cache-invalidation scope)."""
    names: List[str] = []

    def visit(node: P.PlanNode) -> None:
        if isinstance(node, P.TableScan):
            names.append(node.table)
        for c in node.children():
            visit(c)

    visit(plan)
    return sorted(set(names))


class QueryScheduler:
    """Admits, caches, and concurrently executes queries for one Session.

    Example (synchronous clients are threads; the scheduler interleaves
    their pipelines)::

        from repro.core import Session
        from repro.core.scheduler import SchedulerConfig
        from repro.tpch import dbgen, queries

        session = Session(dbgen.load_catalog(sf=0.002))
        session.scheduler_config = SchedulerConfig(memory_budget=256 << 20)
        handles = [session.submit(queries.build_query(q, session.catalog))
                   for q in (1, 6, 14)]
        results = session.gather(*handles)   # list of numpy dicts

    Thread-safe; one instance serves arbitrarily many client threads.
    """

    def __init__(self, session, config: Optional[SchedulerConfig] = None):
        self.session = session
        self.config = config or SchedulerConfig()
        self.plan_cache = _VersionedLRU(self.config.plan_cache_size)
        self.result_cache = _VersionedLRU(
            self.config.result_cache_size if self.config.cache_results else 0)
        self._cond = threading.Condition()
        self._pending: List[Tuple[int, int, QueryHandle]] = []   # heap
        self._mem_in_use = 0
        self._running = 0
        self._closed = False
        self._seq = itertools.count()
        self._ids = itertools.count(1)
        self._threads: List[threading.Thread] = []
        # in-flight coalescing: fingerprint -> queued/running handle, so N
        # simultaneous identical queries execute once and share the result
        self._inflight: Dict[str, QueryHandle] = {}
        # served-query counters (exposed via stats())
        self.completed = 0
        self.failed = 0
        self.rejected = 0
        self.coalesced = 0
        self.spill_admitted = 0
        self.batches = 0           # stacked launches (>= 2 members each)
        self.batched_queries = 0   # queries served via a stacked launch
        self.batch_failures = 0    # stacked launches re-run solo after an error
        self.first_batch_failure: Optional[str] = None

    # -- public API ---------------------------------------------------------
    def submit(self, plan: P.PlanNode, priority: int = 0,
               sql: Optional[str] = None,
               num_workers: Optional[int] = None,
               kernel_backend: Optional[str] = None,
               optimize: Optional[bool] = None,
               feedback: Optional[object] = None,
               batching: Optional[bool] = None) -> QueryHandle:
        """Admit ``plan`` for execution; returns a ``QueryHandle``.

        Raises ``QueryRejected`` when the query could never fit the memory
        budget, or when the wait queue is full (backpressure). Higher
        ``priority`` dequeues first; ties run in submission order. A
        duplicate of an in-flight query coalesces onto its handle (raising
        that handle's queue priority if the duplicate's is higher).

        ``sql``/``num_workers``/``kernel_backend``/``optimize`` carry
        per-query ``ExecutionOptions`` overrides: queries born from SQL
        text prefix their plan/result cache keys with a hash of that text,
        worker-count and backend overrides are pinned on the handle (and
        keyed), and ``optimize=False`` runs the raw plan as-is.

        ``batching=False`` opts this query out of inter-query batching
        even when ``SchedulerConfig.batching`` is on (it has no effect
        when the config flag is off — batching is strictly opt-in).
        """
        query_id = next(self._ids)
        with tracing.query(query_id), tracing.span("repro.sched.submit"):
            return self._admit(query_id, plan, priority, sql, num_workers,
                               kernel_backend, optimize, feedback, batching)

    def _admit(self, query_id: int, plan: P.PlanNode, priority: int,
               sql: Optional[str], num_workers: Optional[int],
               kernel_backend: Optional[str], optimize: Optional[bool],
               feedback: Optional[object],
               batching: Optional[bool]) -> QueryHandle:
        """``submit``'s body: optimize, estimate memory, admit."""
        # the kernel backend is resolved ONCE, here at submit time (the
        # per-query override, else the session's setting, else the
        # submitting thread's use_backend() scope / env default), and
        # pinned on the handle: the worker's ExecutionContext executes
        # with exactly this backend, and the cache keys carry it -- so
        # flipping the backend between submit and execution can never
        # serve (or store) a result under the wrong backend's key, and
        # ``with use_pallas(): session.run(q)`` behaves like the batch
        # path
        backend = (kernel_backend
                   or self.session.kernel_backend
                   or kernel_ops.current_backend())
        w = num_workers if num_workers is not None \
            else self.session.num_workers
        # adaptive execution: resolve the feedback store once, here, and
        # pin it on the handle (the per-query override, else the session's
        # store). True means an ephemeral per-query store; False disables
        # the session store for this query.
        if feedback is None:
            fb = self.session.feedback_store()
        elif feedback is True:
            from .feedback import FeedbackStore
            fb = FeedbackStore()
        elif feedback is False:
            fb = None
        else:
            fb = feedback
        # SQL-born queries prefix their cache keys with the text's hash:
        # two different SQL texts that happen to lower to the same logical
        # plan still share nothing, so a frontend fix that changes the
        # lowering can never serve a stale result cached under the old
        # reading of the same text
        sql_prefix = ""
        if sql is not None:
            digest = hashlib.sha1(sql.encode("utf-8")).hexdigest()[:16]
            sql_prefix = f"sql={digest}:"
        # the feedback flag is part of the key: a warm (feedback-planned)
        # tree and the static plan of the same query differ, so neither
        # cache may serve one where the other was requested
        key = (f"{sql_prefix}w{w}:k={backend}:fb{int(fb is not None)}:"
               f"{P.fingerprint(plan)}")
        # result cache first: a hit skips optimization entirely
        cached = self.result_cache.get(key, self.session.catalog)
        if cached is not None:
            handle = QueryHandle(query_id, plan, priority, 0)
            handle.kernel_backend = backend
            handle.num_workers = w
            handle.cache_hit = True
            handle.started_at = time.perf_counter()
            handle._complete(result=cached)
            with self._cond:
                self.completed += 1
            return handle

        if optimize is False:
            optimized, est_map, plan_hit = plan, {}, False
        else:
            optimized, est_map, plan_hit = self._optimized(plan, key, w, fb)
        try:
            breakdown = estimate_memory_breakdown(
                optimized, self.session.catalog,
                num_workers=w,
                batch_rows=self.session.batch_rows,
                prefetch_depth=self.session.prefetch_depth,
                feedback=fb)
            est = breakdown.total
        except TypeError:
            if optimize is not False:
                raise
            # un-optimized plans may lack derived capacities; admit them
            # conservatively with no estimate rather than refuse
            breakdown, est = None, 0
        # over-budget queries are admitted with spilling: they charge the
        # whole budget (running effectively alone) and degrade through the
        # host/disk tiers instead of being refused
        handle = QueryHandle(query_id, optimized, priority,
                             min(est, self.config.memory_budget))
        handle.footprint = est
        handle.memory_breakdown = breakdown
        handle.plan_cache_hit = plan_hit
        handle.kernel_backend = backend
        handle.num_workers = w
        handle._feedback = fb
        handle._plan_key = "opt:" + key
        handle._est_map = est_map
        # version snapshot taken NOW: if a table is re-registered while the
        # query runs, the snapshot no longer matches at the next lookup and
        # the (stale) result is never served from cache
        handle._versions = self.session.catalog.versions(
            referenced_tables(optimized))

        if est > self.config.spill_disk_ceiling:
            with self._cond:
                self.rejected += 1
            raise QueryRejected(
                f"query footprint ~{est} B exceeds the scheduler's "
                f"memory budget of {self.config.memory_budget} B and the "
                f"spill disk ceiling of {self.config.spill_disk_ceiling} B; "
                f"raise SchedulerConfig.spill_disk_ceiling or shrink the "
                "query\n"
                + breakdown.describe(self.config.memory_budget,
                                     self.config.spill_host_budget))
        if est > self.config.memory_budget:
            handle.spill_plan = breakdown.spill_cost(
                self.config.memory_budget, self.config.spill_host_budget)
            with self._cond:
                self.spill_admitted += 1
        # inter-query batching: only when the config opts in (so the
        # disabled path never even inspects the plan), the query didn't
        # opt out, and the execution mode is the simple one a stacked
        # launch can reproduce exactly — optimized W=1 plan, no feedback
        # store (batched runs skip feedback harvesting), no spill
        if (self.config.batching and batching is not False
                and optimize is not False and fb is None
                and handle.spill_plan is None and w == 1):
            shape = _batch.extract_shape(optimized)
            if shape is not None:
                handle._batch_shape = shape
                handle._batch_key = (shape.program, backend)
        with self._cond:
            if self._closed:
                raise RuntimeError("scheduler is closed")
            if self.config.cache_results:
                existing = self._inflight.get(key)
                if (existing is not None and not existing.done()
                        and self.session.catalog.versions(
                            [n for n, _ in existing._versions])
                        == existing._versions):
                    # identical query already queued/running against
                    # still-current table versions: share its handle
                    # instead of executing twice (request coalescing);
                    # a more urgent duplicate promotes the queued entry.
                    # A version mismatch (table re-registered since the
                    # in-flight query was admitted) falls through to a
                    # fresh execution — coalescing never serves stale data.
                    self.coalesced += 1
                    if priority > existing.priority:
                        existing.priority = priority
                        for i, (_, seq, h) in enumerate(self._pending):
                            if h is existing:
                                self._pending[i] = (-priority, seq, h)
                                heapq.heapify(self._pending)
                                break
                    return existing
            if len(self._pending) >= self.config.max_queue:
                self.rejected += 1
                raise QueryRejected(
                    f"wait queue full ({self.config.max_queue} queries); "
                    f"retry later (backpressure)")
            handle._result_key = key
            self._inflight[key] = handle
            heapq.heappush(self._pending,
                           (-priority, next(self._seq), handle))
            self._ensure_workers()
            self._cond.notify_all()
        return handle

    def gather(self, *handles: QueryHandle) -> List[Dict]:
        """Wait for every handle; returns results in argument order.

        Re-raises the first failed query's exception (after all have
        finished, so no work is silently abandoned).
        """
        for h in handles:
            h._done.wait()
        return [h.result() for h in handles]

    def run(self, plan: P.PlanNode, priority: int = 0) -> Dict:
        """Synchronous submit-and-wait (the serving path for one query)."""
        return self.submit(plan, priority).result()

    def stats(self) -> Dict[str, object]:
        """Served/rejected counters and cache hit/miss totals, plus the
        number of stacked launches that failed and were re-run solo and
        the first such failure's text (None while there is none)."""
        with self._cond:
            return {
                "completed": self.completed,
                "failed": self.failed,
                "rejected": self.rejected,
                "coalesced": self.coalesced,
                "spill_admitted": self.spill_admitted,
                "batches": self.batches,
                "batched_queries": self.batched_queries,
                "batch_failures": self.batch_failures,
                "first_batch_failure": self.first_batch_failure,
                "queued": len(self._pending),
                "running": self._running,
                "mem_in_use": self._mem_in_use,
                "plan_cache_hits": self.plan_cache.hits,
                "plan_cache_misses": self.plan_cache.misses,
                "result_cache_hits": self.result_cache.hits,
                "result_cache_misses": self.result_cache.misses,
            }

    def close(self, wait: bool = True) -> None:
        """Stop accepting queries; optionally wait for workers to drain."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if wait:
            for t in self._threads:
                t.join(timeout=30.0)

    # -- internals ----------------------------------------------------------
    def _optimized(self, plan: P.PlanNode, raw_key: str, w: int,
                   fb: Optional[object]
                   ) -> Tuple[P.PlanNode, Dict[str, int], bool]:
        """Optimized plan via the plan cache. ``raw_key`` already carries
        the SQL-text prefix (when the query came from ``Session.sql``), the
        planned worker count (exchange placement makes the physical plan
        W-dependent), the backend, the feedback flag, and the raw tree's
        fingerprint. Versions are snapshot *before* optimization, which
        reads catalog stats. Entries store ``(optimized, est_map)`` where
        ``est_map`` is the per-node cardinality belief the plan was
        derived under (``optimizer.feedback_estimates``); the q-error
        check after execution compares it against fresh observations."""
        key = "opt:" + raw_key
        cached = self.plan_cache.get(key, self.session.catalog)
        if cached is not None:
            optimized, est_map = cached
            return optimized, est_map, True
        versions = self.session.catalog.versions(referenced_tables(plan))
        config = dataclasses.replace(self.session.optimizer_config(),
                                     num_workers=w, feedback=fb)
        optimized = optimize(plan, self.session.catalog, config=config)
        est_map = (feedback_estimates(optimized, self.session.catalog, config)
                   if fb is not None else {})
        self.plan_cache.put(key, versions, (optimized, est_map))
        return optimized, est_map, False

    def _ensure_workers(self) -> None:
        """Lazily grow the worker pool up to ``max_concurrency`` (held lock)."""
        alive = sum(1 for t in self._threads if t.is_alive())
        want = min(self.config.max_concurrency,
                   len(self._pending) + self._running)
        for i in range(alive, want):
            t = threading.Thread(target=self._worker, daemon=True,
                                 name=f"query-sched-{i}")
            t.start()
            self._threads.append(t)

    def _pick(self) -> Optional[QueryHandle]:
        """Highest-priority pending query that fits the remaining budget
        (held lock). Skipping an over-budget head is deadlock-free: when
        nothing is running the full budget is free, and submit() already
        rejected anything larger than that. To prevent a big head being
        starved by a stream of small backfills, a head that has been
        skipped ``max_head_skips`` times blocks further backfilling until
        it fits (the budget drains as running queries finish)."""
        if not self._pending:
            return None
        remaining = self.config.memory_budget - self._mem_in_use
        head = min(self._pending)               # heap order: priority, FIFO
        if head[2].estimate <= remaining:
            entry = head
        else:
            if head[2]._queue_skips >= self.config.max_head_skips:
                return None                     # drain until the head fits
            fits = [e for e in self._pending if e[2].estimate <= remaining]
            if not fits:
                return None
            # the head is genuinely passed over for a smaller query: only
            # real backfills age it, not idle worker polls
            head[2]._queue_skips += 1
            entry = min(fits)
        self._pending.remove(entry)
        heapq.heapify(self._pending)
        return entry[2]

    def _worker(self) -> None:
        while True:
            with self._cond:
                handle = self._pick()
                while handle is None:
                    if self._closed and not self._pending:
                        return
                    self._cond.wait(timeout=0.1)
                    handle = self._pick()
                self._mem_in_use += handle.estimate
                self._running += 1
                members = [handle]
                if self.config.batching and handle._batch_key is not None:
                    members += self._claim_batch(handle)
            try:
                if len(members) > 1:
                    self._execute_batch(members)
                else:
                    self._execute(handle)
            finally:
                with self._cond:
                    for m in members:
                        self._mem_in_use -= m.estimate
                        self._running -= 1
                        if self._inflight.get(m._result_key) is m:
                            del self._inflight[m._result_key]
                    self._cond.notify_all()

    def _claim_batch(self, leader: QueryHandle) -> List[QueryHandle]:
        """Claim pending queries compatible with ``leader`` for one stacked
        launch (held lock). Compatibility is the leader's batch key — the
        interned program identity (which encodes table, columns, stage
        shape, aggregation, and W=1) plus the kernel backend — and an
        identical catalog-version snapshot, so a batch can never mix data
        generations. The worker waits up to ``batch_window_ms`` for
        stragglers; a keyed aggregation caps the batch at
        ``kernels.segmented_agg.stacked_group_capacity`` so the stacked
        segmented problem stays inside the kernel dispatch bound (a query
        whose ``max_groups`` alone exceeds it degrades to solo execution).
        Claimed members charge their full admission estimates — a
        conservative over-charge, since the stacked run shares one scan."""
        limit = self._batch_limit(leader._batch_shape.program)
        members: List[QueryHandle] = []
        deadline = time.perf_counter() + self.config.batch_window_ms / 1000.0
        while True:
            if len(members) + 1 < limit:
                claimed = []
                for entry in self._pending:
                    h = entry[2]
                    if (h._batch_key == leader._batch_key
                            and h._versions == leader._versions):
                        claimed.append(entry)
                        if len(members) + 1 + len(claimed) >= limit:
                            break
                for entry in claimed:
                    self._pending.remove(entry)
                    h = entry[2]
                    self._mem_in_use += h.estimate
                    self._running += 1
                    members.append(h)
                if claimed:
                    heapq.heapify(self._pending)
            if len(members) + 1 >= limit:
                break
            remaining = deadline - time.perf_counter()
            if remaining <= 0:
                break
            # releases the lock: submits land while we wait, and the loop
            # top sweeps them up (one final sweep after the window closes)
            self._cond.wait(remaining)
        return members

    def _batch_limit(self, program) -> int:
        """Per-program member cap for one stacked launch: ``max_batch``,
        tightened for keyed aggregations so the stacked segmented problem
        (``lanes * max_groups`` groups) stays inside the kernel dispatch
        bound."""
        limit = self.config.max_batch
        if program.group_keys:
            limit = min(limit,
                        _segagg.stacked_group_capacity(program.max_groups))
        return limit

    def _execute_batch(self, members: List[QueryHandle]) -> None:
        """Run a claimed group as ONE stacked execution, scattering the
        per-member results (and per-query stats attribution) back onto
        each handle. Any stacked failure falls back to per-member solo
        execution — a query that would succeed alone must never receive
        a batched error. Its spans carry the first member's query id."""
        with tracing.query(members[0].query_id), \
                tracing.span("repro.sched.run", queries=len(members)):
            self._run_batch(members)

    def _run_batch(self, members: List[QueryHandle]) -> None:
        t_launch = time.perf_counter()
        for m in members:
            m.started_at = t_launch
        try:
            leader = members[0]
            sess = self.session
            if leader.num_workers != sess.num_workers:
                sess = dataclasses.replace(
                    sess, num_workers=leader.num_workers)
            ctx = sess.context()
            ctx = dataclasses.replace(
                ctx, kernel_backend=leader.kernel_backend, feedback=None)
            if self.session.exchange is not None:
                ctx = dataclasses.replace(
                    ctx, exchange=self.session.exchange.clone())
            driver = Driver(ctx)
            # lane count pinned to the per-program cap, not the claimed
            # size: every launch of this program reuses ONE compiled
            # stacked executable no matter how the claim races land
            lanes = _batch.padded_members(
                self._batch_limit(members[0]._batch_shape.program))
            results = driver.collect_batch(
                [m._batch_shape for m in members], lanes=lanes)
            stats = driver.executor_stats()
            for m, result in zip(members, results):
                es = dict(stats)
                es["batch"] = {"size": len(members),
                               "queue_delay_s": t_launch - m.submitted_at}
                m.executor_stats = es
                self.result_cache.put(m._result_key, m._versions, result)
                m._complete(result=result)
            with self._cond:
                self.completed += len(members)
                self.batches += 1
                self.batched_queries += len(members)
        except Exception as exc:  # noqa: BLE001 -- solo fallback delivers
            # counted and kept, so a stacked program that fails to compile
            # or run shows up in stats() instead of hiding behind the
            # (correct, slower) solo re-runs
            with self._cond:
                self.batch_failures += 1
                if self.first_batch_failure is None:
                    self.first_batch_failure = f"{type(exc).__name__}: {exc}"
            for m in members:
                self._execute(m)

    def _execute(self, handle: QueryHandle) -> None:
        """Run one admitted query on this worker thread's own Driver."""
        with tracing.query(handle.query_id), tracing.span("repro.sched.run"):
            self._run_solo(handle)

    def _run_solo(self, handle: QueryHandle) -> None:
        handle.started_at = time.perf_counter()
        try:
            sess = self.session
            if handle.num_workers != sess.num_workers:
                # per-query worker-count override: rebuild the context
                # from a session clone so the exchange/mesh wiring matches
                # the W the plan was optimized for
                sess = dataclasses.replace(
                    sess, num_workers=handle.num_workers)
            ctx = sess.context()
            # pin the backend resolved at submit time (the cache key was
            # computed from it; the worker thread's ambient default may
            # differ by now)
            ctx = dataclasses.replace(
                ctx, kernel_backend=handle.kernel_backend,
                feedback=handle._feedback)
            if self.session.exchange is not None:
                # don't share one protocol's mutable stats across
                # concurrent queries: each Driver gets a fresh clone
                ctx = dataclasses.replace(
                    ctx, exchange=self.session.exchange.clone())
            if handle.spill_plan is not None and ctx.spill is None:
                # admitted over budget: run under a per-query spill
                # manager whose device budget is the scheduler's whole
                # budget (the query charged all of it, so it runs alone)
                from .spill import SpillManager
                ctx = dataclasses.replace(ctx, spill=SpillManager(
                    self.config.memory_budget,
                    self.config.spill_host_budget,
                    spill_dir=self.config.spill_dir,
                    disk_ceiling=self.config.spill_disk_ceiling))
            driver = Driver(ctx)
            result = driver.collect(handle.plan)
            handle.executor_stats = driver.executor_stats()
            self._check_feedback(handle)
            self.result_cache.put(handle._result_key, handle._versions,
                                  result)
            handle._complete(result=result)
            with self._cond:
                self.completed += 1
        except BaseException as exc:  # noqa: BLE001 -- delivered via handle
            handle._complete(error=exc)
            with self._cond:
                self.failed += 1

    def _check_feedback(self, handle: QueryHandle) -> None:
        """Adaptive plan-cache invalidation: after a feedback-enabled
        query runs, compare the cardinalities its cached plan was derived
        under (``handle._est_map``) against the observations the driver
        just harvested. A q-error past ``feedback_qerror_limit`` on any
        node means the plan's capacities/ordering were priced from stale
        beliefs — evict the entry so the next identical submit re-plans
        from the updated store. Warm (feedback-planned) entries have
        estimate == observation and survive, so the loop converges."""
        fb = handle._feedback
        if fb is None or not handle._est_map:
            return
        worst = 1.0
        for key, est in handle._est_map.items():
            entry = fb.get(key)
            if entry is not None:
                worst = max(worst, qerror(est, entry.rows))
        if worst > self.config.feedback_qerror_limit:
            self.plan_cache.invalidate(handle._plan_key)
