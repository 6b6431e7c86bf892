"""Morsel-driven streaming scan infrastructure (paper §2.2, challenge 1).

The paper's first critical challenge is moving data from storage into GPU
operators fast enough that the devices never starve: their minimal
column-chunk format reached 95% of the hardware I/O bound *because* reads
overlap with device compute. This module supplies the pieces shared by every
``TableSource``:

* ``HostMorsel``       -- one scan unit (a worker-stacked chunk of columns)
                          still in host memory, before the device transfer.
* ``MorselPrefetcher`` -- a bounded-queue background producer: while the
                          consumer computes on morsel N, the prefetch thread
                          reads morsel N+1 from storage and places it on the
                          device (double buffering at the default depth 2).
* ``ScanStats``        -- per-scan counters (bytes read, bytes transferred,
                          chunks skipped, prefetch overlap) surfaced through
                          ``Session.explain(plan, analyze=True)``.

Storage backends implement ``TableSource._host_morsels`` (pure host-side
reads); ``TableSource.scan``/``TableSource.stream`` in ``session.py`` wrap
that generator synchronously or through a prefetcher respectively.
"""

from __future__ import annotations

import contextvars
import dataclasses
import queue
import threading
import time
from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import tracing
from .table import DeviceTable


@dataclasses.dataclass
class ScanStats:
    """Counters for one table's scan activity within a query."""

    bytes_read: int = 0          # bytes read from storage (post-skipping)
    bytes_transferred: int = 0   # bytes placed into device memory
    chunks_total: int = 0        # chunks considered by the scan
    chunks_skipped: int = 0      # chunks pruned by zone-map stats
    morsels: int = 0             # morsels produced
    # producer: storage read, host-budget wait and the host->device put's
    # *enqueue* (the transfer is asynchronous; its completion is not here)
    read_seconds: float = 0.0
    wait_seconds: float = 0.0    # consumer: blocked waiting on the queue

    @property
    def prefetch_overlap(self) -> float:
        """Fraction of the producer's read + put-enqueue time hidden behind
        the consumer (1 - wait / read)."""
        if self.read_seconds <= 0.0:
            return 0.0
        return max(0.0, 1.0 - self.wait_seconds / self.read_seconds)

    def summary(self) -> Dict[str, float]:
        """Counters as a plain dict, with derived ``prefetch_overlap``."""
        d = dataclasses.asdict(self)
        d["prefetch_overlap"] = round(self.prefetch_overlap, 4)
        return d


@dataclasses.dataclass
class HostMorsel:
    """One scan unit in host memory: worker-stacked ``[W, cap, ...]`` column
    buffers plus validity, ready for a single interpretation-free device put
    (the paper's memmap -> device_put read path)."""

    columns: Dict[str, np.ndarray]
    validity: np.ndarray
    schema: Dict[str, object]

    def nbytes(self) -> int:
        """Host bytes this morsel occupies (columns + validity)."""
        total = self.validity.nbytes
        for a in self.columns.values():
            total += a.nbytes
        return int(total)


def empty_morsel(schema: Dict[str, object], num_workers: int) -> HostMorsel:
    """A capacity-1, zero-valid-rows morsel with the scan's schema (keeps
    downstream operator shapes alive when a scan prunes everything)."""
    cols = {}
    for c, d in schema.items():
        shape = ((num_workers, 1, d.width) if d.name == "bytes"
                 else (num_workers, 1))
        cols[c] = np.zeros(shape, dtype=d.np_dtype())
    return HostMorsel(cols, np.zeros((num_workers, 1), dtype=bool),
                      dict(schema))


def stacked_morsel(cols, schema, num_workers: int, assigned, cap: int,
                   read) -> HostMorsel:
    """Stack one storage chunk per worker into a ``[W, cap]`` host morsel.

    ``assigned`` lists the chunk ids for workers 0..len(assigned)-1 (a final
    short round leaves the remaining workers all-invalid); ``read(col,
    chunk)`` returns that chunk's column values. Shared by the chunked
    storage backends.
    """
    cap = max(cap, 1)
    validity = np.zeros((num_workers, cap), dtype=bool)
    out = {}
    for c in cols:
        d = schema[c]
        shape = ((num_workers, cap, d.width) if d.name == "bytes"
                 else (num_workers, cap))
        buf = np.zeros(shape, dtype=d.np_dtype())
        for wi, k in enumerate(assigned):
            arr = read(c, k)
            buf[wi, : len(arr)] = arr
            validity[wi, : len(arr)] = True
        out[c] = buf
    return HostMorsel(out, validity, {c: schema[c] for c in cols})


def morsel_to_device(morsel, sharding=None) -> DeviceTable:
    """Place a host morsel into device memory (optionally mesh-sharded).
    Tables that are already on device pass through (legacy sources whose
    scan() yields DeviceTables directly)."""
    if isinstance(morsel, DeviceTable):
        return (jax.device_put(morsel, sharding) if sharding is not None
                else morsel)
    if sharding is not None:
        put = lambda a: jax.device_put(a, sharding)  # noqa: E731
    else:
        put = jnp.asarray
    cols = {n: put(a) for n, a in morsel.columns.items()}
    return DeviceTable(cols, put(morsel.validity), dict(morsel.schema))


_SENTINEL = object()


class MorselPrefetcher:
    """Async double-buffered storage->device prefetcher.

    A daemon thread drains ``host_morsels`` (storage reads), performs the
    host->device transfer, and pushes ready ``DeviceTable`` morsels into a
    bounded queue of ``depth`` slots: while the consumer computes on morsel
    N, morsel N+1 is being read and transferred. The queue bound caps device
    memory at ``depth`` in-flight morsels beyond the one being computed.

    The bound is additionally **bytes-aware**: with a ``host_budget``
    (``core.spill.HostMemoryBudget``, shared with the spill manager's host
    tier) or a private ``max_bytes`` cap, the producer blocks until the
    buffered morsels' host bytes fit the budget -- so prefetch participates
    in the same host-memory accounting as spilled partitions instead of
    only counting morsels.

    Iteration is single-consumer. Abandoning the iterator early (e.g. a
    Limit downstream) stops the producer; producer exceptions re-raise in
    the consumer.
    """

    def __init__(self, host_morsels: Iterator[HostMorsel], depth: int = 2,
                 sharding=None, stats: Optional[ScanStats] = None,
                 host_budget=None, max_bytes: Optional[int] = None):
        self.stats = stats if stats is not None else ScanStats()
        self._gen = host_morsels
        self._sharding = sharding
        self._q: "queue.Queue" = queue.Queue(maxsize=max(int(depth), 1))
        if host_budget is None and max_bytes is not None:
            from .spill import HostMemoryBudget
            host_budget = HostMemoryBudget(max_bytes)
        self._budget = host_budget
        self._closed = threading.Event()
        # the producer runs in a copy of the creating thread's context, so
        # its spans carry the query id the scheduler set there
        self._thread = threading.Thread(
            target=contextvars.copy_context().run, args=(self._produce,),
            daemon=True, name="morsel-prefetch")

    # -- producer (background thread) ---------------------------------------
    def _put(self, item) -> bool:
        while not self._closed.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self) -> None:
        try:
            it = iter(self._gen)
            while not self._closed.is_set():
                t0 = time.perf_counter()
                try:
                    with tracing.span("repro.scan.read"):
                        host = next(it)
                except StopIteration:
                    break
                nbytes = host.nbytes()   # HostMorsel or DeviceTable alike
                if self._budget is not None:
                    # bytes-aware backpressure: stall the storage read
                    # until the buffered morsels fit the host budget
                    if not self._budget.acquire(nbytes,
                                                stop=self._closed.is_set):
                        return
                with tracing.span("repro.scan.put"):
                    table = morsel_to_device(host, self._sharding)
                self.stats.read_seconds += time.perf_counter() - t0
                self.stats.bytes_transferred += nbytes
                self.stats.morsels += 1
                if not self._put((table, nbytes)):
                    if self._budget is not None:
                        self._budget.release(nbytes)
                    return
            self._put(_SENTINEL)
        except BaseException as exc:  # noqa: BLE001 -- re-raised by consumer
            self._put(exc)

    # -- consumer ------------------------------------------------------------
    def close(self) -> None:
        """Stop the producer thread (also called when iteration ends)."""
        self._closed.set()
        if self._budget is not None:
            # return budget held by undrained queued morsels
            while True:
                try:
                    item = self._q.get_nowait()
                except queue.Empty:
                    break
                if isinstance(item, tuple):
                    self._budget.release(item[1])

    def __iter__(self) -> Iterator[DeviceTable]:
        self._thread.start()
        try:
            while True:
                t0 = time.perf_counter()
                with tracing.span("repro.scan.wait"):
                    item = self._q.get()
                self.stats.wait_seconds += time.perf_counter() - t0
                if item is _SENTINEL:
                    return
                if isinstance(item, BaseException):
                    raise item
                table, nbytes = item
                if self._budget is not None:
                    self._budget.release(nbytes)
                yield table
        finally:
            self.close()
