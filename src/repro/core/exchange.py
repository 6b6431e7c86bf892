"""Inter-worker data exchange: the paper's central contribution, on TPU.

Distributed state convention: a *worker-stacked* DeviceTable has arrays of
shape [W, cap, ...] — axis 0 is the worker axis, sharded over the mesh's
``workers`` axis when a mesh is present. Each worker owns one [cap, ...]
slice, exactly like one Presto-native worker owns one GPU in the paper.

Two protocols, mirroring the paper's HttpExchange vs UcxExchange contrast:

* ``HostExchange``  — the HttpExchange analogue. Every cross-worker transfer
  is staged through host memory: device→host copy, host-side partitioning,
  page serialization (request/response pages of a configured size), then
  host→device copy. This is what the paper measures as the CPU-staging
  bottleneck.

* ``ICIExchange``   — the UcxExchange analogue. Repartitioning happens
  entirely on device: a metadata phase (per-partition row counts — the
  paper's "metadata first to determine allocation size" rendezvous
  handshake) sizes the receive buffers; the data phase is a single XLA
  program whose worker-axis transpose lowers to an all-to-all over ICI.
  Data never leaves device memory.

Both implement vector compaction (merge small batches before transmission;
paper §3.3.2) and count-based flow control.
"""

from __future__ import annotations

import dataclasses
import pickle
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import ops as kernel_ops
from . import relational as rel
from . import tracing
from .table import DeviceTable


def _jit(**jit_kwargs):
    """``jax.jit`` of an exchange program, named ``jit_exchange__<fn>``."""
    def deco(fn):
        return jax.jit(tracing.program("exchange", fn), **jit_kwargs)
    return deco


@dataclasses.dataclass
class ExchangeStats:
    """Counters for one exchange protocol instance (rounds, rows/bytes
    moved, and -- for the host-staged baseline -- bytes through host)."""

    rounds: int = 0
    rows_moved: int = 0
    bytes_moved: int = 0            # payload bytes that crossed the exchange
    host_staged_bytes: int = 0      # bytes that round-tripped through host
    seconds: float = 0.0

    def reset(self):
        """Zero all counters (benchmarks reuse one protocol instance)."""
        self.rounds = self.rows_moved = self.bytes_moved = 0
        self.host_staged_bytes = 0
        self.seconds = 0.0


def _hash32_np(x: np.ndarray) -> np.ndarray:
    """numpy mirror of relational.hash32 (host-side partitioning for the
    HttpExchange baseline, which partitions on the CPU)."""
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return (x & np.uint32(0x7FFFFFFE)).astype(np.int32)


def _hash_combine_np(cols) -> np.ndarray:
    n = np.asarray(cols[0]).shape[0]
    h = np.zeros((n,), dtype=np.uint32)

    def mix(h, c):
        hc = _hash32_np(np.asarray(c, dtype=np.int32)).astype(np.uint32)
        return h ^ (hc + np.uint32(0x9E3779B9) + (h << np.uint32(6))
                    + (h >> np.uint32(2)))

    for c in cols:
        c = np.asarray(c)
        if c.ndim == 2:   # bytes column: fold byte lanes (mirrors jnp path)
            folded = np.zeros((n,), dtype=np.uint32)
            for j in range(c.shape[1]):
                folded = folded * np.uint32(31) + c[:, j].astype(np.uint32)
            h = mix(h, folded)
        else:
            h = mix(h, c)
    return (h & np.uint32(0x7FFFFFFE)).astype(np.int32)


def _row_bytes(table: DeviceTable) -> int:
    per_row = 1  # validity byte
    for name, arr in table.columns.items():
        width = int(np.prod(arr.shape[2:])) if arr.ndim > 2 else 1
        per_row += arr.dtype.itemsize * width
    return per_row


# ---------------------------------------------------------------------------
# device-side partitioning programs (shared by both protocols' accounting)
# ---------------------------------------------------------------------------

@_jit(static_argnums=(1, 2, 3))
def _partition_counts(table: DeviceTable, key_names, num_workers: int,
                      backend: str = "jnp"):
    """Metadata phase: rows each src worker holds for each dst partition.

    Under the 'pallas' kernel backend the per-worker histogram is the
    ``radix_histogram`` MXU kernel (invalid rows masked to the dropped
    ``num_workers`` bin); the jnp one-hot sum is its oracle."""

    def per_worker(t: DeviceTable):
        pids = rel.partition_ids([t.columns[k] for k in key_names],
                                 t.validity, num_workers)
        if backend == "pallas":
            masked = jnp.where(t.validity, pids, num_workers)
            return kernel_ops.radix_histogram(masked, num_workers)
        onehot = jax.nn.one_hot(pids, num_workers, dtype=jnp.int32)
        return jnp.sum(onehot * t.validity[:, None].astype(jnp.int32), axis=0)

    return jax.vmap(per_worker)(table)          # [W_src, W_dst]


@_jit(static_argnums=(1,))
def _compact_stacked(table: DeviceTable, cap: int) -> DeviceTable:
    """Vector compaction (paper §3.3.2): per worker, move valid rows to the
    front and truncate to ``cap`` slots. Exchanges call this with ``cap``
    sized from the metadata phase, so dead padding (e.g. unused
    ``max_groups`` slots of an aggregation output) is not transmitted and
    not carried into downstream operators.

    The gather indices are built with a stream-compaction scatter (stable
    rank via cumsum) so only ``cap`` output rows are ever gathered — a full
    argsort-based compact would gather the whole padded capacity, which is
    exactly the cost this call exists to avoid."""

    def per_worker(t: DeviceTable):
        n = t.validity.shape[0]
        csum = jnp.cumsum(t.validity.astype(jnp.int32))
        # j-th valid row = first position where the running count hits j+1
        # (binary-search inversion; XLA CPU scatter is a scalar loop)
        gather = jnp.searchsorted(
            csum, jnp.arange(1, cap + 1, dtype=jnp.int32), side="left")
        out_valid = gather < n
        idx = jnp.minimum(gather, n - 1).astype(jnp.int32)
        cols = {name: jnp.take(a, idx, axis=0)
                for name, a in t.columns.items()}
        return DeviceTable(cols, out_valid, t.schema)

    return jax.vmap(per_worker)(table)


def _pow2(n: int) -> int:
    return int(2 ** np.ceil(np.log2(max(n, 1))))


def maybe_compact(table: DeviceTable,
                  per_worker: Optional[np.ndarray] = None) -> DeviceTable:
    """Vector compaction when it at least halves capacity (§3.3.2): trims
    per-worker rows to pow2(max per-worker valid count). Shared by the
    mesh exchange paths and the driver's blocking operators (a sort over
    ``max_groups`` padding costs more than this one metadata sync).
    ``per_worker`` is each worker's valid-row count where the caller's
    metadata phase already holds it on the host (no second sync)."""
    if per_worker is None:
        per_worker = np.asarray(table.validity.sum(axis=1))
    cap = _pow2(int(per_worker.max()) if per_worker.size else 1)
    if cap * 2 <= table.validity.shape[1]:
        return _compact_stacked(table, cap)
    return table


@_jit(static_argnums=(1, 2, 3))
def _partition_layout_table(table: DeviceTable, key_names, num_workers: int,
                            part_cap: int) -> DeviceTable:
    """Data phase step 1: scatter rows into [W_dst, part_cap] send buffers."""

    def per_worker(t: DeviceTable):
        pids = rel.partition_ids([t.columns[k] for k in key_names],
                                 t.validity, num_workers)
        gather, out_valid = rel.partition_layout(pids, t.validity, num_workers,
                                                 part_cap)
        cols = {n: jnp.take(a, gather, axis=0).reshape(
                    (num_workers, part_cap) + a.shape[1:])
                for n, a in t.columns.items()}
        return DeviceTable(cols, out_valid.reshape(num_workers, part_cap),
                           t.schema)

    return jax.vmap(per_worker)(table)          # leaves [W_src, W_dst, cap, ...]


@_jit(static_argnums=(1, 2, 3))
def _repartition_fused(table: DeviceTable, key_names, num_workers: int,
                       out_cap: int) -> DeviceTable:
    """Single-device fast path: the whole shuffle as index arithmetic plus
    ONE gather per column, straight into compacted [W_dst, out_cap] output.

    The staged path (`_partition_layout_table` + `_exchange_data`)
    materializes a [W_src, W_dst, cap] send buffer, transposes it (the ICI
    all-to-all when a mesh is present), and compacts — three passes over
    the column bytes. Off-mesh those passes share one memory space, so the
    destination row of every source row can be computed up front (stable
    rank within its (src, dst) bucket + exclusive prefix of bucket counts
    over sources) and each column moved exactly once. ``out_cap`` comes
    from the metadata phase: >= the largest per-destination row total, so
    no row is dropped.
    """
    w, cap = table.validity.shape

    def ids(t: DeviceTable):
        return rel.partition_ids([t.columns[k] for k in key_names],
                                 t.validity, num_workers)

    pids = jax.vmap(ids)(table)                              # [W, cap]
    # per-destination running counts over the flattened (src-major) row
    # order; the j-th row received by dst d is the first flat position
    # whose running dst-d count reaches j+1 (binary-search inversion — no
    # sort, no scatter: XLA CPU is slow at both)
    onehot = ((pids[..., None] == jnp.arange(num_workers, dtype=jnp.int32))
              & table.validity[..., None]).astype(jnp.int32)
    csum = jnp.cumsum(onehot.reshape(w * cap, num_workers), axis=0)
    queries = jnp.arange(1, out_cap + 1, dtype=jnp.int32)
    gmap = jax.vmap(
        lambda col: jnp.searchsorted(col, queries, side="left"),
        in_axes=1)(csum)                                     # [D, out_cap]
    out_valid = gmap < w * cap
    idx = jnp.minimum(gmap, w * cap - 1).astype(jnp.int32)
    cols = {}
    for n, a in table.columns.items():
        flat = a.reshape((w * cap,) + a.shape[2:])
        cols[n] = jnp.take(flat, idx, axis=0).reshape(
            (num_workers, out_cap) + a.shape[2:])
    return DeviceTable(cols, out_valid, table.schema)


@_jit(static_argnums=(1, 2))
def _broadcast_fused(table: DeviceTable, num_workers: int,
                     out_cap: int) -> DeviceTable:
    """Single-device fast path for broadcast: compact all valid rows with
    one gather per column, then replicate by broadcast (no per-worker
    copies of dead padding). ``out_cap`` >= total valid rows."""
    w, cap = table.validity.shape
    flatv = table.validity.reshape(-1)
    csum = jnp.cumsum(flatv.astype(jnp.int32))
    gmap = jnp.searchsorted(
        csum, jnp.arange(1, out_cap + 1, dtype=jnp.int32), side="left")
    out_valid = gmap < w * cap
    idx = jnp.minimum(gmap, w * cap - 1).astype(jnp.int32)
    cols = {}
    for n, a in table.columns.items():
        flat = a.reshape((w * cap,) + a.shape[2:])
        row = jnp.take(flat, idx, axis=0)
        cols[n] = jnp.broadcast_to(row[None], (num_workers,) + row.shape)
    valid = jnp.broadcast_to(out_valid[None], (num_workers, out_cap))
    return DeviceTable(cols, valid, table.schema)


def _constrain(tree, mesh: Mesh, axis: str):
    spec = NamedSharding(mesh, P(axis))
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(x, spec), tree)


# The mesh programs take the mesh and its axis as static arguments, so a
# compiled program is keyed on (mesh, axis, shapes) alone: every query's
# protocol instance (the session's, or a scheduler clone) shares it.

@_jit(static_argnums=(1, 2, 3, 4))
def _exchange_data(staged: DeviceTable, mesh: Mesh, axis: str,
                   num_workers: int, part_cap: int) -> DeviceTable:
    """Data phase on the mesh: the worker-axis transpose of the staged
    send buffers, which lowers to an all-to-all over ICI."""
    staged = _constrain(staged, mesh, axis)

    def swap(x):  # [W_src, W_dst, cap, ...] -> [W_dst, W_src*cap, ...]
        x = jnp.swapaxes(x, 0, 1)
        return x.reshape((num_workers, num_workers * part_cap) + x.shape[3:])

    cols = {n: swap(a) for n, a in staged.columns.items()}
    out = DeviceTable(cols, swap(staged.validity), staged.schema)
    return _constrain(out, mesh, axis)


@_jit(static_argnums=(1, 2, 3))
def _broadcast_data(table: DeviceTable, mesh: Mesh, axis: str,
                    num_workers: int) -> DeviceTable:
    """Broadcast on the mesh: every worker receives all rows (lowers to an
    all-gather over ICI)."""
    table = _constrain(table, mesh, axis)
    cap = table.validity.shape[1]

    def bcast(x):  # [W, cap, ...] -> every worker sees all rows
        flat = x.reshape((1, num_workers * cap) + x.shape[2:])
        return jnp.broadcast_to(flat, (num_workers,) + flat.shape[1:])

    cols = {n: bcast(a) for n, a in table.columns.items()}
    out = DeviceTable(cols, bcast(table.validity), table.schema)
    return _constrain(out, mesh, axis)


def _counts_to_host(counts: jax.Array) -> np.ndarray:
    """The metadata phase's blocking device-to-host copy of row counts,
    under the span ``repro.exchange.counts``."""
    with tracing.span("repro.exchange.counts"):
        return np.asarray(counts)


class ExchangeProtocol:
    """Contract for moving worker-stacked tables between workers; the two
    implementations below mirror the paper's UcxExchange (device-native)
    vs HttpExchange (host-staged) contrast."""

    name = "exchange"

    def __init__(self):
        self.stats = ExchangeStats()

    def repartition(self, table: DeviceTable, key_names: Sequence[str],
                    num_workers: int) -> DeviceTable:
        """Hash-partition rows on ``key_names`` so equal keys land on the
        same worker (the shuffle between join/aggregation stages)."""
        raise NotImplementedError

    def broadcast(self, table: DeviceTable, num_workers: int) -> DeviceTable:
        """Replicate every worker's valid rows to all workers."""
        raise NotImplementedError

    def clone(self) -> "ExchangeProtocol":
        """Fresh instance with the same configuration but zeroed stats
        (the scheduler gives each concurrent query its own clone)."""
        return type(self)()

    # -- shared flow control ------------------------------------------------
    def _choose_part_cap(self, counts: np.ndarray) -> int:
        """Receive-buffer sizing from the metadata phase (flow control);
        pow2 for layout friendliness."""
        return _pow2(int(counts.max()) if counts.size else 1)

    @staticmethod
    def _ensure_rows(table: DeviceTable) -> DeviceTable:
        """Pad zero-capacity tables to one (dead) row per worker.

        A fragment can legitimately produce a [W, 0] table (all rows
        filtered, empty partition after a skewed shuffle); the layout/gather
        paths and downstream operators need at least one row slot."""
        if table.validity.shape[-1] > 0:
            return table

        def pad(a):
            widths = [(0, 0)] * a.ndim
            widths[1] = (0, 1)
            return jnp.pad(a, widths)

        return DeviceTable({n: pad(a) for n, a in table.columns.items()},
                           pad(table.validity), table.schema)


class ICIExchange(ExchangeProtocol):
    """Device-native exchange: UcxExchange on TPU interconnect.

    When a mesh is provided, the worker axis is sharded and the transpose in
    the data phase lowers to an ICI all-to-all (verified in the dry-run HLO);
    without a mesh the same program runs on one device (degenerate SPMD).
    """

    name = "ici"

    def __init__(self, mesh: Optional[Mesh] = None, axis: str = "workers"):
        super().__init__()
        self.mesh = mesh
        self.axis = axis

    def clone(self) -> "ICIExchange":
        """Fresh ICI protocol on the same mesh/axis, zeroed stats."""
        return type(self)(self.mesh, self.axis)

    def repartition(self, table, key_names, num_workers):
        t0 = time.perf_counter()
        table = self._ensure_rows(table)
        key_names = tuple(key_names)
        # metadata phase (rendezvous handshake): size the receive buffers
        backend = kernel_ops.current_backend()
        counts = _counts_to_host(
            _partition_counts(table, key_names, num_workers, backend))
        if backend == "pallas":
            kernel_ops.count_dispatch("partition")
        out_cap = _pow2(int(counts.sum(axis=0).max()) if counts.size else 1)
        if self.mesh is None:
            # off-mesh: one fused index-math + gather program per round
            out = _repartition_fused(table, key_names, num_workers, out_cap)
        else:
            # on-mesh: staged send buffers whose worker-axis transpose
            # lowers to the ICI all-to-all, then receive-side compaction
            # (vector compaction, §3.3.2). A source worker's row of the
            # counts is its valid-row count, and compaction preserves each
            # row's source worker and keys, so the metadata above sizes
            # both — no second metadata pass
            table = maybe_compact(table, counts.sum(axis=1))
            part_cap = self._choose_part_cap(counts)
            staged = _partition_layout_table(table, key_names, num_workers,
                                             part_cap)
            out = _exchange_data(staged, self.mesh, self.axis, num_workers,
                                 part_cap)
            if out_cap < out.validity.shape[1]:
                out = _compact_stacked(out, out_cap)
        self.stats.rounds += 1
        moved = int(counts.sum() - np.trace(counts))  # off-diagonal rows move
        self.stats.rows_moved += moved
        self.stats.bytes_moved += moved * _row_bytes(table)
        self.stats.seconds += time.perf_counter() - t0
        return out

    def broadcast(self, table, num_workers):
        t0 = time.perf_counter()
        table = self._ensure_rows(table)
        # metadata phase: valid counts size the replica buffers, so dead
        # padding is compacted away before replication W-fold
        per_worker = _counts_to_host(table.validity.sum(axis=1))
        rows = int(per_worker.sum())
        if self.mesh is None:
            out = _broadcast_fused(table, num_workers, _pow2(rows))
        else:
            out = _broadcast_data(maybe_compact(table, per_worker),
                                  self.mesh, self.axis, num_workers)
        self.stats.rounds += 1
        self.stats.rows_moved += rows * (num_workers - 1)
        self.stats.bytes_moved += rows * (num_workers - 1) * _row_bytes(table)
        self.stats.seconds += time.perf_counter() - t0
        return out


class HostExchange(ExchangeProtocol):
    """Host-staged exchange: the HttpExchange baseline.

    Faithful to the paper's description of Presto's protocol: results are
    serialized into *pages* (smallest unit of transmission, configurable
    size), the consumer fetches pages with a request/reply protocol, and all
    of it transits CPU memory. We reproduce the serialize → page → fetch →
    deserialize path with pickle as the page codec.
    """

    name = "host"

    def __init__(self, page_rows: int = 4096):
        super().__init__()
        self.page_rows = page_rows

    def clone(self) -> "HostExchange":
        """Fresh host-staged protocol at the same page size, zeroed stats."""
        return type(self)(self.page_rows)

    def _to_pages(self, cols: dict, validity: np.ndarray) -> List[bytes]:
        n = validity.shape[0]
        pages = []
        for lo in range(0, max(n, 1), self.page_rows):
            hi = min(lo + self.page_rows, n)
            page = {k: v[lo:hi] for k, v in cols.items()}
            page["__validity"] = validity[lo:hi]
            pages.append(pickle.dumps(page, protocol=4))
        return pages

    def repartition(self, table, key_names, num_workers):
        t0 = time.perf_counter()
        table = self._ensure_rows(table)
        # device -> host staging (the cost the paper eliminates)
        host_cols = {n: np.asarray(a) for n, a in table.columns.items()}
        validity = np.asarray(table.validity)
        self.stats.host_staged_bytes += sum(a.nbytes for a in host_cols.values())

        w = num_workers
        key_cols = [host_cols[k] for k in key_names]
        flat_keys = [k.reshape(-1, k.shape[-1]) if k.ndim == 3
                     else k.reshape(-1) for k in key_cols]
        hashed = _hash_combine_np(flat_keys).reshape(validity.shape)
        pid = hashed % w

        # upstream: serialize each (src, dst) partition into pages
        inboxes: List[List[bytes]] = [[] for _ in range(w)]
        for src in range(w):
            mask = validity[src]
            for dst in range(w):
                sel = mask & (pid[src] == dst)
                if not sel.any():
                    continue
                part = {n: a[src][sel] for n, a in host_cols.items()}
                inboxes[dst].extend(self._to_pages(part, np.ones(sel.sum(), bool)))

        # downstream: fetch + deserialize pages, assemble worker tables
        per_worker = []
        total_bytes = 0
        for dst in range(w):
            rows = {n: [] for n in host_cols}
            vals = []
            for page_bytes in inboxes[dst]:
                total_bytes += len(page_bytes)
                page = pickle.loads(page_bytes)
                v = page.pop("__validity")
                vals.append(v)
                for n, a in page.items():
                    rows[n].append(a)
            cnt = sum(v.shape[0] for v in vals) if vals else 0
            per_worker.append((rows, vals, cnt))

        cap = _pow2(max(c for _, _, c in per_worker))
        out_cols = {n: np.zeros((w, cap) + host_cols[n].shape[2:],
                                dtype=host_cols[n].dtype) for n in host_cols}
        out_valid = np.zeros((w, cap), dtype=bool)
        for dst, (rows, vals, cnt) in enumerate(per_worker):
            if cnt == 0:
                continue
            for n in host_cols:
                out_cols[n][dst, :cnt] = np.concatenate(rows[n], axis=0)
            out_valid[dst, :cnt] = np.concatenate(vals)

        # host -> device staging
        out = DeviceTable({n: jnp.asarray(a) for n, a in out_cols.items()},
                          jnp.asarray(out_valid), table.schema)
        self.stats.rounds += 1
        self.stats.bytes_moved += total_bytes
        self.stats.rows_moved += int(validity.sum())
        self.stats.host_staged_bytes += sum(a.nbytes for a in out_cols.values())
        self.stats.seconds += time.perf_counter() - t0
        return out

    def broadcast(self, table, num_workers):
        t0 = time.perf_counter()
        table = self._ensure_rows(table)
        host_cols = {n: np.asarray(a) for n, a in table.columns.items()}
        validity = np.asarray(table.validity)
        self.stats.host_staged_bytes += sum(a.nbytes for a in host_cols.values())
        w = num_workers
        flat_valid = validity.reshape(-1)
        flat_cols = {n: a.reshape((-1,) + a.shape[2:]) for n, a in host_cols.items()}
        pages = self._to_pages({n: a[flat_valid] for n, a in flat_cols.items()},
                               np.ones(int(flat_valid.sum()), bool))
        total = sum(len(p) for p in pages) * (w - 1)
        cnt = int(flat_valid.sum())
        cap = _pow2(cnt)
        out_cols = {}
        for n, a in flat_cols.items():
            buf = np.zeros((cap,) + a.shape[1:], dtype=a.dtype)
            buf[:cnt] = a[flat_valid]
            out_cols[n] = jnp.asarray(np.broadcast_to(buf, (w,) + buf.shape).copy())
        ov = np.zeros(cap, bool)
        ov[:cnt] = True
        out = DeviceTable(out_cols, jnp.asarray(np.broadcast_to(ov, (w, cap)).copy()),
                          table.schema)
        self.stats.rounds += 1
        self.stats.bytes_moved += total
        self.stats.rows_moved += cnt * (w - 1)
        self.stats.host_staged_bytes += sum(np.asarray(a).nbytes
                                            for a in out_cols.values())
        self.stats.seconds += time.perf_counter() - t0
        return out
