"""Public kernel wrappers + the engine's kernel-backend dispatch switch.

This module is the boundary where the engine picks its physical execution
layer, mirroring the paper's swap of Velox CPU operators for cuDF GPU
kernels behind one operator interface. Two backends exist:

* ``"jnp"``    -- the sort/searchsorted/segment_sum code in
                  ``core.relational`` / ``core.table`` (doubles as the
                  oracle the kernels are validated against);
* ``"pallas"`` -- the Pallas kernels in this package (``hash_probe``,
                  ``segmented_sum``, ``radix_histogram``,
                  ``block_prefix_sum``). On a TPU backend the compiled
                  kernels run natively; elsewhere (CPU containers, CI) they
                  run in interpret mode, which executes the kernel body as
                  ordinary XLA ops and is what the correctness sweeps test.

Selection is thread-scoped: ``use_backend("pallas")`` / ``use_pallas()``
are context managers the driver enters per query, the default comes from
``Session(kernel_backend=...)`` or the ``REPRO_KERNEL_BACKEND`` env var.
Dispatch accounting (``collect_dispatches`` / ``record_kernels``) lets the
driver report per-query ``kernel_dispatch`` counts in ``executor_stats``.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Dict, Iterator, Set

import jax

from . import ref  # noqa: F401  (oracles re-exported for convenience)
from .block_prefix_sum import block_prefix_sum as _bps
from .hash_probe import (build_table as _build, hash_probe as _probe,
                         hash_probe_multi as _probe_multi)
from .radix_histogram import radix_histogram as _hist
from .segmented_agg import (segmented_int_sum as _segisum,
                            segmented_minmax as _segminmax,
                            segmented_sum as _segsum)

BACKENDS = ("jnp", "pallas")

_tls = threading.local()
_default_backend = os.environ.get("REPRO_KERNEL_BACKEND", "jnp")
if _default_backend not in BACKENDS:          # pragma: no cover - env typo
    raise ValueError(
        f"REPRO_KERNEL_BACKEND={_default_backend!r} not in {BACKENDS}")


# ---------------------------------------------------------------------------
# backend selection
# ---------------------------------------------------------------------------

def default_backend() -> str:
    """Process-wide default backend (``REPRO_KERNEL_BACKEND`` or 'jnp')."""
    return _default_backend


def set_default_backend(name: str) -> None:
    """Set the process-wide default backend ('jnp' or 'pallas')."""
    global _default_backend
    if name not in BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}; one of {BACKENDS}")
    _default_backend = name


def current_backend() -> str:
    """The backend active on this thread (innermost ``use_backend`` scope,
    falling back to the process default). Engine hot paths read this at
    trace time; compile caches must key on it."""
    stack = getattr(_tls, "backend_stack", None)
    return stack[-1] if stack else _default_backend


@contextlib.contextmanager
def use_backend(name: str) -> Iterator[None]:
    """Scope the calling thread to kernel backend ``name``::

        with kernels.ops.use_backend("pallas"):
            session.execute(plan)        # hot paths dispatch to Pallas
    """
    if name not in BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}; one of {BACKENDS}")
    stack = getattr(_tls, "backend_stack", None)
    if stack is None:
        stack = _tls.backend_stack = []
    stack.append(name)
    try:
        yield
    finally:
        stack.pop()


def use_pallas():
    """The engine's dispatch switch: ``with use_pallas(): ...`` routes the
    hot relational primitives (join probe, segmented aggregation, stream
    compaction, exchange histogram) through the Pallas kernels."""
    return use_backend("pallas")


# ---------------------------------------------------------------------------
# dispatch accounting
# ---------------------------------------------------------------------------
# Two thread-local channels: ``record_kernels`` captures *which* kernels a
# traced program uses (wrappers run at trace time only, so the driver cannot
# count executions there), and ``collect_dispatches`` receives per-execution
# counts replayed by the callers that invoke the compiled programs
# (operators.table_op, the exchange protocols).

def _stack(name: str) -> list:
    s = getattr(_tls, name, None)
    if s is None:
        s = []
        setattr(_tls, name, s)
    return s


@contextlib.contextmanager
def collect_dispatches(counts: Dict[str, int]) -> Iterator[None]:
    """Accumulate kernel-dispatch counts into ``counts`` (kind -> calls)
    for the duration of the scope; the driver wraps each query with this
    and surfaces the dict as ``executor_stats()['kernel_dispatch']``."""
    stack = _stack("counter_stack")
    stack.append(counts)
    try:
        yield
    finally:
        stack.pop()


def count_dispatch(kind: str, n: int = 1) -> None:
    """Report ``n`` executions of kernel ``kind`` to every active
    ``collect_dispatches`` scope on this thread (no-op outside one)."""
    for counts in _stack("counter_stack"):
        counts[kind] = counts.get(kind, 0) + n


@contextlib.contextmanager
def record_kernels(used: Set[str]) -> Iterator[None]:
    """Trace-time capture: while active, every kernel wrapper invocation
    adds its kind to ``used``. ``operators.table_op`` keeps one set per
    compiled program and replays it through ``count_dispatch`` per call."""
    stack = _stack("record_stack")
    stack.append(used)
    try:
        yield
    finally:
        stack.pop()


# guards recorded-kernel sets: a scheduler worker may replay a set while
# another worker's first call of the same compiled program is still
# tracing into it
_record_lock = threading.Lock()


def kernel_snapshot(used: Set[str]) -> tuple:
    """Race-free snapshot of a ``record_kernels`` set (callers iterate the
    returned tuple while other threads may still be tracing)."""
    with _record_lock:
        return tuple(used)


def _mark(kind: str) -> None:
    with _record_lock:
        for used in _stack("record_stack"):
            used.add(kind)


def mark_kernel(kind: str) -> None:
    """Trace-time record of a kernel dispatch for kernels that live
    outside this package but report through the same accounting (the
    fused per-morsel pipeline kernel in ``core.fused`` records 'fused')."""
    _mark(kind)


def mark_fallback(kind: str) -> None:
    """Trace-time note that a hot path wanted the pallas kernel for
    ``kind`` but took its jnp fallback (oversized capacity, composite key,
    unsupported accumulator...). Recorded as ``fallback_<kind>`` alongside
    the kernel kinds, so ``executor_stats()['kernel_dispatch']`` counts one
    fallback per would-be dispatch — the number adaptive re-planning tries
    to drive down."""
    _mark("fallback_" + kind)


# ---------------------------------------------------------------------------
# kernel wrappers (interpret mode off-TPU)
# ---------------------------------------------------------------------------

def on_tpu() -> bool:
    """True when jax's default backend is a TPU (compiled kernels)."""
    return jax.default_backend() == "tpu"


def _interp() -> bool:
    return not on_tpu()


def segmented_sum(gids, values, num_groups, **kw):
    """MXU scatter-add: sum ``values`` per group id (gids >= num_groups
    are dropped) -> float32[num_groups]. Oracle: ``ref.segmented_agg``."""
    _mark("agg")
    return _segsum(gids, values, num_groups, interpret=_interp(), **kw)


def segmented_int_sum(gids, values, num_groups, **kw):
    """Integer MXU scatter-add with an int32 accumulator (exact past 2^24,
    wraps at 2^31 like the int32 oracle) -> int32[num_groups]. Serves both
    integer sums and counts. Oracle: ``jax.ops.segment_sum``."""
    _mark("agg")
    return _segisum(gids, values, num_groups, interpret=_interp(), **kw)


def segmented_minmax(gids, values, num_groups, kind, **kw):
    """Segmented min/max (kind in 'min'|'max') -> [num_groups] of the
    value dtype; empty groups hold the reduction identity. Oracle:
    ``jax.ops.segment_min/max``."""
    _mark("agg")
    return _segminmax(gids, values, num_groups, kind, interpret=_interp(),
                      **kw)


def radix_histogram(pids, num_partitions, **kw):
    """Rows per destination partition (the exchange's metadata phase) ->
    int32[num_partitions]. Oracle: ``ref.radix_histogram``."""
    _mark("partition")
    return _hist(pids, num_partitions, interpret=_interp(), **kw)


def build_table(keys, vals, table_size, **kw):
    """Build the open-addressing join table (vectorized cooperative
    insertion, pure jnp) -> (table_keys, table_vals)."""
    _mark("build")
    return _build(keys, vals, table_size, **kw)


def hash_probe(table_keys, table_vals, probe_keys, **kw):
    """Probe the open-addressing table -> (found bool[N], vals int32[N]).
    Oracle: ``ref.hash_probe``."""
    _mark("probe")
    return _probe(table_keys, table_vals, probe_keys, interpret=_interp(),
                  **kw)


def hash_probe_multi(table_keys, table_vals, probe_keys, max_matches, **kw):
    """Expansion probe: every slot matching a probe key, in run order ->
    (count int32[N], slots int32[N, max_matches]). Oracle:
    ``relational.join_probe`` over the same build rows."""
    _mark("probe")
    return _probe_multi(table_keys, table_vals, probe_keys, max_matches,
                        interpret=_interp(), **kw)


def block_prefix_sum(mask, **kw):
    """Stream-compaction addresses: mask [N] -> (exclusive positions
    int32[N], total int32). Oracle: ``ref.block_prefix_sum``."""
    _mark("compact")
    return _bps(mask, interpret=_interp(), **kw)
