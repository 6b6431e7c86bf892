"""The engine's one tracing API: host spans and device-program names on the
``jax.profiler`` clock.

* ``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation``, so host spans
  land in the profiler's own ``.xplane.pb``, on the clock its device planes
  are aligned to. Names are fixed strings (``repro.<layer>.<what>``); ids go
  in keyword arguments. Every span carries the ``query_id`` of the query that
  caused it (``query``). With no profiler active a span costs one check and
  a no-op context manager.
* ``program(family, fn)`` names a device program after the operator family
  that issues it: jitted, its XLA module reads ``jit_<family>__<fn>``, and
  its HLO op metadata carries ``<family>/`` through ``jax.named_scope``.
  Its body runs only while JAX traces the program, so it also counts
  traces per family into the ``collect_traces`` scope a query runs under.

Take a trace of a live session with ``jax.profiler.trace(log_dir)`` around
the queries of interest; ``PERF.md`` lists every span name.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Dict, Iterator, Optional

import jax
from jax.profiler import TraceAnnotation

# Families of device programs, one per operator layer that issues them:
# scan (filter/project/compact per morsel, scalar broadcast), agg (group-by
# and its finalization), join (hash build, probe, partition ids), sort
# (order by, head), exchange (repartition/broadcast programs), batch
# (stacked multi-query programs of core.batch).
FAMILIES = ("scan", "agg", "join", "sort", "exchange", "batch")

_QUERY_ID: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "repro_query_id", default=None)
_TRACES: contextvars.ContextVar[Optional[Dict[str, int]]] = \
    contextvars.ContextVar("repro_traces", default=None)
_OFF = contextlib.nullcontext()


def span(name: str, **ids):
    """A host span ``name`` with ``ids`` and the current ``query_id`` as
    its arguments; a no-op while no profiler is tracing."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    qid = _QUERY_ID.get()
    if qid is not None:
        ids["query_id"] = qid
    return TraceAnnotation(name, **ids)


@contextlib.contextmanager
def query(query_id: int) -> Iterator[None]:
    """Attribute the spans of this context (and of threads started with a
    copy of it) to ``query_id``."""
    token = _QUERY_ID.set(query_id)
    try:
        yield
    finally:
        _QUERY_ID.reset(token)


@contextlib.contextmanager
def collect_traces(counts: Dict[str, int]) -> Iterator[None]:
    """Count the traces of ``program``s in this context into ``counts``
    (family -> traces); the driver wraps each query with this and surfaces
    the dict as ``executor_stats()['traces']``. A warm query reads none."""
    token = _TRACES.set(counts)
    try:
        yield
    finally:
        _TRACES.reset(token)


def program(family: str, fn):
    """``fn`` renamed ``<family>__<name>``, its body under
    ``jax.named_scope(family)``; each trace of it counts once for
    ``family`` in the active ``collect_traces`` scope."""
    if family not in FAMILIES:
        raise ValueError(f"unknown program family {family!r}; "
                         f"known: {FAMILIES}")

    @functools.wraps(fn)
    def named(*args, **kwargs):
        counts = _TRACES.get()
        if counts is not None:
            counts[family] = counts.get(family, 0) + 1
        with jax.named_scope(family):
            return fn(*args, **kwargs)

    named.__name__ = named.__qualname__ = \
        f"{family}__{fn.__name__.strip('_')}"
    return named
