"""Collective operations in a reduced device trace (``trace.Reduced``),
for the readers of the exchange layer's device metrics.

A collective is an operation named ``all-to-all``, ``all-gather``,
``all-reduce``, ``collective-permute`` or ``reduce-scatter``. A
synchronous one spans its own event; an asynchronous one is a ``-start``
event and the next ``-done`` event of the same kind on the same device,
and spans from the start's beginning to the done's end, so that the
transfer in flight between the two counts. Per device, the seconds are
the union of these spans; a device is busy while an operation runs or a
collective is in flight.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from .trace import _length, _union

KINDS = ("all-to-all", "all-gather", "all-reduce", "collective-permute",
         "reduce-scatter")


def _kind(op_name: str) -> Tuple[str, str]:
    """``(kind, phase)`` of an operation name, phase ``""`` for a
    synchronous collective, ``"start"`` or ``"done"`` for an asynchronous
    half; ``("", "")`` for anything else."""
    head = op_name.split(" ", 1)[0]
    for kind in KINDS:
        if head == kind:
            return kind, ""
        if head in (kind + "-start", kind + "-done"):
            return kind, head[len(kind) + 1:]
    return "", ""


def spans(ops) -> List[Tuple[float, float]]:
    """The intervals (ns) in which a collective ran or was in flight."""
    out, pending = [], {}
    for o in sorted(ops, key=lambda o: o.start):
        kind, phase = _kind(o.name)
        if not kind:
            continue
        if phase == "":
            out.append((o.start, o.end))
        elif phase == "start":
            pending.setdefault(kind, []).append(o.start)
        elif pending.get(kind):
            out.append((pending[kind].pop(0), o.end))
    return out


def seconds(trace) -> Dict[str, Tuple[float, float]]:
    """``{device plane: (collective seconds, busy seconds)}``."""
    out = {}
    for dev, ops in trace.devices.items():
        coll = spans(ops)
        busy = _union([(o.start, o.end) for o in ops] + coll)
        out[dev] = (_length(_union(coll)) / 1e9, _length(busy) / 1e9)
    return out
