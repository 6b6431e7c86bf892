"""Device time by program family and idle time by program span, from a
``jax.profiler`` trace, read with nothing but JAX.

The engine names each device program after the operator family that
issues it: its XLA module reads ``jit_<family>__<fn>`` (``FAMILIES``), and
its host spans are named ``repro.<layer>.<what>``. ``modules(path,
window)`` reads the ``XLA Modules`` line of every device plane (the
programs, one event per execution); ``family_s`` puts each second of a
``trace.Reduced``'s device busy time down to the program running then,
and ``idle_by_span`` each idle second of the first device to the
innermost ``repro.*`` host span open then.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Tuple

# the program's families (``repro.core.tracing.FAMILIES``; the benchmark
# imports nothing of the program)
FAMILIES = ("scan", "agg", "join", "sort", "exchange", "batch")
OTHER = "other"
NONE = "none"
_MODULES_LINE = "XLA Modules"
# "jit_agg__aggregate(12374614001707826233)" -> "agg"
_FAMILY = re.compile(r"^jit_([a-z]+)__")
_HASH = re.compile(r"\(\d+\)$")

Event = Tuple[float, float, str]        # start ns, end ns, name


def family(module: str) -> str:
    """The family a module name carries, else ``OTHER``."""
    m = _FAMILY.match(module)
    return m.group(1) if m and m.group(1) in FAMILIES else OTHER


def modules(path: str, window: Tuple[float, float]
            ) -> Dict[str, List[Event]]:
    """Per device plane, its program executions clipped to ``window``."""
    from jax.profiler import ProfileData

    from .trace import _DEVICE_PLANE

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    lo, hi = window
    out: Dict[str, List[Event]] = {}
    for plane in data.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        evs = out.setdefault(plane.name, [])
        for line in plane.lines:
            if line.name != _MODULES_LINE:
                continue
            evs.extend((max(ev.start_ns, lo), min(ev.end_ns, hi), ev.name)
                       for ev in line.events
                       if ev.end_ns > lo and ev.start_ns < hi)
    return out


def _busy(ops) -> List[Tuple[float, float]]:
    from .trace import _union

    return _union([(o.start, o.end) for o in ops])


def family_s(reduced, mods: Dict[str, List[Event]]) -> Dict[str, float]:
    """Device busy seconds per family, averaged over devices. A busy
    instant belongs to the program executing then (``OTHER`` when its
    module names no family, or no module covers it), so the values sum to
    ``reduced.busy_s()``."""
    total: Dict[str, float] = {}
    for dev, ops in reduced.devices.items():
        progs, end = [], float("-inf")
        for ps, pe, name in sorted(mods.get(dev, [])):
            if pe > end:                # programs run one at a time
                progs.append((max(ps, end), pe, name))
                end = pe
        starts = [p[0] for p in progs]
        for s, e in _busy(ops):
            # covered time goes to the program, the rest to OTHER
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            covered = 0.0
            for ps, pe, name in progs[i:]:
                if ps >= e:
                    break
                cut = min(pe, e) - max(ps, s)
                if cut > 0:
                    fam = family(name)
                    total[fam] = total.get(fam, 0.0) + cut
                    covered += cut
            total[OTHER] = total.get(OTHER, 0.0) + (e - s - covered)
    k = max(len(reduced.devices), 1)
    return {f: ns / k / 1e9 for f, ns in total.items() if ns > 0}


def top_modules(mods: Dict[str, List[Event]], n: int = 10) -> List[List]:
    """The ``n`` programs (module names without their hash) with the most
    execution seconds, averaged over devices."""
    total: Dict[str, float] = {}
    for evs in mods.values():
        for s, e, name in evs:
            key = _HASH.sub("", name)
            total[key] = total.get(key, 0.0) + (e - s)
    k = max(len(mods), 1)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def idle_by_span(reduced, prefix: str = "repro.") -> Dict[str, float]:
    """Idle seconds of the first device per host span: each idle instant
    goes to the innermost (latest started) span named ``prefix...`` open
    then, on any thread, else to ``NONE``."""
    if not reduced.devices:
        return {}
    first = sorted(reduced.devices)[0]
    lo, hi = reduced.window
    idle, cursor = [], lo
    for s, e in _busy(reduced.devices[first]) + [(hi, hi)]:
        if s > cursor:
            idle.append((cursor, s))
        cursor = max(cursor, e)
    spans = [(s, e, n) for s, e, n in reduced.host
             if n.startswith(prefix) and e > s]
    # sweep the span edges and idle pieces in time order
    edges = sorted([(s, 1, i) for i, (s, _, _) in enumerate(spans)]
                   + [(e, 0, i) for i, (_, e, _) in enumerate(spans)])
    out: Dict[str, float] = {}
    active: Dict[int, Tuple[float, float]] = {}   # span -> (start, -end)
    j = 0
    for gs, ge in idle:
        t = gs
        while True:
            while j < len(edges) and edges[j][0] <= t:
                _, opens, i = edges[j]
                if opens:
                    active[i] = (spans[i][0], -spans[i][1])
                else:
                    active.pop(i, None)
                j += 1
            nxt = min(edges[j][0] if j < len(edges) else ge, ge)
            if nxt > t:
                name = (spans[max(active, key=active.get)][2]
                        if active else NONE)
                out[name] = out.get(name, 0.0) + (nxt - t) / 1e9
            if nxt >= ge:
                break
            t = nxt
    return out

