"""The exchanges' share of the interchip interconnect's peak while a
collective runs: 100 x B / (T x ici_bw x 4).

B is the bytes the exchanges moved, summed over completed queries of
``bytes_moved`` in ``executor_stats()["exchanges"]``: each exchange's
valid rows that change worker, times their row bytes, so set by the work
and not by padded buffers. T is the collective seconds summed over the
cell's devices (``benchlib/collectives.py``). ``ici_bw`` is one link's
published rate and a v5e chip has four (1,600 Gbit/s in all). None where
either is absent."""

from benchlib import collectives
from benchlib.peaks import PEAKS

LINKS = 4


def moved_bytes(requests):
    """B, or None where no completed query reports an exchange."""
    total, seen = 0, False
    for r in requests:
        for e in ((r.stats or {}).get("exchanges") or {}).values():
            seen = True
            total += e.get("bytes_moved", 0)
    return total if seen else None


def read(run, device_kind=None):
    if run.trace is None or not run.trace.devices:
        return None
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    if device_kind not in PEAKS:
        return None
    b = moved_bytes(run.completed)
    t = sum(c for c, _ in collectives.seconds(run.trace).values())
    if b is None or t <= 0:
        return None
    return 100.0 * b / (t * PEAKS[device_kind]["ici_bw"] * LINKS)
