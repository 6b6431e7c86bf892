"""Share of the chips' busy time in which a collective (all-to-all,
all-gather, all-reduce, collective-permute, reduce-scatter) ran or was in
flight: 100 x collective seconds summed over the cell's devices, over
their busy seconds summed (``benchlib/collectives.py``)."""

from benchlib import collectives


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    per_device = collectives.seconds(run.trace).values()
    busy = sum(b for _, b in per_device)
    return 100.0 * sum(c for c, _ in per_device) / busy if busy > 0 else None
