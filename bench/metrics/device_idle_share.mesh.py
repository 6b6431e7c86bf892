"""Share of the traced window in which the device ran no operation,
averaged over the devices the cell uses."""

from benchlib.readings import idle_share_pct


def read(run):
    return idle_share_pct(run)
