"""Share of the jnp join's probe calls that read the build's
direct-address index rather than binary-searching the sorted keys:
100 x the sum of ``executor_stats()["join"]["dense_probes"]`` over that
of ``dense_probes + sorted_probes``, completed queries of the window
(None on a program without the counter)."""

from benchlib.join_probes import dense_probe_share_pct


def read(run):
    return dense_probe_share_pct(run)
