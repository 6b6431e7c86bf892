"""Share of the jnp join's probe calls that read the direct-address index
(``executor_stats()["join"]``), for ``bench/metrics/dense_probe_share.*``."""

from __future__ import annotations

from typing import Optional


def dense_probe_share_pct(run) -> Optional[float]:
    """100 x dense probes over all jnp-path probes, summed over completed
    queries; None where no completed query reports the counter or no
    query probed a jnp-path join."""
    dense = total = 0
    for r in run.completed:
        join = (r.stats or {}).get("join") or {}
        if "dense_probes" in join:
            dense += join["dense_probes"]
            total += join["dense_probes"] + join.get("sorted_probes", 0)
    return 100.0 * dense / total if total else None
