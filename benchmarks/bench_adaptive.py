"""Adaptive execution: cold-vs-warm replan latency + kernel-residency.

Each query runs on a pallas-backend session with a shared feedback store:
the cold run plans from static catalog bounds (oversized capacities push
the hot aggregations/joins onto the jnp fallback), the warm run re-plans
from the cold run's observed cardinalities. Reported per query:

* cold and warm wall time (the warm figure includes the re-optimize, so
  the speedup is end-to-end, not just kernel time);
* jnp-fallback dispatch counts cold vs warm — the number adaptive
  re-planning exists to drive down;
* for the warm run's segmented-sum shape, the achieved fraction of the
  roofline bound (``launch.roofline``): the kernel's FLOPs/bytes from the
  compiled program's cost analysis against the running chip's published
  peaks. Off-TPU it reports "not measured"; on a TPU it tracks how close
  the warm dispatch runs to the §3.2 ceiling.

The scale factor matters: at the default 0.02 the static lineitem-side
aggregation bounds exceed the pallas group-capacity limit, so cold runs
genuinely fall back and the warm delta is visible. ``--sf`` overrides
(the CI smoke run shrinks it; fallback deltas then fade to zero).
"""

from __future__ import annotations

from .common import emit, timeit

# queries whose static bounds overflow pallas capacities at sf=0.02 (the
# warm replan brings every one of them back onto the kernels)
QUERIES = (3, 9, 10, 18)


def _fallbacks(stats) -> int:
    kd = stats.get("kernel_dispatch") or {}
    return sum(v for k, v in kd.items() if k.startswith("fallback"))


def _roofline_fraction(num_rows: int, num_groups: int) -> dict:
    """Achieved roofline fraction for the warm-shape segmented sum,
    via the shared ``launch.roofline.measure_program`` report ("not
    measured" off-TPU)."""
    import jax.numpy as jnp

    from repro.kernels import ops as kernel_ops
    from repro.launch import roofline

    if not kernel_ops.on_tpu():
        return {"rows": num_rows, "groups": num_groups,
                "achieved_fraction": "not measured"}

    gids = jnp.arange(num_rows, dtype=jnp.int32) % max(num_groups, 1)
    vals = jnp.ones((num_rows,), dtype=jnp.float32)
    report = roofline.measure_program(
        lambda g, v: kernel_ops.segmented_sum(g, v, num_groups),
        gids, vals)
    return {"rows": num_rows, "groups": num_groups, **report}


def run(sf: float = 0.02) -> None:
    from repro.core import Session
    from repro.core import plan as P
    from repro.tpch import dbgen, queries

    catalog = dbgen.load_catalog(sf=sf)
    for qnum in QUERIES:
        session = Session(catalog, feedback=True, kernel_backend="pallas")
        q = queries.build_query(qnum, catalog)

        cold_s = timeit(lambda: session.execute(session.optimize(q)),
                        warmup=0, iters=1)
        cold_fb = _fallbacks(session.executor_stats())
        # the store is seeded now: every further run is warm
        warm_s = timeit(lambda: session.execute(session.optimize(q)),
                        warmup=1, iters=3)
        warm_fb = _fallbacks(session.executor_stats())

        warm_plan = session.optimize(q)
        groups = [n.max_groups for n in _walk(warm_plan, P)
                  if isinstance(n, (P.Aggregation, P.Distinct))]
        roof = _roofline_fraction(
            num_rows=catalog.get("lineitem").num_rows(),
            num_groups=max(groups) if groups else 1)

        emit(f"adaptive_q{qnum}_cold_sf{sf}", cold_s,
             derived=f"fallbacks={cold_fb}")
        emit(f"adaptive_q{qnum}_warm_sf{sf}", warm_s,
             derived=(f"fallbacks={warm_fb} "
                      f"speedup={cold_s / warm_s:.2f}x "
                      f"roofline={roof['achieved_fraction']}"),
             detail={
                 "sf": sf,
                 "cold_seconds": cold_s,
                 "warm_seconds": warm_s,
                 "cold_fallbacks": cold_fb,
                 "warm_fallbacks": warm_fb,
                 "feedback": session.executor_stats()["feedback"],
                 "roofline": roof,
             })


def _walk(node, P):
    yield node
    for c in node.children():
        yield from _walk(c, P)


if __name__ == "__main__":
    run()
