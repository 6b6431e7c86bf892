"""Serve N concurrent TPC-H clients through the query scheduler.

    PYTHONPATH=src python examples/serve_queries.py [--clients 8] [--sf 0.002]

Each client is a thread that submits a small dashboard of TPC-H queries
(with priorities) and waits for its results. The session's scheduler admits
them against a device-memory budget, interleaves their morsel pipelines,
coalesces duplicate in-flight queries, and serves repeats from the result
cache — the serving-engine behavior the paper's Presto coordinator provides
for its GPU workers.
"""

from __future__ import annotations

import argparse
import threading
import time

from repro.core import Session, SchedulerConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.tpch import dbgen, queries

# a "dashboard" of quick queries each client refreshes; repeats across
# clients are exactly what the plan/result caches and coalescing serve
DASHBOARD = (1, 6, 14, 3)


def client(session, catalog, cid: int, latencies: list, errors: list) -> None:
    """One synchronous client: submit the dashboard, wait for all results."""
    try:
        handles = []
        for i, qnum in enumerate(DASHBOARD):
            plan = queries.build_query(qnum, catalog, optimized=False)
            # the freshest dashboard panel is the most urgent
            handles.append(session.submit(plan, priority=len(DASHBOARD) - i))
        for h in handles:
            h.result()
            latencies.append(h.latency)
    except Exception as exc:  # noqa: BLE001 -- surface in the summary
        errors.append((cid, exc))


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--clients", type=int, default=8)
    parser.add_argument("--sf", type=float, default=0.002)
    args = parser.parse_args()

    catalog = dbgen.load_catalog(sf=args.sf)
    session = Session(catalog, num_workers=1, batch_rows=16384)
    session.scheduler_config = SchedulerConfig(
        memory_budget=512 << 20, max_concurrency=8,
        max_queue=args.clients * len(DASHBOARD))

    latencies: list = []
    errors: list = []
    threads = [threading.Thread(target=client,
                                args=(session, catalog, c, latencies, errors))
               for c in range(args.clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    if errors:
        raise SystemExit(f"{len(errors)} clients failed: {errors[:3]}")

    latencies.sort()
    n = len(latencies)
    stats = session.scheduler().stats()
    print(f"served {n} queries from {args.clients} clients "
          f"in {wall:.2f}s ({n / wall:.1f} q/s)")
    print(f"latency p50={latencies[n // 2] * 1e3:.1f}ms "
          f"p95={latencies[min(n - 1, int(n * 0.95))] * 1e3:.1f}ms "
          f"max={latencies[-1] * 1e3:.1f}ms")
    print(f"scheduler: completed={stats['completed']} "
          f"coalesced={stats['coalesced']} "
          f"result_cache_hits={stats['result_cache_hits']} "
          f"plan_cache_hits={stats['plan_cache_hits']} "
          f"rejected={stats['rejected']}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
