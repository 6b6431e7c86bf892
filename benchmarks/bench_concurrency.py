"""Concurrent-serving throughput/latency vs the serial baseline.

The paper's coordinator admits many concurrent queries and multiplexes GPU
workers under a device-memory budget. This suite measures what that serving
layer buys: N concurrent clients each submit a fixed dashboard of TPC-H
queries through ``Session.submit`` (admission control + plan/result caches
+ in-flight coalescing + interleaved morsel pipelines), against a serial
baseline that executes the identical workload one query at a time with no
scheduler. Reported per client count: wall time, query throughput, p50/p95
latency, and the speedup over serial; every scheduled result is validated
against the numpy oracle. A "cold" scheduler row disables the result cache
and coalescing (every query executes for real; the plan cache stays on),
separating pipeline-overlap + plan-cache gains from result-reuse gains.

A second, **open-loop** mode measures latency under load the way serving
systems are actually characterized: a dispatcher submits queries at a
fixed arrival rate regardless of completions (no closed-loop
self-throttling), and the suite reports p50/p99 latency per offered rate
-- queueing delay shows up in the tail as the rate approaches the
scheduler's capacity.

A third, **small-queries** mode (``--small-queries``) measures inter-query
batching (``SchedulerConfig.batching``, core/batch.py): N concurrent
clients each issue distinct-literal point lookups, filtered global
aggregates, and low-cardinality group-bys — the high-QPS serving regime
where fixed per-query dispatch cost dwarfs compute — once through the
plain scheduler and once with batching on (compatible queries coalesce
into stacked kernel launches). Reported: throughput both ways, the
batched:unbatched speedup, stacked-launch counters, and open-loop p50/p99
at a fixed arrival rate; every batched result is verified row-count- and
checksum-identical against scheduler-less serial execution.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from repro.core import Session, SchedulerConfig
from repro.tpch import dbgen, oracle, queries

from .common import emit

DASHBOARD = (1, 6, 14, 3)           # quick, shape-diverse queries
CLIENT_COUNTS = (1, 4, 16)


def _assert_oracle(engine: dict, orc: dict, qnum: int) -> None:
    """Order-insensitive engine-vs-oracle row match (numeric columns)."""
    cols = [c for c in orc if c in engine]
    assert cols, f"q{qnum}: no common columns"
    n = np.atleast_1d(np.asarray(orc[cols[0]])).shape[0]
    eng = np.stack([np.asarray(engine[c], dtype=np.float64).reshape(n)
                    for c in cols])
    orc_ = np.stack([np.asarray(orc[c], dtype=np.float64).reshape(n)
                     for c in cols])
    eo = np.lexsort(np.round(eng, 2)[::-1])
    oo = np.lexsort(np.round(orc_, 2)[::-1])
    np.testing.assert_allclose(eng[:, eo], orc_[:, oo], rtol=2e-3, atol=1e-2,
                               err_msg=f"q{qnum} mismatch vs oracle")


def _serial(catalog, n_clients: int) -> float:
    """Baseline: the same workload, one query at a time, no scheduler."""
    session = Session(catalog, num_workers=1, batch_rows=16384)
    t0 = time.perf_counter()
    for _ in range(n_clients):
        for qnum in DASHBOARD:
            session.execute(queries.build_query(qnum, catalog))
    return time.perf_counter() - t0


def _scheduled(catalog, n_clients: int, oracles=None,
               cache_results: bool = True):
    """N client threads submitting through the scheduler; returns
    (wall_seconds, sorted per-query latencies, scheduler stats)."""
    session = Session(catalog, num_workers=1, batch_rows=16384)
    session.scheduler_config = SchedulerConfig(
        memory_budget=512 << 20, max_concurrency=8,
        max_queue=max(64, n_clients * len(DASHBOARD)),
        cache_results=cache_results)
    latencies: list = []
    errors: list = []

    def client():
        try:
            handles = [session.submit(
                queries.build_query(q, catalog, optimized=False))
                for q in DASHBOARD]
            for qnum, h in zip(DASHBOARD, handles):
                res = h.result()
                latencies.append(h.latency)
                if oracles is not None:
                    _assert_oracle(res, oracles[qnum], qnum)
        except Exception as exc:  # noqa: BLE001 -- fail the suite below
            errors.append(exc)

    threads = [threading.Thread(target=client) for _ in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    latencies.sort()
    return wall, latencies, session.scheduler().stats()


def _open_loop(catalog, rate_qps: float, n_queries: int,
               cache_results: bool = False):
    """Open-loop arrivals: submit one dashboard query every ``1/rate``
    seconds from a dispatcher thread, never waiting for completions.
    Returns (sorted latencies, offered seconds, scheduler stats)."""
    session = Session(catalog, num_workers=1, batch_rows=16384)
    session.scheduler_config = SchedulerConfig(
        memory_budget=512 << 20, max_concurrency=8,
        max_queue=max(64, n_queries), cache_results=cache_results)
    handles = []
    interval = 1.0 / rate_qps
    t0 = time.perf_counter()
    for i in range(n_queries):
        target = t0 + i * interval
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)       # fixed schedule: no self-throttling
        qnum = DASHBOARD[i % len(DASHBOARD)]
        handles.append(session.submit(
            queries.build_query(qnum, catalog, optimized=False)))
    offered = time.perf_counter() - t0
    session.gather(*handles)
    lats = sorted(h.latency for h in handles)
    return lats, offered, session.scheduler().stats()


def run(sf: float = 0.005) -> None:
    catalog = dbgen.load_catalog(sf=sf)
    data = dbgen.generate(sf=sf)
    oracles = {q: oracle.ORACLES[q](data) for q in DASHBOARD}

    # warm jit caches once so neither path pays first-compile inside timing
    warm = Session(catalog, num_workers=1, batch_rows=16384)
    for qnum in DASHBOARD:
        warm.execute(queries.build_query(qnum, catalog))

    for n in CLIENT_COUNTS:
        n_queries = n * len(DASHBOARD)
        serial_s = _serial(catalog, n)
        wall, lats, stats = _scheduled(catalog, n, oracles=oracles)
        cold_wall, _, _ = _scheduled(catalog, n, cache_results=False)
        speedup = serial_s / wall
        cold_speedup = serial_s / cold_wall
        p50 = lats[len(lats) // 2]
        p95 = lats[min(len(lats) - 1, int(len(lats) * 0.95))]
        emit(f"concurrency_c{n}", wall,
             derived=f"{speedup:.2f}x_vs_serial",
             detail={
                 "clients": n,
                 "queries": n_queries,
                 "serial_seconds": serial_s,
                 "scheduled_seconds": wall,
                 "cold_scheduled_seconds": cold_wall,
                 "speedup_vs_serial": speedup,
                 "cold_speedup_vs_serial": cold_speedup,
                 "throughput_qps": n_queries / wall,
                 "serial_throughput_qps": n_queries / serial_s,
                 "latency_p50_s": p50,
                 "latency_p95_s": p95,
                 "scheduler": stats,
             })
        print(f"# clients={n:2d}: serial {serial_s:.2f}s | scheduled "
              f"{wall:.2f}s ({speedup:.2f}x) | cold {cold_wall:.2f}s "
              f"({cold_speedup:.2f}x) | p50 {p50 * 1e3:.0f}ms "
              f"p95 {p95 * 1e3:.0f}ms | coalesced={stats['coalesced']} "
              f"cache_hits={stats['result_cache_hits']}", flush=True)

    # open-loop latency percentiles under offered load (cold: every
    # arrival is a real execution, so queueing is not hidden by the
    # result cache)
    for rate in (2.0, 8.0):
        n_queries = max(int(rate) * 4, len(DASHBOARD))
        lats, offered, stats = _open_loop(catalog, rate, n_queries)
        p50 = lats[len(lats) // 2]
        p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
        emit(f"concurrency_openloop_r{rate:g}", p99,
             derived=f"p50_{p50 * 1e3:.0f}ms",
             detail={
                 "offered_rate_qps": rate,
                 "queries": n_queries,
                 "offered_seconds": offered,
                 "latency_p50_s": p50,
                 "latency_p99_s": p99,
                 "latency_max_s": lats[-1],
                 "scheduler": stats,
             })
        print(f"# open-loop {rate:g} q/s: p50 {p50 * 1e3:.0f}ms "
              f"p99 {p99 * 1e3:.0f}ms over {n_queries} arrivals", flush=True)


# --- small-queries mode: inter-query batching vs plain dispatch ------------

SMALL_PER_CLIENT = 3    # one query of each shape per client


def _assert_checksums(ref: dict, got: dict, label: str) -> None:
    """Row-count + per-column checksum identity (floats to reduction
    order; ints/keys exact)."""
    assert set(ref) == set(got), f"{label}: column sets differ"
    for c in ref:
        r, g = np.asarray(ref[c]), np.asarray(got[c])
        assert r.shape == g.shape, f"{label}.{c}: {r.shape} != {g.shape}"
        if np.issubdtype(r.dtype, np.floating):
            np.testing.assert_allclose(
                np.sum(g, dtype=np.float64), np.sum(r, dtype=np.float64),
                rtol=2e-3, atol=1e-2, err_msg=f"{label}.{c} checksum")
        else:
            np.testing.assert_array_equal(g, r,
                                          err_msg=f"{label}.{c} rows")


def _scheduled_small(catalog, builders, n_clients: int, batching: bool):
    """N client threads, ``SMALL_PER_CLIENT`` queries each; returns
    (wall_seconds, results in builder order, sorted latencies, stats)."""
    session = Session(catalog, num_workers=1, batch_rows=16384)
    session.scheduler_config = SchedulerConfig(
        memory_budget=512 << 20, max_concurrency=8,
        max_queue=max(64, len(builders)), cache_results=False,
        batching=batching, batch_window_ms=10.0, max_batch=32)
    results: list = [None] * len(builders)
    latencies: list = []
    errors: list = []

    def client(c: int):
        try:
            idx = range(c * SMALL_PER_CLIENT, (c + 1) * SMALL_PER_CLIENT)
            handles = [(i, session.submit(builders[i])) for i in idx]
            for i, h in handles:
                results[i] = h.result()
                latencies.append(h.latency)
        except Exception as exc:  # noqa: BLE001 -- fail the suite below
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    latencies.sort()
    return wall, results, latencies, session.scheduler().stats()


def _open_loop_small(catalog, builders, rate_qps: float, batching: bool):
    """Open-loop arrivals of the small-query workload; returns sorted
    latencies (queue-to-result, so the batch window shows up in p50)."""
    session = Session(catalog, num_workers=1, batch_rows=16384)
    session.scheduler_config = SchedulerConfig(
        memory_budget=512 << 20, max_concurrency=8,
        max_queue=max(64, len(builders)), cache_results=False,
        batching=batching, batch_window_ms=10.0, max_batch=32)
    handles = []
    interval = 1.0 / rate_qps
    t0 = time.perf_counter()
    for i, b in enumerate(builders):
        target = t0 + i * interval
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        handles.append(session.submit(b))
    session.gather(*handles)
    return sorted(h.latency for h in handles)


def run_small_queries(sf: float = 0.005, clients: int = 16) -> None:
    """Batched vs unbatched dispatch for concurrent small queries."""
    catalog = dbgen.load_catalog(sf=sf)
    data = dbgen.generate(sf=sf)
    order_keys = np.asarray(data["orders"]["o_orderkey"])
    n_queries = clients * SMALL_PER_CLIENT
    builders = queries.small_queries(catalog, order_keys, n_queries)

    # scheduler-less serial reference: timing baseline AND the oracle the
    # batched results must be row/checksum-identical to. Every mode runs
    # the workload once untimed first — jit/XLA compiles amortize across
    # a serving lifetime (the batched path compiles one stacked program
    # per shape and lane count), so the timed pass is steady-state
    # dispatch, the thing batching exists to amortize.
    serial_session = Session(catalog, num_workers=1, batch_rows=16384)
    plans = [b.optimized() for b in builders]
    refs = [serial_session.execute(p) for p in plans]
    t0 = time.perf_counter()
    refs = [serial_session.execute(p) for p in plans]
    serial_s = time.perf_counter() - t0

    _scheduled_small(catalog, builders, clients, batching=False)
    plain_wall, plain_res, plain_lats, plain_stats = _scheduled_small(
        catalog, builders, clients, batching=False)
    _scheduled_small(catalog, builders, clients, batching=True)
    bat_wall, bat_res, bat_lats, bat_stats = _scheduled_small(
        catalog, builders, clients, batching=True)
    for i, (r, p, b) in enumerate(zip(refs, plain_res, bat_res)):
        _assert_checksums(r, p, f"plain q{i}")
        _assert_checksums(r, b, f"batched q{i}")

    speedup = plain_wall / bat_wall
    p50 = bat_lats[len(bat_lats) // 2]
    p99 = bat_lats[min(len(bat_lats) - 1, int(len(bat_lats) * 0.99))]
    emit(f"concurrency_small_c{clients}", bat_wall,
         derived=f"{speedup:.2f}x_batched_vs_unbatched",
         detail={
             "clients": clients,
             "queries": n_queries,
             "serial_seconds": serial_s,
             "unbatched_seconds": plain_wall,
             "batched_seconds": bat_wall,
             "batched_speedup": speedup,
             "unbatched_throughput_qps": n_queries / plain_wall,
             "batched_throughput_qps": n_queries / bat_wall,
             "batched_latency_p50_s": p50,
             "batched_latency_p99_s": p99,
             "stacked_launches": bat_stats["batches"],
             "batched_queries": bat_stats["batched_queries"],
             "unbatched_scheduler": plain_stats,
             "batched_scheduler": bat_stats,
         })
    print(f"# small-queries clients={clients}: serial {serial_s:.2f}s | "
          f"unbatched {plain_wall:.2f}s "
          f"({n_queries / plain_wall:.1f} q/s) | batched {bat_wall:.2f}s "
          f"({n_queries / bat_wall:.1f} q/s, {speedup:.2f}x) | "
          f"{bat_stats['batched_queries']}/{n_queries} queries in "
          f"{bat_stats['batches']} stacked launches | "
          f"p50 {p50 * 1e3:.0f}ms p99 {p99 * 1e3:.0f}ms", flush=True)

    # open-loop: does the batch window hurt latency at moderate load?
    rate = max(8.0, clients / 2)
    for batching in (False, True):
        lats = _open_loop_small(catalog, builders, rate, batching)
        p50 = lats[len(lats) // 2]
        p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
        mode = "batched" if batching else "unbatched"
        emit(f"concurrency_small_openloop_{mode}", p99,
             derived=f"p50_{p50 * 1e3:.0f}ms",
             detail={"offered_rate_qps": rate, "queries": n_queries,
                     "latency_p50_s": p50, "latency_p99_s": p99,
                     "latency_max_s": lats[-1], "batching": batching})
        print(f"# small-queries open-loop {rate:g} q/s [{mode}]: "
              f"p50 {p50 * 1e3:.0f}ms p99 {p99 * 1e3:.0f}ms", flush=True)


def main(argv=None) -> None:
    import argparse
    parser = argparse.ArgumentParser(
        description="Concurrent-serving benchmarks")
    parser.add_argument("--sf", type=float, default=0.005,
                        help="TPC-H scale factor")
    parser.add_argument("--small-queries", action="store_true",
                        help="run the inter-query batching mode instead "
                             "of the dashboard suite")
    parser.add_argument("--clients", type=int, default=16,
                        help="concurrent clients (small-queries mode)")
    args = parser.parse_args(argv)
    if args.small_queries:
        run_small_queries(sf=args.sf, clients=args.clients)
    else:
        run(sf=args.sf)


if __name__ == "__main__":
    main()
