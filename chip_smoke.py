"""Bring-up run of the query engine on a TPU, through its user entry points.

    python chip_smoke.py                 # one chip: TPC-H SF 1
    python chip_smoke.py --chips 4       # only the four-chip mesh phase
    python chip_smoke.py --rehearse      # the same phases on the CPU with
                                         # interpret-mode kernels, tiny SF

One process does all the work, so it alone holds the chip. Phases:

1. device check: the default JAX device must be a TPU (``--rehearse``
   accepts the CPU instead and never reports ``"ok": true``);
2. data: TPC-H at ``--sf`` from a fixed seed (``tpch.dbgen``), registered
   in a ``Session`` catalog;
3. queries: TPC-H Q1, Q3, Q5, Q6 and Q14 from their SQL text, submitted
   together through the scheduler (``Session.sql(...).submit()`` +
   ``Session.gather()``), cold and then warm, once under
   ``kernel_backend="pallas"`` and once under ``"jnp"``; every result is
   checked against the numpy oracle;
4. batching: 16 batch-eligible small queries under
   ``SchedulerConfig(batching=True)`` must form a stacked launch with no
   failed stacked launch, and equal serial execution.

``--chips 4`` runs only the mesh phase instead: Q3 and Q5 at four workers
on a ``make_engine_mesh(4)`` mesh, once with ``ICIExchange`` and once with
``HostExchange``, checked against the oracle; the ICI runs must stage no
bytes through the host and keep scan and exchange outputs on four devices.

A failing phase raises, so the script exits non-zero. The last line of a
passing chip run is ``{"ok": true, "device": {...}}``. Compiled programs
go to the persistent cache (``repro.launch.compile_cache``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

QUERIES = (1, 3, 5, 6, 14)
MESH_QUERIES = (3, 5)
SMALL_QUERIES = 16
SEED = 19940729
# device memory the scheduler may admit at once: every query of the
# concurrent set fits, so none takes the spill path (16 GB HBM on v5e)
MEMORY_BUDGET = 12 << 30
# Rows per scan morsel. The engine's default (8192) makes Q3 quadratic at
# SF 1: its streaming aggregation re-sorts a ~4M-group accumulator after
# every morsel (733 of them), which did not finish within the run's time
# limit on a v5e chip. Million-row morsels keep the same operators and
# kernels with 6 merges instead.
BATCH_ROWS = 1 << 20


def device_line(rehearse: bool) -> dict:
    """Check for the chip before anything else; return the device record."""
    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu" and not rehearse:
        sys.exit(f"no TPU: JAX's default device is {dev['platform']!r} "
                 "(--rehearse runs the phases on the CPU)")
    return dev


def make_data(sf: float):
    from repro.tpch import dbgen

    t0 = time.perf_counter()
    data = dbgen.generate(sf=sf, seed=SEED)
    catalog = dbgen.catalog_from(data)
    rows = len(data["lineitem"]["l_orderkey"])
    mib = sum(a.nbytes for t in data.values() for a in t.values()) / 2**20
    print(f"data: sf={sf} lineitem_rows={rows} columns_mib={mib:.1f} "
          f"gen_s={time.perf_counter() - t0:.2f} batch_rows={BATCH_ROWS}",
          flush=True)
    return data, catalog


def _fallbacks(dispatch: dict) -> dict:
    out = {"fallback_agg": 0, "fallback_probe": 0}
    out.update({k: v for k, v in dispatch.items() if k.startswith("fallback")})
    return out


def run_queries(data, catalog, backend: str, rehearse: bool) -> dict:
    """Q1/3/5/6/14 submitted together, cold then warm, on one backend.
    Returns the kernel dispatch counts summed over both passes."""
    from repro.core import SchedulerConfig, Session
    from repro.kernels import ops as kernel_ops
    from repro.tpch import oracle, sqltext
    from repro.tpch.compare import assert_results_match

    session = Session(catalog, kernel_backend=backend, batch_rows=BATCH_ROWS)
    session.scheduler_config = SchedulerConfig(
        memory_budget=MEMORY_BUDGET, cache_results=False)
    texts = {q: sqltext.sql_text(q, catalog) for q in QUERIES}
    wants = {q: oracle.ORACLES[q](data) for q in QUERIES}
    times = {q: {} for q in QUERIES}
    total: dict = {}
    try:
        for run in ("cold", "warm"):
            t0 = time.perf_counter()
            handles = {q: session.sql(texts[q]).submit() for q in QUERIES}
            results = session.gather(*handles.values())
            wall = time.perf_counter() - t0
            for (q, h), res in zip(handles.items(), results):
                assert_results_match(res, wants[q], q)
                times[q][run] = h.latency
                times[q][run + "_dispatch"] = dict(
                    h.executor_stats["kernel_dispatch"])
                for k, v in h.executor_stats["kernel_dispatch"].items():
                    total[k] = total.get(k, 0) + v
            print(f"{backend} {run}: {len(QUERIES)} queries in flight, "
                  f"wall_s={wall:.3f}", flush=True)
        spilled = session.scheduler().stats()["spill_admitted"]
    finally:
        session.scheduler().close()
    if spilled:
        raise RuntimeError(f"{spilled} queries took the spill path")
    for q in QUERIES:
        t = times[q]
        disp = t["warm_dispatch"]
        print(f"q{q} backend={backend} oracle=match cold_s={t['cold']:.3f} "
              f"warm_s={t['warm']:.3f} "
              f"kernel_dispatch={json.dumps(disp, sort_keys=True)} "
              f"cold_kernel_dispatch="
              f"{json.dumps(t['cold_dispatch'], sort_keys=True)} "
              f"fallbacks={json.dumps(_fallbacks(disp), sort_keys=True)}",
              flush=True)
    if backend == "pallas":
        if not rehearse and not kernel_ops.on_tpu():
            raise RuntimeError("pallas pass ran without compiled kernels")
        if total.get("agg", 0) == 0:
            raise RuntimeError(f"pallas pass dispatched no agg kernel: {total}")
        if total.get("probe", 0) + total.get("fused", 0) == 0:
            raise RuntimeError(
                f"pallas pass dispatched no probe/fused kernel: {total}")
    return total


def run_batching(data, catalog) -> None:
    """Small queries through the batching scheduler == serial execution."""
    import numpy as np

    from repro.core import SchedulerConfig, Session
    from repro.tpch import queries
    from repro.tpch.compare import assert_results_match

    keys = np.asarray(data["orders"]["o_orderkey"])
    builders = queries.small_queries(catalog, keys, SMALL_QUERIES)
    serial = Session(catalog, kernel_backend="pallas", batch_rows=BATCH_ROWS)
    t0 = time.perf_counter()
    refs = [serial.execute(b.optimized()) for b in builders]
    serial_s = time.perf_counter() - t0

    session = Session(catalog, kernel_backend="pallas", batch_rows=BATCH_ROWS)
    session.scheduler_config = SchedulerConfig(
        memory_budget=MEMORY_BUDGET, cache_results=False, batching=True,
        batch_window_ms=200.0, max_batch=SMALL_QUERIES,
        max_queue=2 * SMALL_QUERIES)
    handles: list = [None] * len(builders)
    errors: list = []

    def client(c: int, n_clients: int) -> None:
        try:
            for i in range(c, len(builders), n_clients):
                handles[i] = session.submit(builders[i])
        except Exception as exc:  # noqa: BLE001 -- re-raised below
            errors.append(exc)

    try:
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(c, 4))
                   for c in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        results = session.gather(*handles)
        batched_s = time.perf_counter() - t0
        stats = session.scheduler().stats()
    finally:
        session.scheduler().close()
    for i, (got, ref) in enumerate(zip(results, refs)):
        assert_results_match(got, ref, f"small{i}")
    print(f"batching: queries={len(builders)} batches={stats['batches']} "
          f"batched_queries={stats['batched_queries']} "
          f"batch_failures={stats['batch_failures']} "
          f"serial_s={serial_s:.3f} batched_s={batched_s:.3f} "
          f"(first run of each: includes compiles)", flush=True)
    if stats["batch_failures"]:
        raise RuntimeError(
            f"stacked launch failed: {stats['first_batch_failure']}")
    if stats["batches"] == 0:
        raise RuntimeError("no stacked launch formed")


def run_mesh(data, catalog, chips: int) -> None:
    """Q3/Q5 at ``chips`` workers on a real mesh, ICI vs host exchange."""
    from repro.core import HostExchange, ICIExchange, Session
    from repro.launch.mesh import make_engine_mesh
    from repro.tpch import oracle, sqltext
    from repro.tpch.compare import assert_results_match

    def devices(table) -> int:
        return len(table.validity.sharding.device_set)

    class PlacementICI(ICIExchange):
        """ICI exchange that records how many devices hold the shards of
        each exchange's input (the scan side) and output."""

        def __init__(self, mesh):
            super().__init__(mesh=mesh)
            self.placements: list = []

        def clone(self):
            return self

        def repartition(self, table, key_names, num_workers):
            out = super().repartition(table, key_names, num_workers)
            self.placements.append(("repartition", devices(table),
                                    devices(out)))
            return out

        def broadcast(self, table, num_workers):
            out = super().broadcast(table, num_workers)
            self.placements.append(("broadcast", devices(table),
                                    devices(out)))
            return out

    mesh = make_engine_mesh(chips)
    for q in MESH_QUERIES:
        text = sqltext.sql_text(q, catalog)
        want = oracle.ORACLES[q](data)
        for name in ("ici", "host"):
            ex = PlacementICI(mesh=mesh) if name == "ici" else HostExchange()
            session = Session(catalog, num_workers=chips, mesh=mesh,
                              exchange=ex, kernel_backend="jnp",
                              batch_rows=BATCH_ROWS)
            walls = []
            for _ in ("cold", "warm"):
                t0 = time.perf_counter()
                res = session.sql(text).collect()
                walls.append(time.perf_counter() - t0)
                assert_results_match(res, want, q)
            staged = ex.stats.host_staged_bytes
            line = (f"mesh q{q} exchange={name} workers={chips} "
                    f"oracle=match cold_s={walls[0]:.3f} "
                    f"warm_s={walls[1]:.3f} host_staged_bytes={staged} "
                    f"rows_moved={ex.stats.rows_moved}")
            if name == "ici":
                if staged != 0:
                    raise RuntimeError(f"ICI exchange staged {staged} B")
                placed = ex.placements
                if not placed:
                    raise RuntimeError(f"q{q} at W={chips} ran no exchange")
                bad = [p for p in placed if p[1] != chips or p[2] != chips]
                if bad:
                    raise RuntimeError(
                        f"exchange shards not on {chips} devices: {bad}")
                line += (f" exchanges={len(placed)} "
                         f"shard_devices={chips}")
                placed.clear()
            print(line, flush=True)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs only the four-chip mesh phase")
    parser.add_argument("--sf", type=float, default=None,
                        help="TPC-H scale factor (default 1; 0.01 with "
                             "--rehearse)")
    parser.add_argument("--rehearse", action="store_true",
                        help="run on the CPU with interpret-mode kernels; "
                             "never reports ok")
    args = parser.parse_args(argv)
    sf = args.sf if args.sf is not None else (0.01 if args.rehearse else 1.0)

    dev = device_line(args.rehearse)
    if dev["count"] < args.chips:
        sys.exit(f"--chips {args.chips} needs {args.chips} devices; "
                 f"{dev['count']} found")
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile_cache: {enable_compile_cache()}", flush=True)

    data, catalog = make_data(sf)
    t0 = time.perf_counter()
    if args.chips > 1:
        run_mesh(data, catalog, args.chips)
    else:
        for backend in ("pallas", "jnp"):
            run_queries(data, catalog, backend, args.rehearse)
        run_batching(data, catalog)
    print(f"phases_s={time.perf_counter() - t0:.1f}", flush=True)
    if args.rehearse:
        print("rehearsal passed (CPU, interpret-mode kernels: no chip "
              "result)", flush=True)
        return
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
