"""Distributed TPC-H on a real multi-device mesh with both exchange
protocols — the paper's Figure 5 experiment in miniature.

Run with forced host devices to see true multi-device placement:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_tpch.py
"""

import time

import jax

from repro.core import HostExchange, ICIExchange, Session
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_engine_mesh
from repro.tpch import dbgen, queries


def main():
    n_dev = jax.device_count()
    workers = min(n_dev, 8)
    mesh = make_engine_mesh(workers) if n_dev >= workers > 1 else None
    print(f"devices={n_dev}, workers={workers}, mesh={'yes' if mesh else 'no'}")

    catalog = dbgen.load_catalog(sf=0.002)
    for q in (1, 5, 9, 13):
        row = [f"q{q}"]
        for name, ex in (("ICI", ICIExchange(mesh=mesh)),
                         ("Host", HostExchange())):
            session = Session(catalog, num_workers=workers, exchange=ex,
                              batch_rows=8192, mesh=mesh)
            plan = queries.build_query(q, catalog)
            session.execute(plan)           # warm
            t0 = time.perf_counter()
            session.execute(plan)
            dt = time.perf_counter() - t0
            row.append(f"{name}={dt * 1e3:7.1f}ms staged="
                       f"{ex.stats.host_staged_bytes:>9d}B")
        print("  ".join(row))
    print("\nICI keeps the working set in device memory (staged=0); the "
          "host protocol round-trips every exchanged byte (paper §3.3).")


if __name__ == "__main__":
    enable_compile_cache()
    main()
