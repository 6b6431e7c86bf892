"""Quickstart: run a distributed TPC-H query on the device-resident engine.

    PYTHONPATH=src python examples/quickstart.py

(or ``pip install -e .`` once and drop the PYTHONPATH.)
"""

import numpy as np

from repro.core import ICIExchange, Session, dtypes as dt
from repro.core.expr import col
from repro.launch.compile_cache import enable_compile_cache
from repro.tpch import dbgen, queries


def main():
    # 1) a tiny ad-hoc query on your own data, in the fluent builder API ---
    #    every step validates column names/types against the propagated
    #    schema, and .collect() runs the plan through the rule-based
    #    optimizer (predicate pushdown, column pruning, join distribution,
    #    capacity hints) before the driver executes it.
    catalog = dbgen.load_catalog(sf=0.002)          # TPC-H-like tables
    rng = np.random.default_rng(0)
    catalog.register_numpy(
        "events",
        {"user": rng.integers(0, 100, 5000),
         "amount": rng.random(5000).astype(np.float32) * 50},
        {"user": dt.INT32, "amount": dt.FLOAT32},
        unique_keys=())

    session = Session(catalog, num_workers=4, exchange=ICIExchange(),
                      batch_rows=4096)

    top_spenders = (session.table("events")
                    .filter(col("amount") > 10.0)
                    .group_by("user")
                    .agg(spend=("sum", "amount"))
                    .order_by("spend", descending=[True], limit=5))

    print(top_spenders.explain())                   # plan before/after rules
    out = top_spenders.collect()
    print("\ntop spenders:",
          list(zip(out["user"], np.round(out["spend"], 1))))

    # 2) a real TPC-H query, distributed, data never leaves the device -----
    q5 = queries.build_query(5, catalog)            # optimizer-planned tree
    res = session.execute(q5)
    print("\nTPC-H Q5 (revenue per nation):")
    for n, r in zip(res["n_name"], res["revenue"]):
        print(f"  nation={int(n):2d} revenue={float(r):14.2f}")
    ex = session.exchange
    print(f"\nexchange: rounds={ex.stats.rounds} "
          f"rows_moved={ex.stats.rows_moved} "
          f"host_staged_bytes={ex.stats.host_staged_bytes} (device-native!)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
