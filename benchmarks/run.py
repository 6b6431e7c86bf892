# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows; per-benchmark JSON details land in results/bench/.

from __future__ import annotations

import argparse
import inspect
import sys
import time
import traceback


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description="Run benchmark suites")
    parser.add_argument("--sf", type=float, default=None,
                        help="TPC-H scale factor override for suites that "
                             "take one (CI smoke runs use a tiny value)")
    parser.add_argument("--only", default=None,
                        help="substring filter on suite names")
    args = parser.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (bench_adaptive, bench_barebones, bench_cold_hot,
                   bench_concurrency, bench_cost_perf, bench_exchange,
                   bench_kernels, bench_outofcore, bench_q5_scaling,
                   bench_scaleup, bench_scan_pipeline, bench_sql,
                   bench_storage_format, bench_weak_scaling)

    suites = [
        ("storage_format(§2.2)", bench_storage_format.run),
        ("scan_pipeline(§2.2)", bench_scan_pipeline.run),
        ("sql(frontend)", bench_sql.run),
        ("kernels(§3.2)", bench_kernels.run),
        ("concurrency(serving)", bench_concurrency.run),
        ("concurrency_small(batching)", bench_concurrency.run_small_queries),
        ("barebones(Table1)", bench_barebones.run),
        ("exchange(Fig5,§3.4)", bench_exchange.run),
        ("exchange_planned(§3.3)", bench_exchange.run_planned),
        ("q5_scaling(Fig6)", bench_q5_scaling.run),
        ("weak_scaling(Fig7)", bench_weak_scaling.run),
        ("scaleup(Fig8)", bench_scaleup.run),
        ("cold_hot(Table3)", bench_cold_hot.run),
        ("cost_perf(Fig9)", bench_cost_perf.run),
        ("outofcore(spill)", bench_outofcore.run),
        ("adaptive(feedback)", bench_adaptive.run),
    ]
    if args.only:
        suites = [(n, fn) for n, fn in suites if args.only in n]

    results = []   # (name, ok, seconds)
    for name, fn in suites:
        print(f"# === {name} ===", flush=True)
        kwargs = {}
        if args.sf is not None and "sf" in inspect.signature(fn).parameters:
            kwargs["sf"] = args.sf
        t0 = time.time()
        ok = True
        try:
            fn(**kwargs)
        except Exception:   # noqa: BLE001 — keep the harness running
            ok = False
            print(f"# FAILED {name}", flush=True)
            traceback.print_exc()
        dt = time.time() - t0
        results.append((name, ok, dt))
        print(f"# --- {name} done in {dt:.0f}s", flush=True)

    # scannable per-suite summary for CI logs
    print("# === summary ===", flush=True)
    for name, ok, dt in results:
        print(f"# {'PASS' if ok else 'FAIL'} {name} ({dt:.0f}s)", flush=True)
    failures = sum(1 for _, ok, _ in results if not ok)
    print(f"# {len(results) - failures}/{len(results)} suites passed",
          flush=True)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
