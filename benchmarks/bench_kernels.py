"""Kernel-backend comparison: pallas kernels vs their jnp oracle paths.

Per-primitive micro-benchmarks of the four hot-spot kernels the engine
dispatches through ``kernels.ops`` — segmented aggregation (MXU
scatter-add vs ``jax.ops.segment_sum``), exchange histogram (radix vs
one-hot sum), stream-compaction addresses (two-level scan vs stable
argsort), and hash-table build + probe (open addressing vs
sort + searchsorted) — plus per-kernel achieved roofline fractions
(``launch.roofline.measure_program``), a Q6-shaped fused-vs-per-primitive
morsel scan, and a Q1/Q3 end-to-end run of both Session backends with
their ``kernel_dispatch`` counts.

Off-TPU the pallas numbers are *interpret mode* (the kernel body executed
as plain XLA ops): they validate the dispatch boundary and give a shape of
the work, not a speedup — on a TPU backend the same wrappers run the
compiled kernels. The emitted JSON (``results/bench/kernels.json``) is the
artifact the kernel-backend CI job uploads.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Session
from repro.core import relational as rel
from repro.kernels import ops as kernel_ops
from repro.kernels import ref
from repro.tpch import dbgen, queries

from .common import RESULTS, emit, timeit

N_ROWS = 65536
N_GROUPS = 4096
N_PARTS = 8
N_BUILD = 8192
TABLE = 4 * N_BUILD


def _block(fn):
    return lambda: jax.block_until_ready(fn())


def bench_primitives(detail: dict) -> None:
    """Per-primitive jnp-oracle vs pallas-kernel wall times."""
    rng = np.random.default_rng(0)
    gids = jnp.asarray(rng.integers(0, N_GROUPS, N_ROWS), jnp.int32)
    vals = jnp.asarray(rng.normal(0, 1, N_ROWS), jnp.float32)
    pids = jnp.asarray(rng.integers(0, N_PARTS, N_ROWS), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, N_ROWS).astype(bool))
    keys = jnp.asarray(rng.choice(10**7, N_BUILD, replace=False), jnp.int32)
    rows = jnp.arange(N_BUILD, dtype=jnp.int32)
    probes = jnp.asarray(rng.integers(0, 10**7, N_ROWS), jnp.int32)

    jit_ref_seg = jax.jit(
        lambda g, v: ref.segmented_agg(g, v, N_GROUPS, "sum"))
    jit_ref_hist = jax.jit(lambda p: ref.radix_histogram(p, N_PARTS))
    jit_ref_bps = jax.jit(ref.block_prefix_sum)
    jit_ref_probe = jax.jit(
        lambda bt, pk: rel.join_probe(bt, pk, jnp.ones_like(pk, bool), 1))

    pairs = [
        ("segmented_sum",
         _block(lambda: jit_ref_seg(gids, vals)),
         _block(lambda: kernel_ops.segmented_sum(gids, vals, N_GROUPS))),
        ("radix_histogram",
         _block(lambda: jit_ref_hist(pids)),
         _block(lambda: kernel_ops.radix_histogram(pids, N_PARTS))),
        ("block_prefix_sum",
         _block(lambda: jit_ref_bps(mask)),
         _block(lambda: kernel_ops.block_prefix_sum(mask))),
    ]
    for name, jnp_fn, pallas_fn in pairs:
        t_jnp = timeit(jnp_fn)
        t_pal = timeit(pallas_fn)
        emit(f"kernels_{name}_jnp", t_jnp)
        emit(f"kernels_{name}_pallas", t_pal,
             derived=f"x{t_pal / max(t_jnp, 1e-9):.1f}_vs_jnp")
        detail[name] = {"jnp_s": t_jnp, "pallas_s": t_pal}

    # join build + probe: sorted-searchsorted vs open-addressing table
    valid = jnp.ones((N_BUILD,), bool)
    t_jnp_build = timeit(_block(lambda: rel.join_build(keys, valid)))
    t_pal_build = timeit(
        _block(lambda: kernel_ops.build_table(keys, rows, TABLE)))
    bt = rel.join_build(keys, valid)
    tk, tv = kernel_ops.build_table(keys, rows, TABLE)
    t_jnp_probe = timeit(_block(lambda: jit_ref_probe(bt, probes)))
    t_pal_probe = timeit(
        _block(lambda: kernel_ops.hash_probe(tk, tv, probes,
                                             max_probes=64)))
    emit("kernels_join_build_jnp", t_jnp_build)
    emit("kernels_join_build_pallas", t_pal_build,
         derived=f"x{t_pal_build / max(t_jnp_build, 1e-9):.1f}_vs_jnp")
    emit("kernels_hash_probe_jnp", t_jnp_probe)
    emit("kernels_hash_probe_pallas", t_pal_probe,
         derived=f"x{t_pal_probe / max(t_jnp_probe, 1e-9):.1f}_vs_jnp")
    detail["join_build"] = {"jnp_s": t_jnp_build, "pallas_s": t_pal_build}
    detail["hash_probe"] = {"jnp_s": t_jnp_probe, "pallas_s": t_pal_probe}


def bench_roofline(detail: dict) -> None:
    """Achieved roofline fraction per kernel (``launch.roofline``).

    Each wrapper is lowered at the bench shape; FLOPs/bytes come from the
    compiled cost analysis, the bound from the running chip's published
    peaks. Off-TPU there is no device to hold a fraction against, so each
    kernel reports "not measured"."""
    from repro.launch import roofline

    rng = np.random.default_rng(0)
    gids = jnp.asarray(rng.integers(0, N_GROUPS, N_ROWS), jnp.int32)
    vals = jnp.asarray(rng.normal(0, 1, N_ROWS), jnp.float32)
    ivals = jnp.asarray(rng.integers(0, 100, N_ROWS), jnp.int32)
    pids = jnp.asarray(rng.integers(0, N_PARTS, N_ROWS), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, N_ROWS).astype(bool))
    keys = jnp.asarray(rng.choice(10**7, N_BUILD, replace=False), jnp.int32)
    rows = jnp.arange(N_BUILD, dtype=jnp.int32)
    probes = jnp.asarray(rng.integers(0, 10**7, N_ROWS), jnp.int32)
    tk, tv = kernel_ops.build_table(keys, rows, TABLE)

    programs = {
        "segmented_sum": (
            lambda g, v: kernel_ops.segmented_sum(g, v, N_GROUPS),
            (gids, vals)),
        "segmented_int_sum": (
            lambda g, v: kernel_ops.segmented_int_sum(g, v, N_GROUPS),
            (gids, ivals)),
        "segmented_minmax": (
            lambda g, v: kernel_ops.segmented_minmax(g, v, N_GROUPS, "max"),
            (gids, vals)),
        "radix_histogram": (
            lambda p: kernel_ops.radix_histogram(p, N_PARTS), (pids,)),
        "block_prefix_sum": (kernel_ops.block_prefix_sum, (mask,)),
        "build_table": (
            lambda k, r: kernel_ops.build_table(k, r, TABLE), (keys, rows)),
        "hash_probe": (
            lambda a, b, p: kernel_ops.hash_probe(a, b, p, max_probes=64),
            (tk, tv, probes)),
        "hash_probe_multi": (
            lambda a, b, p: kernel_ops.hash_probe_multi(a, b, p, 4,
                                                        max_probes=64),
            (tk, tv, probes)),
    }
    reports = {}
    for name, (fn, args) in programs.items():
        if not kernel_ops.on_tpu():
            emit(f"kernels_roofline_{name}", 0.0, derived="not measured")
            reports[name] = "not measured"
            continue
        rep = roofline.measure_program(fn, *args)
        reports[name] = rep
        emit(f"kernels_roofline_{name}", rep["measured_s"],
             derived=(f"roofline={rep['achieved_fraction']:.4f}"
                      f"_{rep['dominant']}"))
    detail["roofline"] = reports


def bench_fused_scan(detail: dict) -> None:
    """Q6-shaped scan morsel: filter (shipdate window, discount window,
    quantity cap) then project revenue. 'fused' runs the whole chain as
    one per-morsel pallas kernel (``core.fused``); 'per_primitive' is the
    dispatch baseline — the same stages launched as one kernel each, the
    way the unfused pipeline executes the morsel. The delta is the launch
    + HBM-round-trip overhead the fused path exists to remove."""
    from repro.core import dtypes as dt
    from repro.core import fused
    from repro.core.expr import col
    from repro.core.table import DeviceTable
    from repro.launch import roofline

    rng = np.random.default_rng(1)
    n = N_ROWS
    table = DeviceTable.from_numpy(
        {"l_shipdate": rng.integers(8700, 9200, n).astype(np.int32),
         "l_discount": rng.uniform(0.0, 0.1, n).astype(np.float32),
         "l_quantity": rng.uniform(1.0, 50.0, n).astype(np.float32),
         "l_extendedprice": rng.uniform(1.0, 1e5, n).astype(np.float32)},
        {"l_shipdate": dt.INT32, "l_discount": dt.FLOAT32,
         "l_quantity": dt.FLOAT32, "l_extendedprice": dt.FLOAT32})
    f = (col("l_shipdate").between(8800, 9100)
         & col("l_discount").between(0.05, 0.07)
         & (col("l_quantity") < 24.0))
    proj = (("v", col("l_extendedprice") * col("l_discount")),)
    stages = ((f, None), (None, proj))

    def fused_fn(t):
        out, _, _ = fused.fused_morsel_program(t, stages)
        return out

    def per_primitive_fn(t):
        for stage in stages:
            t, _, _ = fused.fused_morsel_program(t, (stage,))
        return t

    t_fused = timeit(_block(lambda: jax.jit(fused_fn)(table)))
    t_prim = timeit(_block(lambda: jax.jit(per_primitive_fn)(table)))
    if kernel_ops.on_tpu():
        rep = roofline.measure_program(fused_fn, table)
        frac = f"{rep['achieved_fraction']:.4f}"
    else:
        rep = frac = "not measured"
    emit("kernels_fused_q6_scan_per_primitive", t_prim)
    emit("kernels_fused_q6_scan_fused", t_fused,
         derived=(f"x{t_prim / max(t_fused, 1e-9):.2f}_vs_per_primitive_"
                  f"roofline={frac}"))
    detail["fused_q6_scan"] = {
        "fused_s": t_fused, "per_primitive_s": t_prim,
        "speedup": t_prim / max(t_fused, 1e-9), "roofline": rep}


def bench_end_to_end(detail: dict, sf: float) -> None:
    """Q1 + Q3 through both Session backends, with dispatch counts."""
    catalog = dbgen.load_catalog(sf=sf)
    for qnum in (1, 3):
        plan = queries.build_query(qnum, catalog)
        row = {}
        for backend in kernel_ops.BACKENDS:
            session = Session(catalog, num_workers=1,
                              kernel_backend=backend)
            session.execute(plan)             # compile warmup
            t = timeit(lambda s=session: s.execute(plan), iters=2)
            stats = session.executor_stats()
            emit(f"kernels_q{qnum}_{backend}", t)
            row[backend] = {"seconds": t,
                            "kernel_dispatch": stats["kernel_dispatch"]}
        detail[f"q{qnum}"] = row


def run(sf: float = 0.002) -> None:
    """Entry point for benchmarks.run: primitives + end-to-end backends."""
    detail: dict = {"on_tpu": kernel_ops.on_tpu(), "rows": N_ROWS}
    bench_primitives(detail)
    bench_roofline(detail)
    bench_fused_scan(detail)
    bench_end_to_end(detail, sf)
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "kernels.json"), "w") as f:
        json.dump(detail, f, indent=1)


if __name__ == "__main__":
    run()
