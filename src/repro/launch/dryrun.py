import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding config is coherent (no GSPMD errors, supported collectives),
  * the program fits (memory_analysis), and
  * yields cost_analysis + collective bytes for the roofline (§Roofline).

Results are cached per cell in results/dryrun/<cell>.json so the sweep is
resumable; `python -m repro.launch.dryrun --arch qwen2_1_5b --shape train_4k`
runs one cell, no flags runs everything outstanding.
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs.base import ARCH_IDS, SHAPES, applicable_shapes, get_config
from ..models.model import build_model
from ..models import sharding as shp
from ..train.train_step import make_train_step, train_state_init
from . import roofline as rf
from .mesh import axes_of, make_production_mesh

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _cell_path(arch, shape, mesh_name):
    return os.path.join(RESULTS_DIR, f"{arch}__{shape}__{mesh_name}.json")


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               zero_stage: int = 3):
    """Lower + compile one cell; returns the roofline record."""
    import dataclasses as _dc

    cfg = get_config(arch)
    model = build_model(cfg)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = _dc.replace(axes_of(mesh), zero_stage=zero_stage)
    chips = mesh.devices.size

    with mesh, shp.use_axes(axes, mesh):
        state_struct = jax.eval_shape(
            lambda: train_state_init(model, jax.random.key(0)))
        param_struct = state_struct.params
        p_shard = shp.params_shardings(param_struct, axes, mesh)
        in_specs = model.input_specs(shape)
        b_shard = shp.batch_shardings(in_specs, axes, mesh)

        if shape.kind == "train":
            step = make_train_step(model)
            s_shard = shp.params_shardings(state_struct, axes, mesh)
            lowered = jax.jit(step, in_shardings=(s_shard, b_shard)) \
                .lower(state_struct, in_specs)
        elif shape.kind == "prefill":
            def prefill(params, batch):
                return model.prefill(params, batch)
            lowered = jax.jit(prefill, in_shardings=(p_shard, b_shard)) \
                .lower(param_struct, in_specs)
        else:  # decode
            cache_struct = model.cache_specs(shape)
            c_shard = shp.cache_shardings(cache_struct, shape.seq_len, axes,
                                          mesh)
            pos_struct = jax.ShapeDtypeStruct((), jnp.int32)
            from jax.sharding import NamedSharding, PartitionSpec
            pos_shard = NamedSharding(mesh, PartitionSpec())

            def decode(params, tokens, caches, pos):
                return model.decode_step(params, tokens, caches, pos)

            lowered = jax.jit(
                decode,
                in_shardings=(p_shard, b_shard["tokens"], c_shard, pos_shard)
            ).lower(param_struct, in_specs["tokens"], cache_struct, pos_struct)

        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0

    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    try:
        mem = compiled.memory_analysis()
        mem_rec = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes":
                getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # noqa: BLE001 — backend support varies
        mem_rec = {"error": str(e)}

    # trip-count-weighted reparse of the post-SPMD HLO: XLA's cost_analysis
    # counts while (scan) bodies once, so scan-over-layers models would be
    # understated by ~n_layers x (see launch/hloparse.py + test_roofline.py).
    # NOTE: the post-SPMD module is the PER-DEVICE program, so parsed
    # quantities are already per-chip (verified in test_roofline.py) —
    # roofline terms divide by the single-chip peak only.
    hlo = compiled.as_text()
    from . import hloparse
    parsed = hloparse.analyze(hlo)
    flops = max(flops, parsed["flops"])
    bytes_accessed = max(bytes_accessed, parsed["bytes"])
    coll = {k: int(v) for k, v in parsed["collectives"].items()}
    coll_total = int(parsed["collective_total"])
    # the dry-run models the production v5e pod
    terms = rf.roofline_terms(flops, bytes_accessed, coll_total, chips=1,
                              device_kind=rf.V5E)
    mf = rf.model_flops(cfg, shape)

    # per-device parameter residency (proves the FSDP+TP layout fits)
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(
                          jax.eval_shape(lambda: build_model(cfg)
                                         .init(jax.random.key(0)))))
    opt_bytes = 2 * sum(x.size * 4 for x in jax.tree.leaves(
        jax.eval_shape(lambda: build_model(cfg).init(jax.random.key(0)))))

    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(chips),
        "kind": shape.kind,
        "compile_seconds": round(compile_s, 1),
        "hlo_flops": flops,
        "hlo_bytes": bytes_accessed,
        "cost_analysis_flops": float(cost.get("flops", 0.0)),
        "cost_analysis_bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "collective_bytes_total": coll_total,
        "roofline": terms,
        "dominant": rf.dominant(terms),
        "model_flops": mf,
        "model_flops_per_chip": mf / chips,
        "useful_flops_ratio": (mf / chips / flops) if flops else None,
        "memory_analysis": mem_rec,
        "param_bytes_global": int(param_bytes),
        "param_bytes_per_chip": int(param_bytes / chips),
        "state_bytes_per_chip": int((param_bytes + (opt_bytes if
                                     shape.kind == "train" else 0)) / chips),
    }


def run_cell(arch, shape_name, mesh_name, force=False):
    path = _cell_path(arch, shape_name, mesh_name)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f), True
    os.makedirs(RESULTS_DIR, exist_ok=True)
    try:
        rec = lower_cell(arch, shape_name, mesh_name == "2x16x16")
    except Exception as e:  # noqa: BLE001
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec, False


def all_cells():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape_name in applicable_shapes(cfg):
            for mesh_name in ("16x16", "2x16x16"):
                yield arch, shape_name, mesh_name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default=None, choices=[None, "16x16", "2x16x16"])
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    failures = 0
    for arch, shape_name, mesh_name in all_cells():
        if args.arch and arch != args.arch:
            continue
        if args.shape and shape_name != args.shape:
            continue
        if args.mesh and mesh_name != args.mesh:
            continue
        t0 = time.time()
        rec, cached = run_cell(arch, shape_name, mesh_name, args.force)
        status = "cached" if cached else f"{time.time()-t0:.0f}s"
        if "error" in rec:
            failures += 1
            print(f"[FAIL {status}] {arch} {shape_name} {mesh_name}: "
                  f"{rec['error'][:200]}", flush=True)
        else:
            t = rec["roofline"]
            print(f"[ok {status}] {arch} {shape_name} {mesh_name} "
                  f"dom={rec['dominant'][:-2]} "
                  f"c={t['compute_s']:.3g} m={t['memory_s']:.3g} "
                  f"x={t['collective_s']:.3g}", flush=True)
    print(f"done, failures={failures}")
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
