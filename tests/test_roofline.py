"""Roofline machinery tests: trip-count-weighted HLO parsing, collective
detection, term arithmetic."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch import hloparse, roofline as rf


def _layer(x, w):
    return jnp.tanh(x @ w), ()


def test_scan_body_weighted_by_trip_count():
    """XLA cost_analysis counts while bodies once; the parser must multiply
    by the trip count so scan == unrolled."""
    d, layers = 128, 8
    x = jax.ShapeDtypeStruct((32, d), jnp.float32)
    ws = jax.ShapeDtypeStruct((layers, d, d), jnp.float32)

    def scanned(x, ws):
        y, _ = jax.lax.scan(_layer, x, ws)
        return y

    def unrolled(x, ws):
        for i in range(layers):
            x, _ = _layer(x, ws[i])
        return x

    analytic = layers * 2 * 32 * d * d
    for f in (scanned, unrolled):
        txt = jax.jit(f).lower(x, ws).compile().as_text()
        got = hloparse.analyze(txt)["flops"]
        assert got == analytic, (f.__name__, got, analytic)

    # and confirm cost_analysis alone UNDER-counts the scan (the bug the
    # parser exists to fix)
    ca = jax.jit(scanned).lower(x, ws).compile().cost_analysis()
    assert ca["flops"] < analytic / 2


def test_nested_scan_weighting():
    d = 64

    def inner(x, w):
        return x @ w, ()

    def outer(x, ws):
        def body(c, wgroup):
            y, _ = jax.lax.scan(inner, c, wgroup)
            return y, ()
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((8, d), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 5, d, d), jnp.float32)   # 15 layers total
    txt = jax.jit(outer).lower(x, ws).compile().as_text()
    got = hloparse.analyze(txt)["flops"]
    assert got == 15 * 2 * 8 * d * d


def test_collective_bytes_detected_on_mesh():
    import subprocess, sys, os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = r"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch import hloparse
mesh = jax.make_mesh((8,), ("x",))
sh = NamedSharding(mesh, P("x"))
def f(a):
    return jax.lax.with_sharding_constraint(jnp.sum(a, axis=0), P())
a = jax.ShapeDtypeStruct((8, 128), jnp.float32)
with mesh:
    txt = jax.jit(f, in_shardings=sh).lower(a).compile().as_text()
r = hloparse.analyze(txt)
total = r["collective_total"]
assert total > 0, txt[:2000]
print("COLL_OK", total)
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    assert "COLL_OK" in p.stdout


def test_roofline_terms_math():
    t = rf.roofline_terms(flops=197e12 * 256, bytes_accessed=819e9 * 256,
                          coll_bytes=50e9 * 256, chips=256,
                          device_kind="TPU v5 lite")
    assert abs(t["compute_s"] - 1.0) < 1e-9
    assert abs(t["memory_s"] - 1.0) < 1e-9
    assert abs(t["collective_s"] - 1.0) < 1e-9
    assert rf.dominant({"compute_s": 3, "memory_s": 2, "collective_s": 1}) \
        == "compute_s"
    with pytest.raises(ValueError, match="no published peaks"):
        rf.roofline_terms(1.0, 1.0, 0, chips=1, device_kind="cpu")


def test_model_flops_definitions():
    from repro.configs import get_config
    from repro.configs.base import SHAPES
    cfg = get_config("deepseek_moe_16b")
    train = rf.model_flops(cfg, SHAPES["train_4k"])
    # MoE: uses ACTIVE params only
    assert train == 6.0 * cfg.active_param_count() * SHAPES["train_4k"].tokens
    assert cfg.active_param_count() < 0.25 * cfg.param_count()
