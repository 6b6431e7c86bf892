"""End-to-end behaviour tests for the paper's system: the full path from
storage through device-resident operators and exchange to results, plus the
training stack wired to the engine's data layer."""

import numpy as np

import jax

from repro.core import HostExchange, ICIExchange, Session, dtypes as dt
from repro.core import plan as P
from repro.core.expr import col
from repro.tpch import dbgen, oracle, queries


def test_full_pipeline_storage_to_result(tmp_path):
    """dbgen -> column-chunk files -> distributed scan -> join/agg ->
    oracle-validated result. The paper's H1+H2+H3 in one path."""
    data = dbgen.write_dataset(str(tmp_path), sf=0.002, chunks=4)
    catalog = dbgen.storage_catalog(str(tmp_path))
    ex = ICIExchange()
    session = Session(catalog, num_workers=4, exchange=ex, batch_rows=8192)
    res = session.execute(queries.build_query(5, catalog))
    want = oracle.ORACLES[5](data)
    assert len(res["revenue"]) == len(want["revenue"])
    np.testing.assert_allclose(np.sort(res["revenue"]),
                               np.sort(want["revenue"]), rtol=2e-3)
    assert ex.stats.host_staged_bytes == 0       # never left the device


def test_host_exchange_is_mechanism_baseline(tmp_path):
    """Both protocols agree on results; only the host one stages bytes."""
    catalog = dbgen.load_catalog(sf=0.002)
    plan = queries.build_query(13, catalog)
    res_i = Session(catalog, num_workers=4, exchange=ICIExchange(),
                    batch_rows=8192).execute(plan)
    host_ex = HostExchange()
    res_h = Session(catalog, num_workers=4, exchange=host_ex,
                    batch_rows=8192).execute(plan)
    np.testing.assert_array_equal(np.sort(res_i["c_count"]),
                                  np.sort(res_h["c_count"]))
    assert host_ex.stats.host_staged_bytes > 0


def test_driver_adaptation_inserts_conversions():
    """Declaring an operator host-only forces the CudfToVelox-style round
    trip, and the driver accounts the staged bytes (paper §3.1)."""
    catalog = dbgen.load_catalog(sf=0.002)
    session = Session(catalog, num_workers=2, batch_rows=8192,
                      host_only_ops=frozenset({"HashAggregation"}))
    plan = queries.build_query(1, catalog)
    res = session.execute(plan)
    assert len(res["sum_qty"]) == 4
    assert session.last_driver.conversion_stats.get("bytes", 0) > 0


def test_engine_feeds_training_data():
    """The engine is the framework's data substrate: filter/dedup a token
    table with a query, train on the result (paper's technique as the
    input pipeline)."""
    from repro.configs import get_config
    from repro.data import TokenPipeline
    from repro.models import build_model
    from repro.train import make_train_step, train_state_init

    rng = np.random.default_rng(0)
    catalog = dbgen.load_catalog(sf=0.001)
    catalog.register_numpy(
        "corpus",
        {"doc": np.repeat(np.arange(200), 50),
         # skewed (Zipf-flavored) tokens: a uniform vocab draw has no
         # learnable structure, leaving the loss pinned at ln(V) and the
         # loss-decreases assertion to initialization luck
         "tok": (rng.random(10_000) ** 4 * 512).astype(np.int64),
         "quality": rng.random(10_000).astype(np.float32)},
        {"doc": dt.INT32, "tok": dt.INT32, "quality": dt.FLOAT32})
    plan = P.Project(P.Filter(P.TableScan("corpus"),
                              col("quality") > 0.2), [("tok", col("tok"))])
    filtered = Session(catalog, num_workers=2, batch_rows=4096).execute(plan)
    tokens = filtered["tok"]
    assert len(tokens) > 2_000

    model = build_model(get_config("qwen2_1_5b", smoke=True))
    state = train_state_init(model, jax.random.key(0))
    # lr/steps sized so the unigram skew is actually learned: the descent
    # below ln(V) needs ~10 steps to clear per-batch noise
    step = jax.jit(make_train_step(model, base_lr=1e-2))
    pipe = TokenPipeline(tokens, batch=2, seq_len=32)
    losses = []
    for _ in range(16):
        state, m = step(state, next(pipe))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_chip_smoke_refuses_without_tpu(tmp_path):
    """``chip_smoke.py`` reports a chip result only from a TPU: on the CPU
    it exits non-zero before generating data, and prints no ok line."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(repo, "chip_smoke.py")],
                          env=env, cwd=tmp_path, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout and "data:" not in proc.stdout
