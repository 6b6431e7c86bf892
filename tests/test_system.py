"""End-to-end behaviour tests for the paper's system: the full path from
storage through device-resident operators and exchange to results."""

import numpy as np

from repro.core import HostExchange, ICIExchange, Session
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
