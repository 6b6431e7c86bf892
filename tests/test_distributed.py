"""True multi-device distributed execution (subprocess with 8 or 4 host
devices).

The main pytest process keeps the default single CPU device (per the
project's dry-run isolation rule); these tests re-exec python with
XLA_FLAGS=--xla_force_host_platform_device_count=<n> and assert:

* query results over a real 8-device mesh match the oracle,
* the ICI exchange's data phase lowers to an all-to-all collective,
* the broadcast lowers to an all-gather,
* the host-staged exchange moves bytes through host memory,
* ``Session(num_workers=4)`` on four devices places one worker per device
  by itself, and a warm query traces no program, alone or beside another;
* with fewer devices than workers (this process) the workers share one
  device and the session says so.
"""

import logging
import os
import subprocess
import sys

import pytest


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc.stdout


def test_query_on_8_device_mesh_matches_oracle():
    out = _run(r"""
import jax
import numpy as np
assert jax.device_count() == 8, jax.devices()
from repro.launch.mesh import make_engine_mesh
mesh = make_engine_mesh(8)
from repro.core import Session, ICIExchange
from repro.tpch import dbgen, queries, oracle
data = dbgen.generate(sf=0.002)
cat = dbgen.load_catalog(sf=0.002)
s = Session(cat, num_workers=8, exchange=ICIExchange(mesh=mesh),
            batch_rows=4096, mesh=mesh)
for q in (1, 5, 13):
    res = s.execute(queries.build_query(q, cat))
    orc = oracle.ORACLES[q](data)
    assert len(next(iter(res.values()))) == len(next(iter(orc.values()))), q
print("rows-match OK")
""")
    assert "rows-match OK" in out


def test_ici_exchange_lowers_to_all_to_all():
    out = _run(r"""
import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_engine_mesh
mesh = make_engine_mesh(8)
from repro.core import dtypes as dt
from repro.core.table import DeviceTable
from repro.core.exchange import (_broadcast_data, _exchange_data,
                                 _partition_layout_table)
cap = 256
cols = {"k": jnp.zeros((8, cap), jnp.int32), "v": jnp.zeros((8, cap), jnp.float32)}
t = DeviceTable(cols, jnp.ones((8, cap), bool), {"k": dt.INT32, "v": dt.FLOAT32})
staged = _partition_layout_table(t, ("k",), 8, 64)
lowered = _exchange_data.lower(staged, mesh, "workers", 8, 64)
hlo = lowered.compile().as_text()
assert "all-to-all" in hlo, hlo[:3000]
print("a2a OK")

blow = _broadcast_data.lower(t, mesh, "workers", 8)
bhlo = blow.compile().as_text()
assert ("all-gather" in bhlo) or ("all-reduce" in bhlo), bhlo[:3000]
print("bcast OK")
""")
    assert "a2a OK" in out and "bcast OK" in out


def test_exchange_correctness_on_mesh():
    out = _run(r"""
import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_engine_mesh
mesh = make_engine_mesh(8)
from repro.core import dtypes as dt
from repro.core.table import DeviceTable
from repro.core.exchange import ICIExchange, HostExchange
rng = np.random.default_rng(0)
W, cap = 8, 128
k = rng.integers(0, 1000, (W, cap)).astype(np.int32)
v = rng.random((W, cap)).astype(np.float32)
t = DeviceTable({"k": jnp.asarray(k), "v": jnp.asarray(v)},
                jnp.ones((W, cap), bool), {"k": dt.INT32, "v": dt.FLOAT32})
for ex in (ICIExchange(mesh=mesh), HostExchange()):
    out = ex.repartition(t, ("k",), W)
    ov = np.asarray(out.validity)
    ok = np.asarray(out.columns["k"])
    # conservation: every row lands exactly once
    assert ov.sum() == W * cap, (type(ex).__name__, ov.sum())
    got = np.sort(ok[ov]); want = np.sort(k.reshape(-1))
    np.testing.assert_array_equal(got, want)
    # co-location: all rows with equal keys land on one worker
    owner = {}
    for w in range(W):
        for key in set(ok[w][ov[w]].tolist()):
            assert owner.setdefault(key, w) == w, (type(ex).__name__, key)
print("exchange-correct OK")
""")
    assert "exchange-correct OK" in out


# Q3 from SQL text through the scheduler at W=4, no mesh given: the
# session builds its own; result cache off so every submit executes
_Q3_SESSION = r"""
import jax
assert jax.device_count() == 4, jax.devices()
from repro.core import SchedulerConfig, Session
from repro.tpch import dbgen, oracle, sqltext
from repro.tpch.compare import assert_results_match
SF = 0.01
cat = dbgen.load_catalog(sf=SF)
want = oracle.ORACLES[3](dbgen.generate(sf=SF))
text = sqltext.sql_text(3, cat)
s = Session(cat, num_workers=4, kernel_backend="jnp", batch_rows=4096,
            scheduler_config=SchedulerConfig(cache_results=False))
assert s.mesh is None and s.worker_mesh().devices.size == 4


def q3(n=1):
    handles = [s.sql(text).submit() for _ in range(n)]
    for h in handles:
        assert_results_match(h.result(), want, 3)
    return [h.executor_stats for h in handles]
"""


def test_session_places_one_worker_per_device():
    out = _run(_Q3_SESSION + r"""
from repro.core import driver, exchange
seen = {"scan": set(), "exchange_in": set(), "exchange_out": set()}

def devices(t):
    return len(t.validity.sharding.device_set)

batches = driver.StreamingScan.batches
def scan_batches(self):
    for b in batches(self):
        seen["scan"].add(devices(b))
        yield b
driver.StreamingScan.batches = scan_batches

repartition = exchange.ICIExchange.repartition
def placed(self, table, keys, w):
    out = repartition(self, table, keys, w)
    seen["exchange_in"].add(devices(table))
    seen["exchange_out"].add(devices(out))
    return out
exchange.ICIExchange.repartition = placed

stats, = q3()
assert seen == {"scan": {4}, "exchange_in": {4}, "exchange_out": {4}}, seen
assert stats["exchange_protocol"] == "ici" and stats["exchanges"]
assert sum(e["host_staged_bytes"] for e in stats["exchanges"].values()) == 0
s.scheduler().close()
print("placed OK")
""", devices=4)
    assert "placed OK" in out


@pytest.mark.parametrize("in_flight", [1, 2], ids=["alone", "beside-another"])
def test_warm_query_on_mesh_traces_nothing(in_flight):
    out = _run(_Q3_SESSION + rf"""
cold, = q3()
assert cold["traces"].get("exchange", 0) > 0, cold["traces"]
for stats in q3({in_flight}):
    assert stats["traces"] == {{}}, stats["traces"]
s.scheduler().close()
print("warm OK")
""", devices=4)
    assert "warm OK" in out


def test_exchange_clones_share_one_compiled_program():
    out = _run(r"""
import jax.numpy as jnp
import numpy as np
from repro.core import dtypes as dt, tracing
from repro.core.exchange import ICIExchange, _exchange_data
from repro.core.table import DeviceTable
from repro.launch.mesh import make_engine_mesh
ex = ICIExchange(mesh=make_engine_mesh(4))
k = np.arange(4 * 64, dtype=np.int32).reshape(4, 64)
t = DeviceTable({"k": jnp.asarray(k)}, jnp.ones((4, 64), bool),
                {"k": dt.INT32})
traces = []
for clone in (ex.clone(), ex.clone()):
    counts = {}
    with tracing.collect_traces(counts):
        out = clone.repartition(t, ("k",), 4)
    traces.append(counts.get("exchange", 0))
    assert np.asarray(out.validity).sum() == k.size
assert traces[0] > 0 and traces[1] == 0, traces
assert _exchange_data._cache_size() == 1
print("shared OK")
""", devices=4)
    assert "shared OK" in out


@pytest.mark.parametrize("workers", [2, 4])
def test_workers_share_one_device_when_too_few(workers, caplog):
    """In this process (one CPU device) the workers stack on it, as
    before, and the session logs one warning naming the counts."""
    import jax
    from repro.core import Session
    from repro.tpch import dbgen

    assert jax.device_count() < workers
    s = Session(dbgen.load_catalog(sf=0.002), num_workers=workers,
                kernel_backend="jnp")
    with caplog.at_level(logging.WARNING, logger="repro.core.session"):
        ctxs = [s.context(), s.context()]
        out = s.sql("SELECT count(*) AS n FROM orders").collect()
    assert all(c.mesh is None and c.exchange.mesh is None for c in ctxs)
    assert int(out["n"][0]) == s.catalog.get("orders").num_rows()
    warned = [r.getMessage() for r in caplog.records
              if r.name == "repro.core.session"]
    assert warned == [f"Session(num_workers={workers}) found 1 device(s): "
                      "its workers share one device"], warned
