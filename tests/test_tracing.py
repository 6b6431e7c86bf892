"""The engine's tracing: device programs named by operator family, host
spans on the profiler's clock carrying the query id, and operator host
times that count only an operator's own calls."""

import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import Session, dtypes as dt, tracing
from repro.core import operators as ops
from repro.core.driver import Driver, ExecutionContext
from repro.core.expr import col, lit
from repro.core.scheduler import SchedulerConfig
from repro.core.table import DeviceTable
from repro.tpch import dbgen, sqltext


def _stacked_table(n=8):
    """A worker-stacked ``[1, n]`` table, the layout the driver runs."""
    cols = {"k": jnp.arange(n, dtype=jnp.int32)[None] % 3,
            "v": jnp.ones((1, n), jnp.float32)}
    return DeviceTable(cols, jnp.ones((1, n), bool),
                       {"k": dt.INT32, "v": dt.FLOAT32})


@pytest.mark.parametrize("fn, statics, module", [
    (ops._aggregate, (("k",), (("s", "sum", "v"),), 4), "jit_agg__aggregate"),
    (ops._build_join_table, (("k",),), "jit_join__build_join_table"),
    (ops._filter_project, (col("v") > lit(0.5), None, True),
     "jit_scan__filter_project"),
])
def test_table_op_module_named_by_family(fn, statics, module):
    table = _stacked_table()
    jitted, _ = fn.program(table, *statics)
    text = jitted.lower(table).as_text()
    assert f"module @{module} " in text


def test_program_rejects_unknown_family():
    with pytest.raises(ValueError, match="unknown program family"):
        tracing.program("misc", lambda x: x)


def test_span_is_a_no_op_without_profiler():
    assert tracing.span("repro.sched.run", fragment="x") is tracing._OFF


def _spans(log_dir):
    """(name, thread, query_id) of every ``repro.*`` host event; a host
    plane has one line per thread."""
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, thread,
                                dict(ev.stats).get("query_id")))
    return out


def test_query_spans_carry_one_query_id(tmp_path):
    sess = Session(dbgen.load_catalog(sf=0.002),
                   scheduler_config=SchedulerConfig(plan_cache_size=0,
                                                    cache_results=False))
    text = sqltext.sql_text(1)
    sess.sql(text).submit().result()            # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        handle = sess.sql(text).submit()
        handle.result()
    sess.scheduler().close()
    spans = _spans(tmp_path)
    ids = {}
    for name, line, qid in spans:
        ids.setdefault(name, set()).add(qid)
    for name in ("repro.sched.submit", "repro.sched.run",
                 "repro.op.HashAggregation.add_input", "repro.scan.wait",
                 "repro.scan.read", "repro.scan.put", "repro.driver.to_host"):
        assert ids.get(name) == {handle.query_id}, (name, ids.get(name))
    assert "repro.plan.sql" in ids and "repro.plan.optimize" in ids
    # the prefetch thread's spans carry the id on a thread of their own
    lines = {n: {ln for nm, ln, _ in spans if nm == n}
             for n in ("repro.sched.run", "repro.scan.put")}
    assert lines["repro.scan.put"].isdisjoint(lines["repro.sched.run"])
    stats = handle.executor_stats["op_host_seconds"]
    assert stats["HashAggregation"] > 0
    assert sum(stats.values()) <= handle.latency


@pytest.mark.parametrize("keys, where, merges", [
    # groups bounded by the row count
    pytest.param("l_orderkey", "", "once", id="l_orderkey-once"),
    # groups far below a morsel's rows
    pytest.param("l_returnflag", "", "per_morsel",
                 id="l_returnflag-per_morsel"),
    # as the first, with a few groups in each morsel's slots
    pytest.param("l_orderkey", "WHERE l_quantity < 5", "once",
                 id="l_orderkey-once-sparse"),
])
def test_agg_counters_per_query(keys, where, merges):
    catalog = dbgen.load_catalog(sf=0.002)
    sess = Session(catalog, batch_rows=2048,
                   scheduler_config=SchedulerConfig(plan_cache_size=0,
                                                    cache_results=False))
    qb = sess.sql(f"SELECT {keys}, sum(l_quantity) AS q FROM lineitem "
                  f"{where} GROUP BY {keys}")
    handle = qb.submit()
    handle.result()
    sess.scheduler().close()
    stats = handle.executor_stats
    morsels = stats["tables"]["lineitem"]["morsels"]
    agg = stats["agg"]
    assert morsels >= 3 and agg["partials"] == morsels
    if merges == "once":
        # finish merges each partial's first B slots, B the power of two
        # at or above the most groups a morsel holds; the last morsel has
        # fewer than 2048 slots
        data = catalog.get("lineitem").data
        keep = data["l_quantity"] < 5 if where else np.ones(
            len(data["l_quantity"]), bool)
        parts = [(data[keys][i:i + 2048], keep[i:i + 2048])
                 for i in range(0, len(keep), 2048)]
        b = 1 << (max(len(np.unique(k[m])) for k, m in parts)
                  - 1).bit_length()
        assert len(parts) == morsels
        assert agg["merges"] == agg["compacted_merges"] == 1
        assert agg["merge_rows"] == sum(min(b, len(k)) for k, _ in parts)
    else:
        assert agg["merges"] == morsels - 1
        assert agg["compacted_merges"] == 0
    assert ("agg: partials=%d merges=%d merge_rows=%d compacted_merges=%d"
            % (agg["partials"], agg["merges"], agg["merge_rows"],
               agg["compacted_merges"])) in qb.explain(analyze=True)


def test_program_counts_each_trace_per_family():
    prog = jax.jit(tracing.program("join", lambda x: x + 1))
    counts = {}
    with tracing.collect_traces(counts):
        prog(jnp.zeros(3))
        prog(jnp.ones(3))                      # same shape: no trace
        prog(jnp.zeros(5))                     # a new shape traces again
    prog(jnp.zeros(7))                         # outside the scope
    assert counts == {"join": 2}


def test_warm_query_traces_nothing():
    sess = Session(dbgen.load_catalog(sf=0.002), batch_rows=2048,
                   scheduler_config=SchedulerConfig(cache_results=False))
    text = ("SELECT l_returnflag, sum(l_quantity) AS q FROM lineitem "
            "WHERE l_discount < 0.031 GROUP BY l_returnflag")
    handles = []
    for _ in range(2):
        handles.append(sess.sql(text).submit())
        handles[-1].result()
    sess.scheduler().close()
    cold, warm = handles
    assert cold.executor_stats["traces"].get("agg", 0) > 0
    assert warm.executor_stats["traces"] == {}
    shown = sess.sql(text).explain(analyze=True)
    traces = sess.executor_stats()["traces"]
    assert "traces: " + " ".join(
        f"{k}={v}" for k, v in sorted(traces.items())) in shown


class _Sleeper(ops.Operator):
    """Passes batches through; each own call sleeps ``delay`` seconds."""

    def __init__(self, delay):
        self.delay = delay

    def add_input(self, batch):
        time.sleep(self.delay)
        return [batch]


class _Upstream(_Sleeper):
    name = "Upstream"


class _Downstream(_Sleeper):
    name = "Downstream"


def test_op_host_seconds_is_self_time():
    driver = Driver(ExecutionContext(catalog=None))
    up = driver._run_pipeline(_Upstream(0.02), iter(range(5)))
    down = driver._run_pipeline(_Downstream(0.0), up)
    t0 = time.perf_counter()
    for _ in down:
        time.sleep(0.02)                       # the consumer's own work
    wall = time.perf_counter() - t0
    secs = driver.executor_stats()["op_host_seconds"]
    assert secs["Upstream"] >= 5 * 0.02
    # neither the upstream pulls nor the consumer's work between yields
    assert secs["Downstream"] < 0.02
    assert sum(secs.values()) <= wall


def test_prefetch_thread_inherits_query_id():
    from repro.core.streaming import HostMorsel, MorselPrefetcher

    seen = []

    def morsels():
        seen.append((threading.current_thread().name,
                     tracing._QUERY_ID.get()))
        yield HostMorsel({"k": np.zeros((1, 4), np.int32)},
                         np.ones((1, 4), bool), {"k": dt.INT32})

    with tracing.query(41):
        pf = MorselPrefetcher(morsels())
    assert tracing._QUERY_ID.get() is None
    assert len(list(pf)) == 1
    assert seen == [("morsel-prefetch", 41)]
