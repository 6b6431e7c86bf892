"""Device time by program family and idle time by program span: on
hand-made intervals, and on a small trace recorded on a TPU v5e by
``record_trace.py`` after the engine named its programs and spans."""

import os

import pytest

from benchlib import families, trace

NAMED = os.path.join(os.path.dirname(__file__), "data",
                     "probe_named.xplane.pb.gz")
DEV = "/device:TPU:0"


def _op(s, e):
    return trace.Op(s, e, "fusion f32[8]", False)


def _reduced(ops, host=()):
    return trace.Reduced(window=(0.0, 10e9), devices={DEV: ops},
                         host=list(host))


def test_family_seconds_sum_to_busy():
    r = _reduced([_op(0, 2e9), _op(1e9, 3e9), _op(4e9, 5e9), _op(8e9, 9e9)])
    mods = {DEV: [(0, 3e9, "jit_agg__aggregate(1)"),
                  (4e9, 4.5e9, "jit_join__probe_join(2)"),
                  (4.5e9, 5e9, "jit__lambda(3)")]}
    got = families.family_s(r, mods)
    # the op at 8-9 s runs in no module: it counts as other
    assert got == pytest.approx({"agg": 3.0, "join": 0.5, "other": 1.5})
    assert sum(got.values()) == pytest.approx(r.busy_s())
    assert families.top_modules(mods, 1) == [["jit_agg__aggregate", 3.0]]


def test_idle_goes_to_innermost_span():
    r = _reduced([_op(0, 2e9), _op(6e9, 10e9)],
                 host=[(0, 10e9, trace.WINDOW),
                       (1e9, 9e9, "repro.sched.run"),
                       (3e9, 4e9, "repro.scan.wait"),
                       (3.5e9, 5e9, "repro.driver.to_host"),
                       (2e9, 6e9, "PjitFunction(f)")])
    # 2-3 s and 5-6 s in sched.run alone; wait from 3 s, then to_host
    # (started later) from 3.5 s to 5 s
    assert families.idle_by_span(r) == pytest.approx(
        {"repro.sched.run": 2.0, "repro.scan.wait": 0.5,
         "repro.driver.to_host": 1.5})


def test_idle_outside_any_span_is_none():
    r = _reduced([_op(0, 4e9)], host=[(5e9, 6e9, "repro.scan.wait")])
    assert families.idle_by_span(r) == pytest.approx(
        {"none": 5.0, "repro.scan.wait": 1.0})


def test_recorded_named_trace():
    r = trace.reduce(NAMED)
    assert r is not None and list(r.devices) == [DEV]
    mods = families.modules(NAMED, r.window)
    fam = families.family_s(r, mods)
    assert sum(fam.values()) == pytest.approx(r.busy_s(), rel=1e-6)
    for share in (fam.get("agg", 0.0), fam.get("join", 0.0)):
        assert 0 < 100.0 * share / r.busy_s() < 100
    top = families.top_modules(mods)
    assert top and not any(name.startswith("jit__lambda") for name, _ in top)
    idle = families.idle_by_span(r)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s(),
                                               rel=1e-6)
    assert any(name.startswith("repro.") for name in idle)
