"""The four-chip cell ``mesh4.q3`` rehearsed on four host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``): the session
places one worker per device, a warm Q3 traces nothing and a planted
fault turns ``correct`` false; and the arithmetic of the exchange layer's
device readers on hand-made traces and runs."""

import pytest

from benchlib import trace
from benchlib.runner import Run, reader
from benchlib.traffic import Request
from cells import checkout, run_cell
from test_faults import ALTER

CELL = "mesh4.q3"
FOUR = "--xla_force_host_platform_device_count=4"


def test_rehearsal_on_four_devices_is_correct_and_traces_nothing(
        tmp_path, monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", FOUR)
    rc, out, err, res = run_cell(CELL, seed=2**31 + 17, trace=1,
                                 root=checkout(tmp_path))
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
    assert res["metrics"]["traces_per_query.mesh"]["value"] == 0.0


def test_altered_answer_on_the_mesh_turns_correct_false(tmp_path,
                                                        monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", FOUR)
    rc, out, err, res = run_cell(CELL, seed=9, seconds=4.0, patch=ALTER,
                                 root=checkout(tmp_path))
    assert rc == 0, err[-3000:]
    assert res["correct"] is False, res["checks"]


def _op(s, e, name="fusion f32[8]"):
    return trace.Op(s, e, name, False)


# ns; each device busy 10 s of a 20 s window
DEVICES = {
    # a synchronous all-to-all, 2 s, inside a 10 s fusion
    "/device:TPU:0": [_op(0, 10e9), _op(1e9, 3e9, "all-to-all s32[4,8]")],
    # an asynchronous all-gather in flight from 2 s to 6 s: its halves are
    # instants, the transfer between them counts; the fusion overlaps it
    "/device:TPU:1": [_op(0, 4e9), _op(2e9, 2e9, "all-gather-start f32[8]"),
                      _op(6e9, 6e9, "all-gather-done f32[8]"),
                      _op(6e9, 10e9)],
    # overlapping collectives count once: (1, 4) of busy (0, 10)
    "/device:TPU:2": [_op(0, 10e9), _op(1e9, 3e9, "all-reduce f32[10]"),
                      _op(2e9, 4e9, "all-to-all pred[4,4]")],
    # no collective; a local copy's halves are not one
    "/device:TPU:3": [_op(0, 10e9), _op(1e9, 1e9, "copy-start s32[8]"),
                      _op(5e9, 5e9, "copy-done s32[8]")],
}
COLLECTIVE_S = 2.0 + 4.0 + 3.0 + 0.0


def _run(stats_list, devices=DEVICES):
    reqs = []
    for stats in stats_list:
        r = Request("tpch_q3", stats=stats)
        r.result = {} if stats is not None else None
        reqs.append(r)
    reduced = None if devices is None else trace.Reduced(
        window=(0.0, 20e9), devices=devices, host=[])
    return Run(CELL, 20.0, 0.0, reqs, 0.0, 20.0, trace=reduced)


def _exchanges(*bytes_moved):
    return {"exchanges": {f"#{i} Repartition(k)": {"bytes_moved": b}
                          for i, b in enumerate(bytes_moved)}}


def test_collective_share_pairs_async_halves():
    read = reader("collective_share.mesh")
    assert read(_run([])) == pytest.approx(100.0 * COLLECTIVE_S / 40.0)
    assert read(_run([], devices={"/device:TPU:0": [_op(0, 1e9)]})) == 0.0
    assert read(_run([], devices=None)) is None


def test_collective_share_counts_an_unpaired_start_as_nothing():
    read = reader("collective_share.mesh")
    devices = {"/device:TPU:0": [_op(0, 4e9),
                                 _op(1e9, 1e9, "all-to-all-start s32[8]")]}
    assert read(_run([], devices=devices)) == 0.0


def test_ici_roofline_share_over_collective_seconds():
    read = reader("ici_roofline_share.mesh")
    # 45e9 bytes moved by two completed queries; a failed one is left out
    run = _run([_exchanges(20e9, 10e9), _exchanges(15e9),
                None])
    want = 100.0 * 45e9 / (COLLECTIVE_S * 50e9 * 4)
    assert read(run, device_kind="TPU v5 lite") == pytest.approx(want)
    assert 0 < want < 100


@pytest.mark.parametrize("stats,devices", [
    ([{"kernel_dispatch": {}}], DEVICES),          # no exchange stats
    ([_exchanges(1e9)], {"/device:TPU:0": [_op(0, 1e9)]}),  # no collective
    ([_exchanges(1e9)], None),                      # untraced run
], ids=["no-exchanges", "no-collectives", "no-trace"])
def test_ici_roofline_share_none_where_absent(stats, devices):
    read = reader("ici_roofline_share.mesh")
    assert read(_run(stats, devices=devices),
                device_kind="TPU v5 lite") is None


def test_ici_roofline_share_none_on_a_device_without_peaks():
    read = reader("ici_roofline_share.mesh")
    assert read(_run([_exchanges(1e9)]), device_kind="cpu") is None


def test_traces_per_query_mean_and_absence():
    read = reader("traces_per_query.mesh")
    assert read(_run([{"traces": {"exchange": 3, "join": 1}},
                      {"traces": {}}, None])) == 2.0
    assert read(_run([{"agg": {}}])) is None
