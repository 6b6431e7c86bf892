"""The readers of ``dense_probe_share.streams`` and ``.mesh`` on hand-made
runs."""

import pytest

from benchlib.runner import Run, reader
from benchlib.traffic import Request

NAMES = ["dense_probe_share.streams", "dense_probe_share.mesh"]


def _run(stats_list):
    reqs = []
    for stats in stats_list:
        r = Request("tpch_q3", stats=stats)
        r.result = {} if stats is not None else None
        reqs.append(r)
    return Run("sf1.streams_no_q5", 1.0, 0.0, reqs, 0.0, 1.0)


def _join(dense, sorted_, slots=1 << 18):
    return {"join": {"dense_probes": dense, "sorted_probes": sorted_,
                     "index_slots": slots if dense else 0}}


@pytest.mark.parametrize("name", NAMES)
def test_summed_over_completed_queries(name):
    read = reader(name)
    stats = [_join(6, 0), _join(3, 1), _join(0, 0, 0),   # Q1, Q6: no join
             None]                                      # failed: not counted
    assert read(_run(stats)) == pytest.approx(100.0 * 9 / 10)
    assert read(_run([_join(6, 0), _join(12, 0)])) == 100.0


@pytest.mark.parametrize("name", NAMES)
def test_failed_requests_not_counted(name):
    read = reader(name)
    failed = Request("tpch_q3", stats=_join(0, 50))
    failed.result = None
    run = _run([_join(4, 0)])
    run.requests.append(failed)
    assert read(run) == 100.0


@pytest.mark.parametrize("name", NAMES)
def test_none_without_the_counter(name):
    read = reader(name)
    assert read(_run([{"kernel_dispatch": {"probe": 3}}, {"join": {}}])) is None
    assert read(_run([_join(0, 0, 0)])) is None          # no jnp probe ran
    assert read(_run([])) is None
