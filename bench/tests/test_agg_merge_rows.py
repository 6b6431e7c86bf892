"""The reader of ``agg_merge_rows.streams`` on hand-made runs."""

from benchlib.runner import Run, reader
from benchlib.traffic import Request


def _run(stats_list):
    reqs = []
    for stats in stats_list:
        r = Request("tpch_q3", stats=stats)
        r.result = {} if stats is not None else None
        reqs.append(r)
    return Run("sf1.streams_no_q5", 1.0, 0.0, reqs, 0.0, 1.0)


def test_mean_over_completed_queries():
    read = reader("agg_merge_rows.streams")
    agg = [{"agg": {"partials": 6, "merges": 1, "merge_rows": 6 << 20}},
           {"agg": {"partials": 6, "merges": 5, "merge_rows": 20}},
           None]                      # failed: not counted
    assert read(_run(agg)) == ((6 << 20) + 20) / 2


def test_none_without_the_counter():
    read = reader("agg_merge_rows.streams")
    assert read(_run([{"kernel_dispatch": {"agg": 3}}, {"agg": {}}])) is None
    assert read(_run([])) is None
