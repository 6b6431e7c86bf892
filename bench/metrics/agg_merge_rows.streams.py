"""Rows fed to the streaming aggregation's merges per query: the mean of
``executor_stats()["agg"]["merge_rows"]`` over completed queries (none on
a program without the counter)."""


def read(run):
    rows = [r.stats["agg"]["merge_rows"] for r in run.completed
            if r.stats and "merge_rows" in r.stats.get("agg", {})]
    return sum(rows) / len(rows) if rows else None
