"""Device programs traced per query in the window: the mean over
completed queries of the sum of ``executor_stats()["traces"]`` (per
family). A warm query traces nothing, so anything above 0 is a program
traced, and compiled, again for each query (None on a program without
the counter)."""


def read(run):
    counts = [sum(r.stats["traces"].values()) for r in run.completed
              if r.stats and "traces" in r.stats]
    return sum(counts) / len(counts) if counts else None
