"""Times per completed query that a final or complete aggregate held more
than one batch of partial results, concatenated them and aggregated again:
the engine's ``aggMergeCount`` (``exec/agg.py``). A program without the
counter (before PR 30), or a cell whose aggregates never merge, reports
nothing; ``BENCHMARK.json`` lists the cells that do."""


def read(window):
    if "aggMergeCount" not in window.counters:
        return None
    return window.per_query("aggMergeCount")
