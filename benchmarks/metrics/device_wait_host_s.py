"""Thread-seconds the host spent blocked reading device values back, per
completed query: the engine's ``deviceSyncTime`` (row counts, fetches,
the aggregate's counts, join sizes; mirrored as ``deviceSync`` spans with
``site=``), summed over its task threads. Near the wall in a cell the
device bounds, near nothing in one the host paces. A program without the
timer (before PR 26) reports nothing."""


def read(window):
    if "deviceSyncTime" not in window.counters:
        return None
    ns = window.per_query("deviceSyncTime")
    return None if ns is None else ns / 1e9
