"""Seconds the aggregate operators spent per completed query: the engine's
``computeAggTime`` (``exec/agg.py``: partial, final and merge passes, summed
over the task threads, so host thread-seconds and not device time). A
program without the timer reports nothing."""


def read(window):
    if "computeAggTime" not in window.counters:
        return None
    ns = window.per_query("computeAggTime")
    return None if ns is None else ns / 1e9
