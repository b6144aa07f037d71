"""Seconds the join operators spent probing and gathering per completed
query: the engine's ``joinTime`` (``exec/join.py``, around ``device_join``,
summed over the task threads: host thread-seconds). A cell whose statement
joins nothing has no such timer and reports nothing."""


def read(window):
    if "joinTime" not in window.counters:
        return None
    ns = window.per_query("joinTime")
    return None if ns is None else ns / 1e9
