"""Seconds per completed query around the conditional semi and anti joins:
the engine's ``joinConditionTime`` (``exec/join.py``: the count program's
enqueue, the mask program's and the read of the pairs, host thread-seconds
inside ``joinTime``). A program without the timer (before PR 34), or a cell
whose joins carry no residual condition, reports nothing."""


def read(window):
    if "joinConditionTime" not in window.counters:
        return None
    ns = window.per_query("joinConditionTime")
    return None if ns is None else ns / 1e9
