"""Seconds per completed query of host work around the window execs: the
engine's ``windowTime`` (``exec/window.py``: concatenating a partition's
batches, key batching over ``batchSizeRows``, enqueueing the window program;
host thread-seconds, not device time). A program without the timer (before
PR 36), or a cell whose statement has no window, reports nothing."""


def read(window):
    if "windowTime" not in window.counters:
        return None
    ns = window.per_query("windowTime")
    return None if ns is None else ns / 1e9
