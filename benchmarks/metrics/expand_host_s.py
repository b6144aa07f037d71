"""Seconds per completed query of host work around the grouping-sets
expansion: the engine's ``expandTime`` (``exec/basic.py``: building and
enqueueing the one program that writes every set of a batch; host
thread-seconds, not device time, and not the wait for a batch's row count,
which ``device_wait_host_s`` reads). A program without the timer, or a
cell whose statement has no ROLLUP or CUBE, reports nothing."""


def read(window):
    if "expandTime" not in window.counters:
        return None
    ns = window.per_query("expandTime")
    return None if ns is None else ns / 1e9
