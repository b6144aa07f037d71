"""Seconds per completed query in which the scan's device-decode plans
decompressed pages, dictionary pages included: the engine's
``scanInflateTime`` (spans ``scanInflate``, one a column chunk, nested in
``scanParse``; an interval union over the scan's threads, per scan; PR
38). Host wall, not device time. A program without the timer (before PR
38) reports nothing."""


def read(window):
    if "scanInflateTime" not in window.counters:
        return None
    ns = window.per_query("scanInflateTime")
    return None if ns is None else ns / 1e9
