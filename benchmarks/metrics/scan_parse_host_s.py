"""Seconds per completed query of the rest of the scan's device-decode
plans: page headers, level and run tables, dictionary pages, string
lengths, DELTA runs, in Python (the engine's ``scanParseTime``, spans
``scanParse``, one a column chunk, with the decompression left out; an
interval union over the scan's threads, per scan; PR 38). Host wall,
not device time. A program without the timer (before PR 38) reports
nothing."""


def read(window):
    if "scanParseTime" not in window.counters:
        return None
    ns = window.per_query("scanParseTime")
    return None if ns is None else ns / 1e9
