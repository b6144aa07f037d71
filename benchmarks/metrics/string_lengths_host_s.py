"""Seconds per completed query in which the scan's device-decode plans
found the value lengths of PLAIN and DELTA_LENGTH byte-array pages on the
host, or had pyarrow decode a string chunk with a PLAIN page: the
engine's ``scanStrLengthsTime`` (spans ``scanStrLengths``, one a page or
a chunk, nested in ``scanParse`` and a part of it; an interval union over
the scan's threads, per scan). Host wall, not device time. A program
without the timer reports nothing."""


def read(window):
    if "scanStrLengthsTime" not in window.counters:
        return None
    ns = window.per_query("scanStrLengthsTime")
    return None if ns is None else ns / 1e9
