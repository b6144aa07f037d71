"""Seconds per completed query in which the scan's device-decode plans
read files: footers and each column chunk's seek + read, the engine's
``scanReadTime`` (spans ``scanRead``, nested in
``FileScan.deviceDecodeTime``; an interval union over the scan's
threads, per scan; PR 38). Host wall, not device time. A program
without the timer (before PR 38) reports nothing."""


def read(window):
    if "scanReadTime" not in window.counters:
        return None
    ns = window.per_query("scanReadTime")
    return None if ns is None else ns / 1e9
