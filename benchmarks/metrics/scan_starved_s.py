"""Seconds per completed query in which every open scan stream of the
query waited on an empty prefetch ring at once, so that no stream had a
batch to hand the device: the engine's ``scanStarvedTime`` (booked by
``trace.QueryScope.scan_streams`` from the consumers' ``scanWait``
pulls; PR 38). It measures host starvation of the scan streams, and
books time in which the device is still busy with queued work too: it
is not device idle, and can pass the trace's idle seconds. A program
without the counter (before PR 38) reports nothing."""


def read(window):
    if "scanStarvedTime" not in window.counters:
        return None
    ns = window.per_query("scanStarvedTime")
    return None if ns is None else ns / 1e9
