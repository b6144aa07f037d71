"""Seconds from a query's begin to its first enqueue of any device
program, per completed query: the engine's ``firstDispatchTime`` (booked
once a query, with a ``firstDispatch`` instant naming the program). The
host's own reading of the first idle gap of the device. A program
without the timer (before PR 26) reports nothing."""


def read(window):
    if "firstDispatchTime" not in window.counters:
        return None
    ns = window.per_query("firstDispatchTime")
    return None if ns is None else ns / 1e9
