"""Seconds per completed query the interpreter spent in its garbage
collections, every generation, on any thread of the process: the
engine's ``gcPauseTime`` (one ``gc.callbacks`` hook registered at
engine start; collections of generation 1 and 2 are also ``gcPause``
spans; PR 38). A program without the hook (before PR 38) reports
nothing."""


def read(window):
    if "gcPauseTime" not in window.counters:
        return None
    ns = window.per_query("gcPauseTime")
    return None if ns is None else ns / 1e9
