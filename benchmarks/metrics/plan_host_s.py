"""Seconds of planning on the calling thread per completed query: the
engine's ``planTime`` (SQL parse in ``session.sql``, then analysis,
overrides, plan cache and fingerprints in ``execute_plan`` up to
``execute_collect``; mirrored as ``plan`` spans). A program without the
timer (before PR 26) reports nothing."""


def read(window):
    if "planTime" not in window.counters:
        return None
    ns = window.per_query("planTime")
    return None if ns is None else ns / 1e9
