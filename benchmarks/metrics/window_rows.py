"""Rows per completed query that the window execs were handed: the engine's
``windowRows`` (``exec/window.py``, from the row counts of their input
batches). A program without the counter (before PR 36), or a cell whose
statement has no window, reports nothing."""


def read(window):
    if "windowRows" not in window.counters:
        return None
    return window.per_query("windowRows")
