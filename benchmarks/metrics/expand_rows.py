"""Rows per completed query out of the grouping-sets expansion: the
engine's ``expandRows`` (``exec/basic.py``: sets x each input batch's row
count where the host has it, else sets x its lanes; no device read). A
program without the counter, or a cell whose statement has no ROLLUP or
CUBE, reports nothing."""


def read(window):
    if "expandRows" not in window.counters:
        return None
    return window.per_query("expandRows")
