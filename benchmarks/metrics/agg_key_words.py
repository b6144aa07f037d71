"""Group-key words per completed query that the aggregate passes sort or
gather: the engine's ``aggKeyWords`` (``exec/agg.py``: lanes x subkey
words of each dispatched partial, merge or final pass, a string key
counting its validity, its length and ``char_cap/8`` packed words; from
the program's static shapes, no device read). A program without the
counter reports nothing."""


def read(window):
    if "aggKeyWords" not in window.counters:
        return None
    return window.per_query("aggKeyWords")
