"""Character bytes per completed query that the device decode lays out
for string columns: lanes x ``char_cap`` of each string column of each
staged batch, the engine's ``deviceDecodeStringBytes``
(``columnar/transfer.py``, booked on the host from the batch's decode
plan). A program without the counter reports nothing."""


def read(window):
    if "deviceDecodeStringBytes" not in window.counters:
        return None
    return window.per_query("deviceDecodeStringBytes")
