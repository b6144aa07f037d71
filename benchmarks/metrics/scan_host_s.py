"""Thread-seconds of host work in the scan per completed query:
``decodeTime`` + ``deviceDecodeTime`` + ``scanPrefetchTime``. The engine's
timers run on the host around decode, enqueue and prefetch and are summed
over its task threads: this is host work, not device time, and can pass the
query's wall time."""


def read(window):
    ns = window.per_query("decodeTime", "deviceDecodeTime", "scanPrefetchTime")
    return None if ns is None else ns / 1e9
