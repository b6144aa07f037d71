"""Out-of-memory retries in the window: ``retryCount`` + ``splitRetryCount``.
0 on a clean run."""


def read(window):
    return (window.counters.get("retryCount", 0)
            + window.counters.get("splitRetryCount", 0))
