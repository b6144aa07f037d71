"""Thread-seconds of host work moving batches per completed query:
``packBatchTime`` + ``copyToDeviceTime`` + ``copyFromDeviceTime``, summed
over the engine's task threads. Host work and enqueue, not device time."""


def read(window):
    ns = window.per_query("packBatchTime", "copyToDeviceTime",
                          "copyFromDeviceTime")
    return None if ns is None else ns / 1e9
