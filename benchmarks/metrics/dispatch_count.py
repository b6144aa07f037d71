"""Device programs dispatched per completed query: the engine's
``dispatchCount`` over the window."""


def read(window):
    return window.per_query("dispatchCount")
