"""Process start to the start of the measured window: imports, device init,
data from the seed, Parquet written, views registered, reference computed,
warm-up queries (programs compiled or loaded from the cache)."""


def read(window):
    return window.setup_s
