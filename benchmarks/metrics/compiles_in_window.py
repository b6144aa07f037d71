"""Programs JAX compiled, or loaded from its persistent cache, inside the
measured window (``backend_compile_duration`` events). 0 when the warm-up
covered every shape."""


def read(window):
    return window.compiles.compiles
