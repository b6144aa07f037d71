"""Share of the traced part of the window in which no operation ran on the
device, from the profiler's trace (``harness/profiler.py``), averaged over
the chips. Nothing without a trace."""


def read(window):
    if window.trace is None:
        return None
    return 100.0 * window.trace["idle_share"]
