"""Input rows of the completed queries over the span from the window's start
to the last completion. Ending the span at the last completion keeps the
window's edge out of the number."""


def read(window):
    done = window.completed
    if not done:
        return None
    span = max(r.t_end for r in done) - window.t_start
    return window.scan_rows * len(done) / span
