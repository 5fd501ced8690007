"""Device: the share of the traced window in which no operation ran on
the chip, in %: 1 - (union of the device's op intervals) / window.
Moves ``itl_p95_s``."""


def read(run):
    if run.trace is None or run.trace_window is None:
        return None
    w = run.trace_window
    return 100.0 * (1.0 - run.busy_s / (w[1] - w[0]))
