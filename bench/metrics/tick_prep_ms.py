"""Tick loop: the host's preparation in one tick, in ms.

Per tick in the traced window: the time the device was idle inside the
union of the program's ``srv.admit`` (admission), ``srv.prefill.prep`` and
``srv.decode.prep`` (the host arrays of each chunk and of the decode
step) spans; the mean over the window's ``srv.tick`` spans.  Nothing to
read where the program writes no such span.  Moves ``itl_p95_s``."""
from harness import tick_split

SPANS = ("srv.admit", "srv.prefill.prep", "srv.decode.prep")


def read(run):
    return tick_split.idle_ms(run, SPANS)
