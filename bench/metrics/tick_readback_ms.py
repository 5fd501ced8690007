"""Tick loop: the host's read-back in one tick, in ms.

Per tick in the traced window: the time the device was idle inside the
union of the program's ``srv.prefill.read`` (a final chunk's first token),
``srv.prefill.register`` (written blocks made matchable),
``srv.decode.read`` (the step's tokens) and ``srv.decode.commit`` (the
per-slot bookkeeping) spans; the mean over the window's ``srv.tick``
spans.  Nothing to read where the program writes no such span.  Moves
``itl_p95_s``."""
from harness import tick_split

SPANS = ("srv.prefill.read", "srv.prefill.register", "srv.decode.read",
         "srv.decode.commit")


def read(run):
    return tick_split.idle_ms(run, SPANS)
