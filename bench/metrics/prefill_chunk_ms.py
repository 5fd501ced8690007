"""Model step: device time of one call of the chunk-prefill executable,
in ms.

The program names that executable ``chunk_prefill``, so each of its runs
is an ``XLA Modules`` event named ``jit_chunk_prefill(<hash>)``; this reads
the mean duration of its runs that start in the traced window.  A tick
that carries a chunk waits for it before its decode step, so the chunk
sets the longest gaps between tokens.  Nothing to read where no run has
that name (a program that does not name it, or prefill that is not
chunked).  Moves ``itl_p95_s``."""

MODULE = "jit_chunk_prefill("


def read(run):
    if run.trace is None or run.trace_window is None:
        return None
    calls = [m[2] for m in run.modules_in(run.trace_window)
             if m[0].startswith(MODULE)]
    return 1e3 * sum(calls) / len(calls) if calls else None
