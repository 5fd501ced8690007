"""Model step: device time of one call of the decode executable, in ms.

In every tick the program runs its prefill work first, one executable call
per chunk (or per admitted prompt where prefill is monolithic), then one
decode step if any sequence is decoding.  So in each ``srv.tick`` span of
the traced window the last step-sized executable run (``XLA Modules``
line; runs under 0.1 ms are small helper programs, such as a key's seed or
a type conversion) is, in nearly every tick, the decode step: the module
that is last in most ticks is the decode executable, and this reads the
mean duration of all its runs in the window.  Moves ``itl_p95_s``."""
from collections import Counter

STEP_S = 1e-4


def read(run):
    if run.trace is None or run.trace_window is None:
        return None
    last = Counter()
    for span in run.tick_spans():
        runs = [m for m in run.modules_in(span) if m[2] >= STEP_S]
        if runs:
            last[runs[-1][0]] += 1
    if not last:
        return None
    name = last.most_common(1)[0][0]
    calls = [m[2] for m in run.modules_in(run.trace_window)
             if m[0] == name and m[2] >= STEP_S]
    return 1e3 * sum(calls) / len(calls)
