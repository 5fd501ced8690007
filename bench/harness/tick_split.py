"""The host's time inside a tick, split by the program's own spans.

The program annotates each ``ElasticServer.tick`` with a tree of ``srv.*``
spans (DESIGN.md §9): admission, and for each prefill chunk and the decode
step its preparation, dispatch and read-back.  ``idle_ms`` reads, per tick
of the traced window, the time the device was idle inside the union of the
named spans, as the metrics ``tick_prep_ms`` and ``tick_readback_ms`` do.
A trace without those spans (a program that does not write them) reads
nothing.
"""
from __future__ import annotations

from bisect import bisect_left, bisect_right
from itertools import accumulate
from typing import Iterable, List, Optional, Tuple

from harness import trace as tracemod

Interval = Tuple[float, float]


def busy_cover(busy: List[Interval]):
    """``cover(a, b)``: the seconds of ``[a, b]`` that the sorted, disjoint
    ``busy`` intervals cover, by bisection."""
    starts = [s for s, _ in busy]
    ends = [e for _, e in busy]
    cum = [0.0, *accumulate(e - s for s, e in busy)]

    def cover(a: float, b: float) -> float:
        i, j = bisect_right(ends, a), bisect_left(starts, b)
        if i >= j:
            return 0.0
        return (cum[j] - cum[i] - max(0.0, a - starts[i])
                - max(0.0, ends[j - 1] - b))
    return cover


def idle_ms(run, names: Iterable[str]) -> Optional[float]:
    """Mean over the window's ``srv.tick`` spans of the device-idle
    milliseconds inside the union of the ``names`` spans that start in a
    tick; None where the trace holds no such span."""
    if run.trace is None or run.trace_window is None:
        return None
    ticks = run.tick_spans()
    if not ticks:
        return None
    names = set(names)
    w = run.trace_window
    t0s = [s for s, _ in ticks]
    spans = []
    for n, s, d in run.trace["host"]:
        if n not in names or not (w[0] <= s and s + d <= w[1]):
            continue
        k = bisect_right(t0s, s) - 1
        if k >= 0 and s < ticks[k][1]:
            spans.append((s, min(s + d, ticks[k][1])))
    if not spans:
        return None
    cover = busy_cover(run.trace_busy())
    idle = sum((e - s) - cover(s, e) for s, e in tracemod.union(spans))
    return 1e3 * idle / len(ticks)
