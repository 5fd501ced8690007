"""Kernel: the paged attention kernel's share of its roofline, in %.

For every ``paged_attention`` call in the traced window (decode steps and
chunked-prefill tiles), the least time its useful work needs
(``work/paged_attention.py``: the K and V of live positions, the causal
score FLOPs) over the kernel's self time in the trace.  Moves
``itl_p95_s``."""


def read(run):
    return run.roofline("paged_attention", run.work("paged_attention"))
