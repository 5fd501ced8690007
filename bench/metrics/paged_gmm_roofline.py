"""Kernel: the pooled expert FFN's share of its roofline, in %.

For every ``paged_gmm`` call in the traced window, the least time the chip
could take for its useful work (``work/paged_gmm.py``: routed rows and
the pages of the experts they reach) is the larger of FLOPs over the peak
rate and bytes over the HBM bandwidth; their sum over the kernel's self
time in the trace.  Moves ``itl_p95_s``."""


def read(run):
    return run.roofline("paged_gmm", run.work("paged_gmm"))
