"""Scale lifecycle: the serve loop's time spent inside the scale task's
``advance`` calls (``ScaleEvent.stall_s``: staging polls and any compile
on the serve thread), in s.  Moves ``output_tok_s``.  Scale cells only."""


def read(run):
    s = run.scale
    if s is None or s["event"] is None:
        return None
    return s["event"].stall_s
