"""Model step: the whole window's useful model FLOPs over the chip's peak,
in %.  The FLOPs of every decoded token and every prefilled prompt
position in the window (``work/model_step.py``), over the window's length
times the peak bf16 rate of the chips used.  Moves ``itl_p95_s``."""


def read(run):
    if not run.ticks or run.peaks is None:
        return None
    step = run.work("model_step")
    flops = sum(step.tick(run.conf, t) for t in run.ticks)
    return 100.0 * flops / (run.seconds * run.chips * run.peaks.flops)
