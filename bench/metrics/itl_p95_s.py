"""p95 of every gap between two consecutive output tokens of one request,
both of which reached the host in the window."""


def read(run):
    t0, t1 = run.win
    gaps = [b - a for r in run.records for a, b in zip(r.times, r.times[1:])
            if t0 <= a and b < t1]
    return run.percentile(gaps, 95)
