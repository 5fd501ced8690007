"""Output tokens that reached the host in the window, over its length.
Every request's tokens count, whenever it was due."""


def read(run):
    t0, t1 = run.win
    n = sum(1 for r in run.records for t in r.times if t0 <= t < t1)
    return n / (t1 - t0)
