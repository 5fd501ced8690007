"""Scale lifecycle: host wall time from ``ElasticServer.start_scale`` to
the scale task coming out done, its ``switchover`` included, in s.  Read
in scale cells only."""


def read(run):
    s = run.scale
    if s is None or s["t1"] is None or s["phase"] != "DONE":
        return None
    return s["t1"] - s["t0"]
