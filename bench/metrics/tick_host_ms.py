"""Tick loop: the host's share of one ``ElasticServer.tick``, in ms.

Per tick in the traced window: the wall time of the harness's ``srv.tick``
annotation less the time the device was busy inside it; the mean over the
ticks.  Moves ``itl_p95_s``."""


def read(run):
    if run.trace is None or run.trace_window is None:
        return None
    busy = run.trace_busy()
    host = [(e - s) - run.overlap(busy, s, e) for s, e in run.tick_spans()]
    return 1e3 * sum(host) / len(host) if host else None
