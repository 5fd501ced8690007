"""Unified tracing & telemetry for the serving stack (DESIGN.md §9).

Every live span is also a ``jax.profiler.TraceAnnotation``, so a profiler
trace holds it on the device ops' clock; spans recorded after the fact
(``complete``, the simulator's sim-clock spans) stay in the ring buffer.

Span schema (shared by the real engine and the simulator — a driver
closed-loop run over either backend exports the same trace shape):

| cat        | events (ph)                                               |
|------------|-----------------------------------------------------------|
| ``scale``  | ``scale.<PHASE>`` spans, one per ScalePhase (lane "scale")|
| ``hmm``    | ``hmm.begin_scale/stage_increment/commit/abort/boot`` spans|
| ``transfer``| one span per TransferOp, named by its label, emitted on  |
|            | the worker thread that ran it (kvmig ops included)        |
| ``serve``  | ``srv.*`` spans (one tree per tick, DESIGN.md §9),        |
|            | ``chunk.plan`` / ``preempt`` / ``kv.cow_copy`` instants   |
| ``req``    | ``req.admit`` / ``req.first_token`` / ``req.finish``      |
| ``routing``| ``routing.top_expert_share`` counter samples              |

Usage::

    from repro import obs
    obs.install(obs.Tracer())            # enable (None to disable)
    ... serve ...
    obs.write_chrome_trace("trace.json", obs.get_tracer())
"""
from repro.obs.export import (chrome_trace, load_trace, validate_trace,
                              write_chrome_trace)
from repro.obs.tracer import (NULL_TRACER, NullTracer, TraceEvent, Tracer,
                              annotate, get_tracer, install, traced)

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "TraceEvent",
    "install", "get_tracer", "traced", "annotate",
    "chrome_trace", "write_chrome_trace", "load_trace", "validate_trace",
]
