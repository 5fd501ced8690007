"""Lock-cheap, thread-safe span tracer with a profiler sink (DESIGN.md §9).

Every live span (:meth:`Tracer.span`, :meth:`NullTracer.span`, the
:func:`traced` decorator) opens a ``jax.profiler.TraceAnnotation`` of the
same name around its body, installed tracer or not, so a JAX profiler
trace holds the program's spans on one clock with the device ops.  Its
args become the annotation's stats; ``set_metadata`` adds stats known only
at the end of the body.  With no profiler running an annotation is a
~1 µs no-op.

A process-global :class:`Tracer` (installed via :func:`install`) also
collects timeline events from every layer of the serving stack —
scale-phase spans, per-``TransferOp`` worker-thread spans, serve-loop
spans, request lifecycle instants, routing-skew counters — into a bounded
ring buffer.

Design constraints, in order:

* **cheap when off** — the default global is a :data:`NULL_TRACER`
  singleton: its ``span`` is the bare annotation and its other methods
  return immediately, so a hot path pays one inactive annotation per span.
* **thread-safe without a hot-path lock** — events land in a
  ``collections.deque(maxlen=...)``; ``deque.append`` is atomic under the
  GIL, so ``TransferEngine`` worker threads and the serve loop record
  concurrently without contention.  The only lock guards the (rare)
  first-sighting registration of a thread name.
* **monotonic, injectable clock** — defaults to ``time.perf_counter``;
  the simulator installs a tracer whose clock reads modelled time, and
  every recording method also accepts explicit timestamps so
  already-measured intervals (``TransferOp.t_done``) and sim-time spans
  (``SimScaleEvent.t_command``..``t_ready``) export losslessly.  Spans
  recorded after the fact (:meth:`Tracer.complete`) go to the ring buffer
  only: a sim-clock span never reaches the profiler.

Timestamps are stored in **seconds** (clock domain of the installed
clock); the Chrome-trace exporter (obs/export.py) converts to µs.
"""
from __future__ import annotations

import functools
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Union

from jax.profiler import TraceAnnotation

Lane = Union[int, str]


def annotate(name: str, args: Optional[dict] = None) -> TraceAnnotation:
    """The profiler half of a span alone: a ``TraceAnnotation`` named
    ``name`` whose stats are ``args``.  For work whose ring-buffer record
    is written after the fact (``complete``), or that spans calls."""
    return TraceAnnotation(name, **args) if args else TraceAnnotation(name)


class TraceEvent:
    """One recorded event.  ``ph`` follows the Chrome-trace phase codes:
    ``"X"`` complete span (``t0``..``t1``), ``"i"`` instant (``t0``),
    ``"C"`` counter sample (``t0``, value in ``args``)."""

    __slots__ = ("name", "cat", "ph", "t0", "t1", "tid", "args")

    def __init__(self, name: str, cat: str, ph: str, t0: float, t1: float,
                 tid: Lane, args: Optional[dict]):
        self.name = name
        self.cat = cat
        self.ph = ph
        self.t0 = t0
        self.t1 = t1
        self.tid = tid
        self.args = args

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    def __repr__(self) -> str:  # debugging/tests
        return (f"TraceEvent({self.name!r}, cat={self.cat!r}, ph={self.ph!r},"
                f" t0={self.t0:.6f}, dur={self.dur:.6f}, tid={self.tid!r})")


class _Span:
    """Re-entrant-free context manager emitted by :meth:`Tracer.span`: a
    profiler annotation plus a ring-buffer record."""

    __slots__ = ("_tr", "_name", "_cat", "_args", "_tid", "_t0", "_ann")

    def __init__(self, tr: "Tracer", name: str, cat: str,
                 args: Optional[dict], tid: Optional[Lane]):
        self._tr = tr
        self._name = name
        self._cat = cat
        self._args = args
        self._tid = tid

    def __enter__(self) -> "_Span":
        self._ann = annotate(self._name, self._args)
        self._ann.__enter__()
        self._t0 = self._tr._clock()
        return self

    def set_metadata(self, **args: Any) -> None:
        """Add args known only once the body has run (counts)."""
        self._args = {**(self._args or {}), **args}
        self._ann.set_metadata(**args)

    def __exit__(self, *exc) -> bool:
        t1 = self._tr._clock()
        self._ann.__exit__(*exc)
        self._tr.complete(self._name, self._t0, t1,
                          cat=self._cat, args=self._args, tid=self._tid)
        return False


class Tracer:
    """Collecting tracer.  All recording methods are safe to call from any
    thread; events beyond ``capacity`` evict the oldest (bounded memory —
    a serve loop can run traced indefinitely)."""

    enabled = True

    def __init__(self, *, capacity: int = 65536,
                 clock: Callable[[], float] = time.perf_counter):
        self._clock = clock
        self._events: deque = deque(maxlen=capacity)
        self._thread_names: Dict[int, str] = {}
        self._name_lock = threading.Lock()

    # ------------------------------------------------------------- clock
    def now(self) -> float:
        return self._clock()

    # ------------------------------------------------------------ record
    def _resolve_tid(self, tid: Optional[Lane]) -> Lane:
        if tid is not None:
            return tid
        ident = threading.get_ident()
        if ident not in self._thread_names:
            with self._name_lock:
                self._thread_names.setdefault(
                    ident, threading.current_thread().name)
        return ident

    def complete(self, name: str, t0: float, t1: float, *, cat: str = "",
                 args: Optional[dict] = None,
                 tid: Optional[Lane] = None) -> None:
        """Record an already-measured span (explicit timestamps, in the
        tracer's clock domain — real seconds or sim seconds)."""
        self._events.append(TraceEvent(name, cat, "X", t0, t1,
                                       self._resolve_tid(tid), args))

    def span(self, name: str, *, cat: str = "",
             args: Optional[dict] = None,
             tid: Optional[Lane] = None) -> _Span:
        """``with tracer.span("srv.step", cat="serve"): ...`` — times
        the body with the tracer's clock and annotates it for the
        profiler."""
        return _Span(self, name, cat, args, tid)

    def instant(self, name: str, *, cat: str = "",
                args: Optional[dict] = None, t: Optional[float] = None,
                tid: Optional[Lane] = None) -> None:
        if t is None:
            t = self._clock()
        self._events.append(TraceEvent(name, cat, "i", t, t,
                                       self._resolve_tid(tid), args))

    def counter(self, name: str, value: float, *, cat: str = "",
                t: Optional[float] = None,
                tid: Optional[Lane] = None) -> None:
        if t is None:
            t = self._clock()
        self._events.append(TraceEvent(name, cat, "C", t, t,
                                       self._resolve_tid(tid),
                                       {"value": value}))

    # ------------------------------------------------------------ access
    def events(self) -> List[TraceEvent]:
        return list(self._events)

    def thread_names(self) -> Dict[int, str]:
        with self._name_lock:
            return dict(self._thread_names)

    def clear(self) -> None:
        self._events.clear()


class NullTracer:
    """The disabled fast path: ``span`` is the bare profiler annotation,
    every other method returns immediately.  ``now`` still reads the wall
    clock so call sites can use it unconditionally."""

    enabled = False

    def now(self) -> float:
        return time.perf_counter()

    def complete(self, *a: Any, **k: Any) -> None:
        pass

    def span(self, name: str, *, cat: str = "",
             args: Optional[dict] = None,
             tid: Optional[Lane] = None) -> TraceAnnotation:
        return annotate(name, args)

    def instant(self, *a: Any, **k: Any) -> None:
        pass

    def counter(self, *a: Any, **k: Any) -> None:
        pass

    def events(self) -> List[TraceEvent]:
        return []

    def thread_names(self) -> Dict[int, str]:
        return {}

    def clear(self) -> None:
        pass


NULL_TRACER = NullTracer()
_active: Union[Tracer, NullTracer] = NULL_TRACER


def install(tracer: Optional[Tracer]) -> Union[Tracer, NullTracer]:
    """Install the process-global tracer (``None`` disables tracing)."""
    global _active
    _active = tracer if tracer is not None else NULL_TRACER
    return _active


def get_tracer() -> Union[Tracer, NullTracer]:
    return _active


def traced(name: str, cat: str = "") -> Callable:
    """Decorator form of :meth:`Tracer.span`; with the null tracer it is
    one global read, an identity check and the bare annotation."""
    def deco(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def wrapper(*args: Any, **kwargs: Any):
            tr = _active
            if tr is NULL_TRACER:
                with TraceAnnotation(name):
                    return fn(*args, **kwargs)
            with tr.span(name, cat=cat):
                return fn(*args, **kwargs)
        return wrapper
    return deco
