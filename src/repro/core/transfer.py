"""Background transfer engine — the async half of the HMM (DESIGN.md §3).

``HMM.begin_scale`` emits its per-tensor / per-page staging work list as
independent :class:`TransferOp` s; with ``staging="overlap"`` they execute on
this bounded thread pool while the serving thread keeps running decode ticks.
Staging only *reads* immutable live weights (weights never mutate during
serving; the KV cache is untouched until commit), so ticks concurrent with
in-flight ops are safe by construction — the paper's "scaling steps proceed
concurrently with serving" (§4.4–§4.5) as real off-thread ``jax.device_put``
traffic instead of tick-interleaved slices.

The op list is a trivially parallel graph: every op stages one parameter
tensor (or pool bank / index array) and the only join point is the final
tree assembly, performed on the serving thread by ``HMM.poll_staging`` once
every op has finished.  ``TransferSession.cancel`` is the abort barrier:
pending ops never start, running ops are joined — after it returns no worker
can touch HMM state, so ``ExpertPageTable.abort`` may safely unwind.

JAX note: the CPU/TPU PJRT clients are thread-safe; compiled decode steps on
the serving thread only donate the KV cache, never params, so concurrent
reads of param shards from worker threads race with nothing.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import wait as _futures_wait
from typing import Any, Callable, List, Optional

from repro import obs


@dataclasses.dataclass
class TransferOp:
    """One independent unit of staging work (one tensor, pool bank, or index
    array).  ``fn`` must be self-contained: it reads only immutable inputs
    captured at creation time and returns the staged result."""
    index: int
    label: str
    fn: Callable[[], Any]
    state: str = "pending"      # pending | running | done | failed | cancelled
    result: Any = None
    error: Optional[BaseException] = None
    seconds: float = 0.0        # execution time of fn (0 if never ran)
    t_done: float = 0.0         # perf_counter() when fn returned


class TransferSession:
    """A submitted batch of ops, polled/joined/cancelled as a unit."""

    def __init__(self, ops: List[TransferOp]):
        self.ops = ops
        self.futures: List[Future] = []
        self.cancelled = threading.Event()

    def finished(self) -> bool:
        """Non-blocking: True once every op has run (or been cancelled)."""
        return all(f.done() for f in self.futures)

    def remaining(self) -> int:
        return sum(1 for f in self.futures if not f.done())

    def join(self, timeout: Optional[float] = None) -> bool:
        """Block until every op has finished; returns ``finished()``."""
        _futures_wait(self.futures, timeout=timeout)
        return self.finished()

    def cancel(self) -> None:
        """Cancel-or-join barrier: ops that have not started never will;
        ops already running are joined.  On return no worker thread holds a
        reference into the caller's state."""
        self.cancelled.set()
        for f in self.futures:
            f.cancel()
        _futures_wait(self.futures)
        for op, f in zip(self.ops, self.futures):
            if f.cancelled():
                op.state = "cancelled"

    def failed_ops(self) -> List[TransferOp]:
        return [op for op in self.ops if op.state == "failed"]

    @property
    def op_seconds(self) -> float:
        """Σ per-op execution time — the serial-equivalent transfer work.
        Compared against the session's wall-clock this is the overlap
        efficiency reported by ``metrics.summarize``.

        Only ops that actually *executed* count: a cancelled op did zero
        transfer work, so including it (even at ``seconds == 0``) would be
        wrong twice over — it can't dilute the numerator, and if a stray
        timestamp ever landed on a skipped op it must not inflate it
        either.  The state filter pins that contract structurally rather
        than relying on cancelled ops never being timed."""
        return sum(op.seconds for op in self.ops
                   if op.state in ("done", "failed"))

    @property
    def last_done_t(self) -> float:
        return max((op.t_done for op in self.ops if op.t_done), default=0.0)


class TransferEngine:
    """Bounded worker pool issuing staging ops off the serving thread.

    One engine per HMM, persistent across scaling sessions (threads are
    reused, not churned per scale event).  ``max_workers`` bounds HBM/link
    contention with the serving hot path — the knob the cost model's
    ``overlap_contention`` constant projects to paper scale."""

    def __init__(self, max_workers: int = 4):
        self.max_workers = max(1, int(max_workers))
        self._pool = ThreadPoolExecutor(max_workers=self.max_workers,
                                        thread_name_prefix="hmm-transfer")

    def submit(self, ops: List[TransferOp]) -> TransferSession:
        session = TransferSession(ops)
        session.futures = [self._pool.submit(self._run, session, op)
                           for op in ops]
        return session

    @staticmethod
    def _run(session: TransferSession, op: TransferOp) -> None:
        if session.cancelled.is_set():
            # Skipped entirely: no span, no timing.  Emitting a complete()
            # here (state "cancelled", seconds≈0) would pollute the trace
            # timeline and the op_seconds / overlap-efficiency denominators
            # with ops that did zero transfer work — the span below is
            # reserved for ops that actually executed fn().
            op.state = "cancelled"
            return
        op.state = "running"
        t0 = time.perf_counter()
        try:
            # the op's live annotation, on this worker thread's lane of a
            # profiler trace; the ring-buffer span is recorded below
            with obs.annotate(op.label, {"index": op.index}):
                op.result = op.fn()
            op.state = "done"
        except BaseException as e:  # noqa: BLE001 — surfaced via failed_ops
            op.error = e
            op.state = "failed"
        finally:
            op.t_done = time.perf_counter()
            op.seconds = op.t_done - t0
            # span lands on the worker thread's lane (obs captures the
            # "hmm-transfer-*" thread name lazily); timestamps are the
            # already-measured perf_counter interval, not re-clocked
            obs.get_tracer().complete(op.label, t0, op.t_done,
                                      cat="transfer",
                                      args={"state": op.state,
                                            "index": op.index})

    def shutdown(self) -> None:
        self._pool.shutdown(wait=True)
