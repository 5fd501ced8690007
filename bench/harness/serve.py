"""Drive the system under test: build the server, offer load, read tokens.

The window calls the program's own serving entry points and nothing else:
``ElasticServer.submit`` when a request is due, ``ElasticServer.tick``
otherwise (admission, prefill chunks and one decode step for every running
sequence).  Token times are the host clock read after each ``tick``
returns, by counting what ``engine.generated`` gained; the decode step
returns its tokens to the host inside ``tick``, so a token stamped here has
reached the host.  When nothing is queued or running the loop sleeps until
the next request is due.

A scale cell opens a live scale event at the window's start
(``ElasticServer.start_scale``); the loop then calls the task's
``advance`` before every ``tick``, as the program's own serving loop does,
until the task has committed (``switchover``) and is done.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Tuple

from harness import traffic


@dataclasses.dataclass
class Record:
    req: traffic.Req
    phase: str                       # "warmup" | "window"
    due: float                       # host clock
    submit: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    finish: Optional[float] = None
    failed: Optional[str] = None


@dataclasses.dataclass
class Tick:
    """One ``tick`` of a traced window: its host span, the context length
    of every decoded row, and the prefill work it ran, each as
    ``(start, take)``: ``take`` prompt tokens after ``start`` cached ones
    (a chunk, or a whole prompt where prefill is monolithic)."""
    t0: float
    t1: float
    decode_ctx: List[int]
    prefill: List[Tuple[int, int]]


def build_server(mcfg, serving: Dict, seed: int):
    """An ``ElasticServer`` with the configuration's serving shape: the
    pooled expert store, overlapped staging, dropless routing."""
    from repro.core.elastic_engine import ElasticServer
    return ElasticServer(
        mcfg, tp=1, batch_per_replica=serving["batch_per_replica"],
        max_len=serving["max_len"],
        prefill_buckets=tuple(serving["prefill_buckets"]), seed=seed,
        kv_mode=serving["kv_mode"], kv_block_size=serving["kv_block"],
        expert_mode="pooled", staging="overlap",
        prefill_chunk=serving["prefill_chunk"])


class Driver:
    """Offers requests to one server and records what comes back."""

    def __init__(self, srv, annotate=None):
        self.srv = srv
        self.records: Dict[int, Record] = {}
        self.live: Dict[int, int] = {}           # rid -> tokens seen
        self.ticks: List[Tick] = []
        self.record_ticks = False
        self._ann = annotate or (lambda name: contextlib.nullcontext())
        self.lateness: List[float] = []          # window submits only
        self.tick_walls: List[Tuple[float, float]] = []  # (start, seconds)
        self.task = None                         # a live scale event
        self.scale_t0: Optional[float] = None
        self.scale_t1: Optional[float] = None    # the task came out done
        self.scale_task = None

    def start_scale(self, target) -> None:
        """Open a live scale event now; the loop advances it."""
        self.scale_t0 = time.perf_counter()
        self.task = self.scale_task = self.srv.start_scale(target)

    def _advance(self) -> None:
        if self.task is None:
            return
        with self._ann("srv.scale"):
            self.task.advance(time.perf_counter())
        if self.task.done:
            self.scale_t1 = time.perf_counter()
            self.task = None

    # ------------------------------------------------------------------ run
    def run(self, reqs: List[traffic.Req], phase: str, origin: float,
            until: float) -> None:
        """Offer ``reqs`` (due ``origin + req.due``) and tick until the host
        clock passes ``until``."""
        from repro.serving.workload import Request
        pending = sorted(reqs, key=lambda r: r.due)
        i = 0
        srv, eng = self.srv, self.srv.engine
        while True:
            now = time.perf_counter()
            if now >= until:
                return
            with self._ann("bench.submit"):
                while i < len(pending) and origin + pending[i].due <= now:
                    r = pending[i]
                    i += 1
                    rec = Record(r, phase, origin + r.due)
                    self.records[r.rid] = rec
                    try:
                        srv.submit(Request(r.rid, r.due, r.prompt_len,
                                           r.output_len, prompt=r.prompt))
                    except ValueError as e:     # refused: it can never fit
                        rec.failed = str(e)
                        continue
                    rec.submit = time.perf_counter()
                    if phase == "window":
                        self.lateness.append(rec.submit - rec.due)
                    self.live[r.rid] = 0
            self._advance()
            if not srv.queue and eng.active_count() == 0:
                nxt = (origin + pending[i].due if i < len(pending)
                       else until)
                if self.task is not None:
                    nxt = min(nxt, now + 0.002)
                with self._ann("bench.idle"):
                    time.sleep(max(0.0, min(nxt, until) - now))
                continue
            self.tick(now)

    def drain(self, rids, until: float) -> None:
        """Tick, offering nothing new, until every request in ``rids`` has
        finished (or failed) and any scale event is done, or the host
        clock passes ``until``."""
        wait = [r for r in rids if r in self.records]
        while time.perf_counter() < until:
            if self.task is None and all(
                    self.records[r].finish is not None
                    or self.records[r].failed for r in wait):
                return
            self._advance()
            if self.srv.queue or self.srv.engine.active_count():
                self.tick(time.perf_counter())
            else:
                time.sleep(0.002)

    def tick(self, now: float) -> None:
        srv, eng = self.srv, self.srv.engine
        before = ({j.rid: j.pos for j in eng._prefilling}
                  if self.record_ticks else None)
        t0 = time.perf_counter()
        with self._ann("srv.tick"):
            finished = srv.tick(now)
        t1 = time.perf_counter()
        self.tick_walls.append((t0, t1 - t0))
        with self._ann("bench.collect"):
            gen = eng.generated
            decode_ctx, prefill = [], []
            for rid, seen in list(self.live.items()):
                n = len(gen.get(rid, ()))
                if n == seen:
                    continue
                rec = self.records[rid]
                rec.times.extend([t1] * (n - seen))
                self.live[rid] = n
                if before is not None:
                    P = rec.req.prompt_len
                    first = 1 if seen == 0 else 0
                    # token j >= 1 came from a decode step over P + j
                    # positions; token 0 from the prompt's final prefill
                    decode_ctx.extend(P + j for j in range(seen + first, n))
                    if first:
                        prefill.append(self._final_prefill(rid, P, before))
            for rid in finished:
                self.records[rid].finish = t1
                self.live.pop(rid, None)
            if before is not None:
                for j in eng._prefilling:
                    start = before.get(j.rid, 0)
                    if j.pos > start:
                        prefill.append((start, j.pos - start))
                self.ticks.append(Tick(t0, t1, decode_ctx, prefill))

    @staticmethod
    def _final_prefill(rid: int, P: int, before) -> Tuple[int, int]:
        """The prefill that produced a first token: the prompt's last chunk,
        from where its job stood before the tick (the whole prompt where
        prefill is monolithic or the prompt fits one chunk)."""
        start = before.get(rid, 0)
        return (start, P - start)

    # --------------------------------------------------------------- views
    def window(self) -> List[Record]:
        return [r for r in self.records.values() if r.phase == "window"]


def free(srv) -> None:
    """Drop every device array the server holds, so the reference that
    runs after the window has the chip's memory."""
    import gc
    if srv.hmm._transfer is not None:
        srv.hmm._transfer.shutdown()
    eng = srv.engine
    eng.params = eng.cache = None
    eng.compiled = {}
    srv.hmm.params = srv.hmm.cache = srv.hmm.staged = None
    srv.imm._cache.clear()
    gc.collect()
