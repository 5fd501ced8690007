"""ElasticServer — ties the Coordinator, HMM and IMM to the serving engine.

The serving lifecycle (paper §5):
* ``boot(cfg)`` — HMM loads weights once, IMM compiles + attaches, engine
  starts taking requests.
* ``scale_to(cfg')`` — concurrent scaling: HMM stages the minimal-cost
  reconfiguration (zero-copy + P2P + expert-page remap) and the IMM prepares
  the target instance, **while the active instance keeps serving**
  (tick() remains callable throughout).  ``switchover()`` retargets traffic:
  surviving decode slots continue on the *same* KV cache rows — zero
  downtime, zero token divergence (asserted in tests).
* scale-down (paged KV, ``scaledown="migrate"``, default): live sequences
  in doomed slots MIGRATE — their KV blocks device-copy onto survivor
  partitions in the background (MIGRATING phase) and devices release as
  soon as the copies land, instead of waiting out the longest in-flight
  sequence.  ``scaledown="drain"`` (and the dense layout) keeps the
  legacy drain of evicted slots.

For closed-loop operation, ``ElasticServer`` implements the
``ServingBackend`` protocol (serving/driver.py): ``start_scale`` returns an
``EngineScalingTask`` whose ``advance`` is a non-blocking poll.  With the
default ``staging="serial"`` each poll performs one per-tensor HMM reshard
(tick-interleaved staging); with ``staging="overlap"`` the whole work list
runs on the HMM's background ``TransferEngine`` while real decode ticks
proceed concurrently and the IMM AOT compile overlaps the transfer window
(DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
import math
import time
from functools import partial
from typing import Dict, List, Optional

import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.coordinator import LoadEstimator, ScalingPolicy
from repro.core.hmm import HMM, TransferStats
from repro.core.imm import IMM
from repro.core.topology import ElasticConfig
from repro.serving.driver import ScalePhase, admission_during_scale
from repro.serving.engine import InferenceEngine
from repro.serving.rebalance import RebalancePolicy
from repro.serving.workload import Request


def _phase_annotation(task, old: Optional[ScalePhase], new: ScalePhase,
                      prefix: str) -> None:
    """The live half of a task's phase span: on a transition, close the
    profiler annotation of the phase left and open ``<prefix>.<PHASE>``
    for the phase entered (none for a terminal one).  Phase setters run
    on the serving thread, so the annotation lands on its lane."""
    if old is new:
        return
    ann = getattr(task, "_phase_ann", None)
    if ann is not None:
        ann.__exit__(None, None, None)
    task._phase_ann = None
    if not new.terminal:
        task._phase_ann = obs.annotate(f"{prefix}.{new.name}")
        task._phase_ann.__enter__()


@dataclasses.dataclass
class ScaleEvent:
    t: float
    src: str
    dst: str
    stats: TransferStats
    compile_hit: bool
    stage_s: float
    switch_s: float
    # serve-loop time blocked on staging/compile work (the decode-stall
    # during scaling): ~= stage_s on the blocking/serial paths, near-zero
    # with the background TransferEngine (staging="overlap")
    stall_s: float = 0.0
    staging: str = "serial"
    # staging wall-clock frozen at record time: ``stats`` aliases
    # ``hmm.last_stats``, whose wall_s later grows by the commit/KV-grow
    # time at switchover — overlap-efficiency ratios must use this snapshot
    stage_wall_s: float = 0.0
    # zero-drain scale-down: live KV blocks device-copied off doomed
    # partitions during the MIGRATING phase (0 for scale-up / drain mode)
    migrated_blocks: int = 0
    migration_bytes: int = 0


class EngineScalingTask:
    """Resumable scale transition over the real JAX engine (driver.ScalingTask).

    ``advance`` is a non-blocking completion poll; what runs inside it
    depends on the HMM's staging mode:

    * ``staging="serial"`` — one per-tensor HMM reshard per ``advance``,
      then a COMPILING advance (IMM pre-init; LRU hit makes it ~free),
    * ``staging="overlap"`` — the transfers already run on the background
      ``TransferEngine`` (submitted at ``start_scale``); the first
      ``advance`` runs the IMM AOT compile on the serve thread *while* the
      transfers proceed (STAGING ∥ COMPILING, DESIGN.md §3) and every later
      ``advance`` just polls completion.

    Scale-down continues into MIGRATING (``scaledown="migrate"``, paged
    KV: live sequences' blocks device-copy onto survivor partitions as
    per-block ops on the HMM's TransferEngine while decode ticks proceed —
    the doomed devices release as soon as the copies land) or DRAINING
    (``scaledown="drain"`` / dense KV: evicted slots run to completion).
    Either way the phases continue -> COMMITTING (switchover, a barrier
    that joins any in-flight ops) -> DONE, and the engine's ``tick()`` is
    legal — and expected — between every ``advance`` call.
    """

    def __init__(self, server: "ElasticServer", target: ElasticConfig):
        # scale events take priority over background rebalancing: an
        # in-flight rebalance is aborted (its staged pages freed) before
        # the remap is staged — the page table forbids both at once
        server._preempt_rebalance()
        self.server = server
        self.target = target
        self.phase = ScalePhase.STAGING
        self.staging_mode = server.hmm.staging_mode
        self.increments_total = server.hmm.begin_scale(target) + 1  # +compile
        self.increments_done = 0
        self.stats: TransferStats = server.hmm._stage_stats
        # staging-only snapshot, frozen when STAGING completes (``stats``
        # keeps accumulating: commit merges the KV handover bytes into it)
        self.stage_stats: Optional[TransferStats] = None
        self.event: Optional[ScaleEvent] = None
        self.stall_s = 0.0      # serve-loop time spent inside advance()
        self._compile_hit: Optional[bool] = None
        self._down = target.ndev < server.engine.cfg.ndev
        self._keep = target.dp * server.engine.batch_per_replica
        self._migrate = self._down and server.scaledown_mode == "migrate"
        # in-flight KV migrations: (MigrationJob, TransferSession)
        self._mig_inflight: List = []
        self._mig_warm = False
        self.migrated_blocks = 0
        self.migration_bytes = 0
        if self._down:
            # stop admitting into doomed slots right away so the drain
            # overlaps the staging increments instead of following them
            server.engine.admit_limit = self._keep
        server._active_task = self

    @property
    def phase(self) -> ScalePhase:
        return self._phase

    @phase.setter
    def phase(self, new: ScalePhase) -> None:
        """Every phase transition emits one ``scale.<PHASE>`` span on the
        "scale" lane — the per-ScalePhase timeline of the trace layer.
        Captures ABORTED unwinds too, since those also assign here."""
        tr = obs.get_tracer()
        now = tr.now()
        old = getattr(self, "_phase", None)
        self._phase = new
        if old is not None and old is not new:
            tr.complete(f"scale.{old.name}", self._phase_t0, now,
                        cat="scale", tid="scale",
                        args={"target": self.target.describe(),
                              "next": new.name})
        _phase_annotation(self, old, new, "scale")
        self._phase_t0 = now

    @property
    def done(self) -> bool:
        return self.phase.terminal

    @property
    def overlap_efficiency(self) -> Optional[float]:
        """Σ transfer-op time / staging wall-clock (>1 = real overlap);
        None until staging completed (driver event log, metrics)."""
        st = self.stage_stats
        if st is None or st.wall_s <= 0 or st.op_s <= 0:
            return None
        return st.op_s / st.wall_s

    def _finish_staging(self):
        """STAGING complete: freeze the staging snapshot, record the event
        (IMM compile is a hit by now on the overlapped path) and move on."""
        self.stage_stats = dataclasses.replace(self.stats)
        self.event = self.server._record_stage(self.target,
                                               self.stats.wall_s)
        if self._compile_hit is not None:
            self.event.compile_hit = self._compile_hit
        self.phase = self._scaledown_phase()

    def _scaledown_phase(self) -> ScalePhase:
        if not self._down:
            return ScalePhase.COMMITTING
        return (ScalePhase.MIGRATING if self._migrate
                else ScalePhase.DRAINING)

    def _unwind_failed(self):
        """A staging/compile step raised: release every piece of task state
        so the server keeps serving on the still-active config (the HMM
        session itself is aborted — poll_staging already did for overlap
        failures; abort() is idempotent either way)."""
        self.server.hmm.abort()
        if self._down:
            self.server.engine.admit_limit = None
        self.server._staged_cfg = None
        self.server._active_task = None
        self.phase = ScalePhase.ABORTED

    def advance(self, now: float) -> ScalePhase:
        ph = self.phase
        if ph is ScalePhase.STAGING:
            t0 = time.perf_counter()
            try:
                if self.staging_mode == "overlap":
                    if self._compile_hit is None:
                        # the AOT compile runs on the serve thread while the
                        # TransferEngine moves bytes in the background — the
                        # overlapped pipeline's COMPILING ∥ STAGING
                        self._compile_hit = self.server.imm.has(self.target)
                        self.server.imm.preinitialize(self.target)
                    if self.server.hmm.poll_staging():
                        self.increments_done = self.increments_total
                        self._finish_staging()
                    else:
                        self.increments_done = (
                            self.increments_total - 1
                            - self.server.hmm.staging_remaining)
                else:
                    more = self.server.hmm.stage_increment()
                    self.increments_done += 1
                    if not more:
                        self.stage_stats = dataclasses.replace(self.stats)
                        self.phase = ScalePhase.COMPILING
            except BaseException:
                self._unwind_failed()
                raise
            self.stall_s += time.perf_counter() - t0
        elif ph is ScalePhase.COMPILING:
            t0 = time.perf_counter()
            self.increments_done += 1
            # staging time = the HMM's tracked staging work, NOT wall time
            # since task creation (which would count the decode ticks that
            # ran between increments); _record_stage adds the compile time
            try:
                self.event = self.server._record_stage(
                    self.target, self.stats.wall_s)
            except BaseException:
                self._unwind_failed()
                raise
            self.phase = self._scaledown_phase()
            self.stall_s += time.perf_counter() - t0
        elif ph is ScalePhase.MIGRATING:
            t0 = time.perf_counter()
            try:
                if self._advance_migration():
                    self.phase = ScalePhase.COMMITTING
            except BaseException:
                self._cancel_migrations()
                self._unwind_failed()
                raise
            self.stall_s += time.perf_counter() - t0
        elif ph is ScalePhase.DRAINING:
            if self.server.engine.drained(self._keep):
                self.phase = ScalePhase.COMMITTING
        elif ph is ScalePhase.COMMITTING:
            self.server.switchover()
            self.phase = ScalePhase.DONE
            self.server._active_task = None
        if self.event is not None:
            self.event.stall_s = self.stall_s
        return self.phase

    def _advance_migration(self) -> bool:
        """One MIGRATING poll: harvest finished per-block copy sessions
        (cut the slots over), submit new component moves, and report
        whether every doomed partition is evacuated.  The copies run as
        TransferOps on the HMM's background TransferEngine, so decode
        ticks between polls overlap them exactly like overlapped staging
        (DESIGN.md §3 — migration is asynchronous in every staging mode)."""
        eng = self.server.engine
        for job, sess in list(self._mig_inflight):
            if not sess.finished():
                continue
            self._mig_inflight.remove((job, sess))
            failed = sess.failed_ops()
            if failed:
                self._cancel_one(job)
                raise RuntimeError(
                    f"KV migration copy op {failed[0].label!r} failed "
                    f"({len(failed)} op(s)); scale-down aborted"
                ) from failed[0].error
            eng.finish_migration(job)
            self.migrated_blocks += job.ticket.num_blocks
            self.migration_bytes += (job.ticket.num_blocks
                                     * eng.block_nbytes())
            if self.event is not None:
                # per-harvest, not only at completion: components already
                # committed are permanent even if a later abort lands
                self.event.migrated_blocks = self.migrated_blocks
                self.event.migration_bytes = self.migration_bytes
        while True:
            job = eng.plan_migration()
            if job is None:
                break
            if not self._mig_warm:
                # compile the block-copy executable on the serve thread so
                # no worker ever compiles concurrently with serving
                eng.prewarm_block_copy()
                self._mig_warm = True
            from repro.core.transfer import TransferOp
            ops = [TransferOp(index=i, label=f"kvmig:{s}->{d}",
                              fn=partial(eng.copy_block, s, d))
                   for i, (s, d) in enumerate(job.ticket.pairs)]
            sess = self.server.hmm.transfer_engine().submit(ops)
            self._mig_inflight.append((job, sess))
        if self._mig_inflight:
            # bounded yield to the copy workers — the same GIL courtesy as
            # HMM.poll_staging: with every doomed sequence paused and the
            # survivors idle, the serve loop degenerates into a pure Python
            # busy-loop that would otherwise starve the copies
            self._mig_inflight[0][1].join(timeout=0.002)
        return not self._mig_inflight and not eng.doomed_active_slots()

    def _cancel_one(self, job) -> None:
        self.server.engine.cancel_migration(job)

    def _cancel_migrations(self):
        """Abort barrier for in-flight migrations: cancel-or-join every
        copy session FIRST (no worker may touch the cache afterwards),
        then unwind tickets/slots — tables were never flipped, so the
        paused sequences simply resume where they were."""
        for job, sess in self._mig_inflight:
            sess.cancel()
            self._cancel_one(job)
        self._mig_inflight = []

    def abort(self):
        assert self.phase in (ScalePhase.STAGING, ScalePhase.COMPILING,
                              ScalePhase.MIGRATING, ScalePhase.DRAINING)
        self._cancel_migrations()
        self.server.hmm.abort()
        if self._down:
            # re-open the slots we stopped admitting into in __init__
            self.server.engine.admit_limit = None
        self.server._staged_cfg = None
        self.server._active_task = None
        self.phase = ScalePhase.ABORTED


class UnparkTask:
    """Resumable cold start from the pinned-host tier (driver.ScalingTask).

    The scale-from-zero twin of ``EngineScalingTask``: ``begin_unpark``
    opened an HMM staging session that streams the whole parked snapshot
    back to devices.  With ``staging="overlap"`` the first ``advance``
    runs the IMM AOT compile on the calling thread *while* the
    ``TransferEngine`` moves the snapshot (the same STAGING ∥ COMPILING
    discipline as a scale event — the H2D window hides the compile);
    serial mode streams one unit per ``advance`` then compiles.
    COMMITTING allocates a fresh KV cache/block pool and binds the
    engine; the first post-commit ``tick()`` serves.  There is no
    MIGRATING/DRAINING arm — a parked model has no live sequences by
    construction.  Every phase transition emits an ``unpark.<PHASE>``
    span on the scale lane, so park→unpark shows up on the same timeline
    as ordinary scale events.
    """

    def __init__(self, server: "ElasticServer", target: ElasticConfig):
        assert server.hmm.parked, "unpark requires a parked server"
        self.server = server
        self.target = target
        self.phase = ScalePhase.STAGING
        self.staging_mode = server.hmm.staging_mode
        self.increments_total = server.hmm.begin_unpark(target) + 1
        self.increments_done = 0
        self.stats: TransferStats = server.hmm._stage_stats
        self.stage_stats: Optional[TransferStats] = None
        self.event: Optional[ScaleEvent] = None
        self.stall_s = 0.0
        self._compile_hit: Optional[bool] = None
        server._active_task = self

    @property
    def phase(self) -> ScalePhase:
        return self._phase

    @phase.setter
    def phase(self, new: ScalePhase) -> None:
        tr = obs.get_tracer()
        now = tr.now()
        old = getattr(self, "_phase", None)
        self._phase = new
        if old is not None and old is not new:
            tr.complete(f"unpark.{old.name}", self._phase_t0, now,
                        cat="scale", tid="scale",
                        args={"target": self.target.describe(),
                              "next": new.name})
        _phase_annotation(self, old, new, "unpark")
        self._phase_t0 = now

    @property
    def done(self) -> bool:
        return self.phase.terminal

    def _unwind_failed(self):
        """A staging step raised: abort the HMM session.  The parked
        snapshot itself survives (``abort`` leaves ``_parked`` intact), so
        a later ``start_unpark`` can retry the cold start."""
        self.server.hmm.abort()
        self.server._active_task = None
        self.phase = ScalePhase.ABORTED

    def advance(self, now: float) -> ScalePhase:
        ph = self.phase
        if ph is ScalePhase.STAGING:
            t0 = time.perf_counter()
            try:
                if self.staging_mode == "overlap":
                    if self._compile_hit is None:
                        # AOT compile on the calling thread while the
                        # TransferEngine streams the snapshot; the explicit
                        # span is the trace-level witness that the unpark
                        # H2D window hid the compile
                        tr = obs.get_tracer()
                        c0 = tr.now()
                        self._compile_hit = self.server.imm.has(self.target)
                        self.server.imm.preinitialize(self.target)
                        tr.complete("unpark.compile", c0, tr.now(),
                                    cat="scale", tid="scale",
                                    args={"hit": self._compile_hit,
                                          "target": self.target.describe()})
                    if self.server.hmm.poll_staging():
                        self.increments_done = self.increments_total
                        self.stage_stats = dataclasses.replace(self.stats)
                        self.phase = ScalePhase.COMMITTING
                    else:
                        self.increments_done = (
                            self.increments_total - 1
                            - self.server.hmm.staging_remaining)
                else:
                    more = self.server.hmm.stage_increment()
                    self.increments_done += 1
                    if not more:
                        self.stage_stats = dataclasses.replace(self.stats)
                        self.phase = ScalePhase.COMPILING
            except BaseException:
                self._unwind_failed()
                raise
            self.stall_s += time.perf_counter() - t0
        elif ph is ScalePhase.COMPILING:
            t0 = time.perf_counter()
            self.increments_done += 1
            try:
                self._compile_hit = self.server.imm.has(self.target)
                self.server.imm.preinitialize(self.target)
            except BaseException:
                self._unwind_failed()
                raise
            self.phase = ScalePhase.COMMITTING
            self.stall_s += time.perf_counter() - t0
        elif ph is ScalePhase.COMMITTING:
            self.server._unpark_switchover(self)
            self.phase = ScalePhase.DONE
            self.server._active_task = None
        return self.phase

    def abort(self):
        assert self.phase in (ScalePhase.STAGING, ScalePhase.COMPILING)
        self._unwind_failed()


@dataclasses.dataclass
class RebalanceEvent:
    """One completed (or aborted) rebalance pass (DESIGN.md §10)."""
    t: float
    actions: int
    replicated: int = 0
    demoted: int = 0
    dropped: int = 0
    promoted: int = 0
    stats: Optional[TransferStats] = None
    aborted: bool = False


class RebalanceTask:
    """Resumable background expert rebalance (DESIGN.md §10).

    Same two-phase discipline as ``EngineScalingTask`` but much smaller:
    STAGING (replica/demotion rows stream on the HMM's background
    ``TransferEngine`` while tick() keeps serving) -> COMMITTING (pool
    banks gain the replica rows, the pooled index tables are swapped in
    place, the host tier absorbs demoted rows) -> DONE.  ``abort()``
    at any point before commit frees every staged page and leaves the
    serving layout untouched — tick() is legal between every ``advance``.

    Unlike a scale event a rebalance never pauses admission: the serving
    assignment only changes at commit, and commit is atomic with respect
    to the single-threaded serve loop."""

    def __init__(self, server: "ElasticServer", actions: List,
                 load=None):
        self.server = server
        self.actions = list(actions)
        self.event: Optional[RebalanceEvent] = None
        self.stats: Optional[TransferStats] = None
        self._load = load
        self.phase = ScalePhase.STAGING
        try:
            self.ops_total = server.hmm.begin_rebalance(actions, load=load)
        except BaseException:
            self.phase = ScalePhase.ABORTED
            raise
        server._rebalance_task = self

    @property
    def phase(self) -> ScalePhase:
        return self._phase

    @phase.setter
    def phase(self, new: ScalePhase) -> None:
        """Phase transitions emit ``rebalance.<PHASE>`` spans on their own
        trace lane, parallel to the scale lane's ``scale.<PHASE>``."""
        tr = obs.get_tracer()
        now = tr.now()
        old = getattr(self, "_phase", None)
        self._phase = new
        if old is not None and old is not new:
            tr.complete(f"rebalance.{old.name}", self._phase_t0, now,
                        cat="rebalance", tid="rebalance",
                        args={"actions": len(self.actions),
                              "next": new.name})
        _phase_annotation(self, old, new, "rebalance")
        self._phase_t0 = now

    @property
    def done(self) -> bool:
        return self.phase.terminal

    def advance(self, now: float) -> ScalePhase:
        ph = self.phase
        if ph is ScalePhase.STAGING:
            try:
                if self.server.hmm.poll_rebalance():
                    self.phase = ScalePhase.COMMITTING
            except BaseException:
                # poll_rebalance already aborted the HMM session on a
                # failed op; just release the task slot
                self.server._rebalance_task = None
                self.phase = ScalePhase.ABORTED
                raise
        elif ph is ScalePhase.COMMITTING:
            try:
                self.stats = self.server.hmm.commit_rebalance(
                    load=self._load)
            except BaseException:
                self.server._rebalance_task = None
                self.phase = ScalePhase.ABORTED
                raise
            # the histogram described the OLD placement — restart it so
            # the next policy pass sees post-rebalance traffic only
            # (same staleness fix as scale-event switchover)
            self.server.engine.reset_routing_stats()
            self.event = self._record(now)
            self.server._rebalance_task = None
            self.phase = ScalePhase.DONE
        return self.phase

    def _record(self, now: float) -> RebalanceEvent:
        kinds = [a[0] for a in self.actions]
        ev = RebalanceEvent(t=now, actions=len(self.actions),
                            replicated=kinds.count("replicate"),
                            demoted=kinds.count("demote"),
                            dropped=kinds.count("drop_replica"),
                            promoted=kinds.count("promote"),
                            stats=self.stats)
        self.server.rebalance_events.append(ev)
        return ev

    def abort(self):
        assert self.phase in (ScalePhase.STAGING, ScalePhase.COMMITTING)
        self.server.hmm.abort_rebalance()
        self.server._rebalance_task = None
        self.server.rebalance_events.append(
            RebalanceEvent(t=time.time(), actions=len(self.actions),
                           aborted=True))
        self.phase = ScalePhase.ABORTED


class ElasticServer:
    def __init__(self, mcfg: ModelConfig, *, tp: int, batch_per_replica: int,
                 max_len: int, prefill_buckets=(64,), all_devices=None,
                 policy: Optional[ScalingPolicy] = None, seed: int = 0,
                 kv_mode: str = "dense", kv_block_size: int = 16,
                 kv_blocks_per_replica: Optional[int] = None,
                 expert_mode: str = "dense",
                 expert_pool_pages: Optional[int] = None,
                 staging: str = "serial", transfer_workers: int = 4,
                 scaledown: str = "migrate",
                 prefill_chunk: int = 0,
                 prefill_budget: Optional[int] = None,
                 routing_sample_every: int = 0,
                 rebalance: Optional[RebalancePolicy] = None,
                 expert_slot_slack: Optional[int] = None,
                 expert_host_pages: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 expert_dtype: Optional[str] = None,
                 imm_cache=None):
        self.mcfg = mcfg
        self.kv_mode = kv_mode
        # quantized storage (ISSUE 9): 'int8' stores the paged KV pool /
        # pooled expert pages as int8 with f32 scale sidecars (HMM owns the
        # layout; kernels fuse the dequant).  The driver's cost projections
        # adopt these through the ``kv_dtype``/``expert_dtype`` attributes —
        # halved KV-migration and expert P2P/H2D bytes show up in plan_cost.
        self.kv_dtype = kv_dtype
        self.expert_dtype = expert_dtype
        # continuous batching: prefill_chunk > 0 splits prompt processing
        # into fixed-size token chunks interleaved with decode ticks under
        # a per-tick budget (serving/scheduler.py); 0 keeps the monolithic
        # prefill-at-admission path
        self.prefill_chunk = prefill_chunk
        # scale-down policy: 'migrate' (paged KV only — live sequences'
        # blocks device-copy onto survivor partitions, devices release in
        # seconds) or 'drain' (evicted slots run to completion; latency
        # bounded by the longest in-flight sequence).  The dense layout has
        # no block indirection to rewrite, so it always drains.
        assert scaledown in ("migrate", "drain")
        self.scaledown_mode = scaledown if kv_mode == "paged" else "drain"
        # 'pooled': expert weights live as page pools + tables, so an EP
        # scale event migrates only the min-move page set and commit only
        # rewrites tables (DESIGN.md §2); the driver's cost projections
        # adopt this through the ``expert_mode`` attribute
        self.expert_mode = expert_mode
        # 'overlap': staging transfers run on the HMM's background
        # TransferEngine while tick() keeps serving; the driver's cost
        # projections adopt this through the ``staging_mode`` attribute
        self.staging_mode = staging
        # skew-aware rebalancing (DESIGN.md §10): a RebalancePolicy turns
        # routing histograms into replicate/demote actions that tick()
        # drives through a background RebalanceTask.  Replication needs
        # spare compiled table width, so enabling it defaults the slot
        # slack to 1 (each rank can serve one extra expert copy); 0 keeps
        # the legacy byte-identical table shapes.
        self.rebalance_policy = rebalance
        if expert_slot_slack is None:
            expert_slot_slack = 1 if rebalance is not None else 0
        self.hmm = HMM(mcfg, tp, batch_per_replica=batch_per_replica,
                       max_len=max_len, all_devices=all_devices, seed=seed,
                       kv_mode=kv_mode, kv_block_size=kv_block_size,
                       kv_blocks_per_replica=kv_blocks_per_replica,
                       expert_mode=expert_mode,
                       expert_pool_pages=expert_pool_pages,
                       staging=staging, transfer_workers=transfer_workers,
                       expert_slot_slack=expert_slot_slack,
                       expert_host_pages=expert_host_pages,
                       kv_dtype=kv_dtype, expert_dtype=expert_dtype)
        # routing telemetry: every Nth decode tick runs the counts-emitting
        # executable and accumulates per-(layer, expert) histograms
        # (models/moe.py; exposed via routing_stats()).  0 disables — no
        # extra executable is compiled, the decode path is untouched.
        self.routing_sample_every = routing_sample_every
        # ``imm_cache``: an OrderedDict shared across a fleet's servers so
        # the standby-executable LRU is bounded once globally (IMM keys
        # carry the full model identity, so entries can never collide)
        self.imm = IMM(mcfg, self.hmm, batch_per_replica=batch_per_replica,
                       max_len=max_len, prefill_buckets=prefill_buckets,
                       prefill_chunk=prefill_chunk,
                       collect_routing=routing_sample_every > 0,
                       shared_cache=imm_cache)
        self.engine = InferenceEngine(mcfg, batch_per_replica=batch_per_replica,
                                      max_len=max_len,
                                      prefill_bucket=min(prefill_buckets),
                                      prefill_chunk=prefill_chunk,
                                      prefill_budget=prefill_budget,
                                      routing_sample_every=routing_sample_every)
        self.estimator = LoadEstimator(policy) if policy else None
        self.queue: List[Request] = []
        self.requests: Dict[int, Request] = {}
        self.events: List[ScaleEvent] = []
        self.rebalance_events: List[RebalanceEvent] = []
        self._staged_cfg: Optional[ElasticConfig] = None
        self._active_task: Optional[EngineScalingTask] = None
        self._rebalance_task: Optional[RebalanceTask] = None

    # ------------------------------------------------------------ lifecycle
    def boot(self, cfg: ElasticConfig):
        self.hmm.boot(cfg)
        inst, params, cache, _ = self.imm.activate(cfg)
        self.hmm.cache = None  # ownership moves to the engine (donated steps)
        self.engine.bind(cfg, inst.mesh, params, cache, inst.compiled,
                         kv=self.hmm.kv_blocks)

    def preinitialize(self, cfg: ElasticConfig):
        """Warm the IMM cache for an anticipated configuration."""
        self.imm.preinitialize(cfg)

    def scale_to(self, new_cfg: ElasticConfig) -> ScaleEvent:
        """Stage + switchover.  The engine remains serveable between the two
        phases; tests interleave tick() calls to prove zero downtime."""
        ev = self.stage_scale(new_cfg)
        self.switchover()
        return ev

    def stage_scale(self, new_cfg: ElasticConfig) -> ScaleEvent:
        """Monolithic staging (all increments back-to-back).  The
        incremental path is ``start_scale`` + ``task.advance``; both funnel
        into the same ``_record_stage`` bookkeeping."""
        self._preempt_rebalance()
        t0 = time.perf_counter()
        self.hmm.scale(new_cfg)                  # weights only; serving free
        return self._record_stage(new_cfg, time.perf_counter() - t0)

    def _record_stage(self, new_cfg: ElasticConfig, stage_s: float
                      ) -> ScaleEvent:
        hit = self.imm.has(new_cfg)
        t0 = time.perf_counter()
        self.imm.preinitialize(new_cfg)          # no-op if pre-initialized
        stage_s += time.perf_counter() - t0      # cold compile counts as stage
        self._staged_cfg = new_cfg
        if new_cfg.ndev < self.engine.cfg.ndev:
            # scale-down: stop admitting into slots that will be evicted
            self.engine.admit_limit = new_cfg.dp * self.engine.batch_per_replica
        ev = ScaleEvent(t=time.time(),
                        src=self.hmm.active_cfg.describe(),
                        dst=new_cfg.describe(), stats=self.hmm.last_stats,
                        compile_hit=hit,
                        stage_s=stage_s, switch_s=0.0,
                        # blocking callers stall for the whole stage; the
                        # incremental task overwrites with its measured poll
                        # time (near-zero when overlapped)
                        stall_s=stage_s, staging=self.staging_mode,
                        stage_wall_s=self.hmm.last_stats.wall_s)
        self.events.append(ev)
        return ev

    def switchover(self):
        assert self._staged_cfg is not None
        t0 = time.perf_counter()
        new_cfg = self._staged_cfg
        self.hmm.commit(live_cache=self.engine.cache)
        inst, params, cache, hit = self.imm.activate(new_cfg)
        self.hmm.cache = None
        self.engine.bind(new_cfg, inst.mesh, params, cache, inst.compiled,
                         kv=self.hmm.kv_blocks)
        # the routing histogram described the OLD placement; carrying it
        # across the commit would bias the first post-scale rebalance /
        # autoscale decisions toward experts that may no longer be hot
        # (or may now live elsewhere), so restart accumulation here
        self.engine.reset_routing_stats()
        self.engine.admit_limit = None
        self._staged_cfg = None
        if self.events:
            self.events[-1].switch_s = time.perf_counter() - t0
            self.events[-1].compile_hit = hit

    # -------------------------------------------------------- scale-to-zero
    @property
    def parked(self) -> bool:
        return self.hmm.parked

    def park(self) -> TransferStats:
        """Scale to ZERO devices (DESIGN.md §12): snapshot every weight
        bank into the pinned-host tier, unbind the engine and drop all
        device state.  Legal only when fully idle — empty queue, no active
        sequences, no scale/rebalance in flight — so parking never kills a
        request.  ``submit`` stays legal while parked (requests queue); the
        fleet driver answers the queue with ``start_unpark``."""
        assert self._active_task is None or self._active_task.done, \
            "cannot park during a scale event"
        self._preempt_rebalance()
        assert not self.queue and self.engine.active_count() == 0, \
            "park requires a drained server (queue empty, no live slots)"
        stats = self.hmm.park()
        # the engine's old handles would pin the freed device buffers
        self.engine.unbind()
        self._staged_cfg = None
        return stats

    def start_unpark(self, target: ElasticConfig) -> UnparkTask:
        """Open a resumable cold start from the pinned-host tier (the
        scale-from-zero twin of ``start_scale``); the driver advances it
        once per tick until DONE, after which ``tick()`` serves again."""
        return UnparkTask(self, target)

    def _unpark_switchover(self, task: UnparkTask):
        """Commit tail of an unpark: adopt the streamed weights, fresh KV,
        bind the engine — the ``switchover`` analogue for cold starts."""
        t0 = time.perf_counter()
        target = task.target
        self.hmm.commit()
        inst, params, cache, hit = self.imm.activate(target)
        self.hmm.cache = None
        self.engine.bind(target, inst.mesh, params, cache, inst.compiled,
                         kv=self.hmm.kv_blocks)
        self.engine.reset_routing_stats()
        self.engine.admit_limit = None
        ev = ScaleEvent(t=time.time(), src="parked", dst=target.describe(),
                        stats=self.hmm.last_stats,
                        compile_hit=(task._compile_hit
                                     if task._compile_hit is not None
                                     else hit),
                        stage_s=task.stats.wall_s,
                        switch_s=time.perf_counter() - t0,
                        stall_s=task.stall_s, staging=self.staging_mode,
                        stage_wall_s=(task.stage_stats.wall_s
                                      if task.stage_stats else 0.0))
        self.events.append(ev)
        task.event = ev

    # -------------------------------------------------------------- serving
    def submit(self, req: Request):
        kv = self.hmm.kv_blocks
        if kv is not None:
            # fail fast on a request no partition can EVER hold (its final
            # footprint is prompt + output tokens): admission is FIFO
            # head-of-line, so letting it queue would stall serving forever
            need = kv.blocks_needed(req.prompt_len + req.output_len)
            if need > kv.blocks_per_partition:
                raise ValueError(
                    f"request {req.rid} needs {need} KV blocks at completion"
                    f" but a partition holds {kv.blocks_per_partition}")
        self.requests[req.rid] = req
        self.queue.append(req)

    def tick(self, now: float) -> List[int]:
        """One engine tick: admit queued requests into free slots, then one
        decode step.  Returns rids finished this tick.

        While a ScalingTask is in flight the shared gating policy applies —
        the SAME ``admission_during_scale`` the simulator uses — so elastic
        transitions pause *new* admissions until switchover (paper §C)
        while in-flight decodes continue.

        Paged KV: admission is additionally gated by free blocks in the
        target slot's partition (FIFO: the head request tries every free
        slot before admission stalls), and sequences preempted under pool
        pressure re-enter at the *front* of the queue.

        Spans: ``srv.admit`` (admission), ``srv.step`` (the engine's
        prefill chunks and decode step, serving/engine.py) and, while a
        rebalance task or policy exists, ``srv.rebalance``."""
        if self.parked:
            # zero devices: nothing serves, the queue simply accrues until
            # the driver cold-starts us (a tick is legal, not an error —
            # fleet loops tick every backend uniformly)
            return []
        tr = obs.get_tracer()
        finished = []
        with tr.span("srv.admit", cat="serve") as span:
            admitted = self._admit(now)
            for rid in self.engine.drain_finished_at_admission():
                req = self.requests[rid]
                req.finish_s = now
                finished.append(rid)
                tr.instant("req.finish", cat="req", args={"rid": rid})
                if self.estimator:
                    self.estimator.record(req)
            span.set_metadata(admitted=admitted, queue=len(self.queue))
        for rid, tok, fin in self.engine.decode_tick():
            req = self.requests[rid]
            if req.first_token_s is None:
                # chunked prefill: the final chunk's token is the TTFT mark
                req.first_token_s = now
                req.token_times = [now]
                tr.instant("req.first_token", cat="req", args={"rid": rid})
            elif req.token_times is not None:
                req.token_times.append(now)
            if fin:
                req.finish_s = now
                finished.append(rid)
                tr.instant("req.finish", cat="req", args={"rid": rid})
                if self.estimator:
                    self.estimator.record(req)
        preempted = self.engine.drain_preempted()
        if preempted:
            self.queue[:0] = [self.requests[r] for r in preempted]
        # background skew rebalance (DESIGN.md §10): advance an in-flight
        # session or let the policy open one — transfers run on the HMM's
        # TransferEngine so this never blocks the tick
        task = self._rebalance_task
        if (task is not None and not task.done) \
                or self.rebalance_policy is not None:
            with tr.span("srv.rebalance", cat="serve"):
                self._drive_rebalance(now)
        return finished

    def _admit(self, now: float) -> int:
        """Admit queued requests into free slots (FIFO, head-of-line
        blocking); returns how many were admitted."""
        tr = obs.get_tracer()
        admitting = True
        if self._active_task is not None \
                and not self._active_task.phase.terminal:
            _, admitting = admission_during_scale("elastic")
        free = self.engine.free_slots()
        n = 0
        while admitting and self.queue and free:
            req = self.queue[0]
            # prefix-cache-aware placement: try slots whose partition
            # already holds the longest registered prefix of this prompt
            slot = next((s for s in
                         self.engine.preferred_slots(req, req.prompt, free)
                         if self.engine.can_admit(req, req.prompt, s)), None)
            if slot is None:
                break                   # head-of-line blocks; no skipping
            free.remove(slot)
            self.queue.pop(0)
            n += 1
            tr.instant("req.admit", cat="req",
                       args={"rid": req.rid, "slot": slot})
            first = self.engine.start_request(req, req.prompt, slot)
            if first is None:
                continue    # chunked: first token arrives from decode_tick
            if req.first_token_s is None:
                req.first_token_s = now
                req.token_times = [now]
                tr.instant("req.first_token", cat="req",
                           args={"rid": req.rid})
            elif req.token_times is not None:   # preemption resume
                req.token_times.append(now)
        return n

    # ------------------------------------------------------------ decisions
    def autoscale_decision(self, now: float) -> Optional[str]:
        if not self.estimator:
            return None
        return self.estimator.decide(now, len(self.queue), self.utilization())

    # --------------------------------------------- ServingBackend protocol
    def step(self, now: float) -> List[Request]:
        """One driver quantum == one engine tick; returns finished Requests."""
        return [self.requests[rid] for rid in self.tick(now)]

    def queue_depth(self) -> int:
        return len(self.queue)

    def utilization(self) -> float:
        return 0.0 if self.parked else self.engine.utilization()

    def kv_stats(self):
        """Block-pool stats (None in dense mode); serving/metrics.py."""
        return self.engine.kv_stats()

    def routing_stats(self) -> Optional[dict]:
        """Per-expert routing histogram accumulated from sampled decode
        ticks (None when sampling is off or no sample has landed yet);
        serving/metrics.py, DESIGN.md §9."""
        return self.engine.routing_stats()

    def scaling_summary(self) -> Optional[dict]:
        """Aggregate staging-overlap metrics over completed scale events
        (None before the first one); consumed by ``metrics.summarize``:

        * ``decode_stall_s`` — total serve-loop time blocked on staging
          work across all events,
        * ``overlap_efficiency`` — mean Σ-op-time / staging-wall-clock
          (>1 = transfers genuinely overlapped serving)."""
        if not self.events:
            return None
        effs = [ev.stats.op_s / ev.stage_wall_s for ev in self.events
                if ev.stage_wall_s > 0 and ev.stats.op_s > 0]
        return {"staging_mode": self.staging_mode,
                "scaledown_mode": self.scaledown_mode,
                "decode_stall_s": sum(ev.stall_s for ev in self.events),
                "overlap_efficiency":
                    sum(effs) / len(effs) if effs else None,
                "migrated_blocks": sum(ev.migrated_blocks
                                       for ev in self.events),
                "migration_bytes": sum(ev.migration_bytes
                                       for ev in self.events)}

    def current_config(self) -> Optional[ElasticConfig]:
        """Active configuration, or None while parked (zero devices)."""
        return self.hmm.active_cfg

    def start_scale(self, target: ElasticConfig) -> EngineScalingTask:
        """Open a resumable scaling task (the driver advances it one
        increment per tick; ``scale_to`` remains the blocking equivalent)."""
        return EngineScalingTask(self, target)

    # ---------------------------------------------------- expert rebalance
    def _preempt_rebalance(self) -> None:
        """Abort an in-flight rebalance (scale events take priority; the
        page table forbids a remap and a rebalance being staged at once)."""
        task = self._rebalance_task
        if task is not None and not task.done:
            task.abort()

    def start_rebalance(self, actions: List, load=None) -> RebalanceTask:
        """Open a resumable rebalance session over explicit
        ``stage_rebalance`` actions; tick() advances it to completion."""
        assert self._rebalance_task is None or self._rebalance_task.done
        return RebalanceTask(self, actions, load=load)

    def maybe_rebalance(self, now: float) -> Optional[RebalanceTask]:
        """One policy pass: feed the routing histogram to the
        ``RebalancePolicy`` and open a ``RebalanceTask`` if it emits
        actions.  A pool-exhausted staging attempt is skipped, not fatal —
        the policy retries after its cooldown with fresh stats."""
        if self.rebalance_policy is None or self.expert_mode != "pooled":
            return None
        stats = self.engine.routing_stats()
        cfg = self.hmm.active_cfg
        elm = (math.ceil(self.mcfg.num_experts / cfg.ndev)
               + self.hmm.expert_slot_slack)
        actions = self.rebalance_policy.decide(
            stats, self.hmm.page_table, cfg, now, slots_per_rank=elm)
        if not actions:
            return None
        try:
            return self.start_rebalance(actions, load=stats["counts"])
        except MemoryError as err:
            obs.get_tracer().instant(
                "rebalance.skip", cat="rebalance",
                args={"reason": str(err)})
            return None

    def _drive_rebalance(self, now: float) -> None:
        """Per-tick rebalance pump: advance the in-flight task, else ask
        the policy — never while a scale event is in flight."""
        task = self._rebalance_task
        if task is not None and not task.done:
            task.advance(now)
            return
        if self.rebalance_policy is None:
            return
        if self._active_task is not None \
                and not self._active_task.phase.terminal:
            return
        self.maybe_rebalance(now)

    def rebalance_summary(self) -> Optional[dict]:
        """Aggregate rebalance telemetry (None before the first pass);
        consumed by ``metrics.summarize`` and ``benchmarks/expert_skew``."""
        if not self.rebalance_events:
            return None
        done = [ev for ev in self.rebalance_events if not ev.aborted]
        return {"passes": len(done),
                "aborted": len(self.rebalance_events) - len(done),
                "replicated": sum(ev.replicated for ev in done),
                "demoted": sum(ev.demoted for ev in done),
                "dropped": sum(ev.dropped for ev in done),
                "promoted": sum(ev.promoted for ev in done),
                "replica_bytes": sum(ev.stats.expert_replica_bytes
                                     for ev in done if ev.stats),
                "d2h_bytes": sum(ev.stats.expert_d2h_bytes
                                 for ev in done if ev.stats),
                "host_tier_bytes": self.hmm.host_tier_bytes()}

    def prewarm(self, target: ElasticConfig) -> None:
        self.preinitialize(target)

    def capacity(self, cfg: ElasticConfig) -> int:
        return cfg.dp * self.engine.batch_per_replica
