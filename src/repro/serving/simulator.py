"""Discrete-event cluster simulator for paper-scale serving experiments.

This container has no NPUs, so Figs 9/10 and Table 2 (SLO dynamics /
compliance / throughput windows at CloudMatrix scale) are reproduced with a
calibrated discrete-event model.  What is *measured* vs *modelled*:

* scaling latency / downtime / peak memory — from the real planner
  (scaling_plan) + cost model (costmodel), byte-exact;
* per-step serving time — a roofline-flavoured performance model
  (weights-read memory bound for decode, compute bound for prefill) with a
  single system-efficiency fudge calibrated once against Table 2's
  "6 rps before scaling on 6 NPUs" for DeepSeek-V2-Lite and reused
  everywhere;
* engine semantics (continuous batching, drain-free switchover, admission
  pause during scaling) — *shared* code with the real JAX engine: the
  admission gate during a transition is ``driver.admission_during_scale``
  (the same function the ClusterDriver applies to ``ElasticServer``), and
  scaling runs as a ``SimScalingTask`` implementing the same
  ``ScalingTask`` phases the engine path uses, so a ``ClusterDriver`` loop
  runs unchanged over either backend.

Measured vs modelled (the README table is generated from this docstring):

| quantity                         | source                                  |
|----------------------------------|-----------------------------------------|
| scaling latency / downtime       | planner bytes x cost model (byte-exact) |
| peak memory during transition    | planner placement (byte-exact)          |
| per-step decode/prefill time     | roofline model, one calibrated sys_eff  |
| engine/scaling semantics         | shared code with serving/engine.py      |
| KV admission (dense vs paged)    | same policies as the engine: full-length|
|                                  | reservation vs block occupancy with     |
|                                  | preemption (kv_blocks.py, DESIGN.md §7) |
"""
from __future__ import annotations

import dataclasses
import heapq
import math
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.costmodel import DEFAULT_HW, HardwareModel, ScalingCost
from repro.core.expert_pages import ExpertPageTable
from repro.core.topology import ElasticConfig, kv_cache_bytes
from repro.serving.driver import (ScalePhase, admission_during_scale,
                                  projected_migration_blocks,
                                  transition_cost, unpark_transition_cost)
from repro.serving.kv_blocks import blocks_for as kv_blocks_for
from repro.serving.metrics import latency_percentiles
from repro.serving.rebalance import RebalancePolicy
from repro.serving.scheduler import PrefillJob, TokenBudgetScheduler
from repro.serving.workload import Request, merge_arrivals


@dataclasses.dataclass
class PerfModel:
    mcfg: ModelConfig
    hbm_bw: float = 1.6e12          # Ascend 910C-class HBM bandwidth
    chip_flops: float = 350e12      # bf16
    sys_eff: float = 0.4            # end-to-end efficiency (calibrated once:
                                    # ~9 rps sustainable for DeepSeek-V2-Lite
                                    # on 6 NPUs with 2000/500-750 workload)
    step_overhead_s: float = 0.004
    max_batch_per_dev: int = 12
    kv_seq_len: int = 4096
    kv_block_size: int = 256        # paged mode: tokens per KV block
    kv_dtype: Optional[str] = None  # 'int8': quantized KV pool byte sizing

    def __post_init__(self):
        bpe = 2
        self._weight_bytes = self.mcfg.param_count() * bpe
        self._active_flops_per_tok = 2 * self.mcfg.param_count(active_only=True)
        self._kv_bytes_per_seq = kv_cache_bytes(self.mcfg, 1, self.kv_seq_len,
                                                kv_dtype=self.kv_dtype)
        self._kv_block_bytes = kv_cache_bytes(self.mcfg, 1, self.kv_block_size,
                                              kv_dtype=self.kv_dtype)

    def decode_step_s(self, batch: int, ndev: int) -> float:
        """Memory-bound: every step streams the (sharded) weights."""
        t_mem = self._weight_bytes / (ndev * self.hbm_bw * self.sys_eff)
        t_comp = (batch * self._active_flops_per_tok
                  / (ndev * self.chip_flops * self.sys_eff))
        return self.step_overhead_s + max(t_mem, t_comp)

    def prefill_s(self, prompt: int, ndev: int) -> float:
        return self.step_overhead_s + (
            prompt * self._active_flops_per_tok
            / (ndev * self.chip_flops * self.sys_eff * 4))  # prefill batches well

    def _free_kv_bytes(self, ndev: int, kv_frac: float) -> float:
        return (ndev * DEFAULT_HW.device_hbm * 0.9
                - self._weight_bytes) * kv_frac

    def max_batch(self, ndev: int, kv_frac: float = 1.0) -> int:
        """Dense admission: every sequence reserves a full ``kv_seq_len``
        row up front."""
        hbm_limit = int(self._free_kv_bytes(ndev, kv_frac)
                        / self._kv_bytes_per_seq)
        return max(1, min(hbm_limit, int(self.max_batch_per_dev * ndev
                                         * kv_frac)))

    def pool_blocks(self, ndev: int, kv_frac: float = 1.0) -> int:
        """Paged admission: the same KV budget carved into blocks
        (serving/kv_blocks.py) — a sequence only occupies blocks for the
        tokens it currently holds."""
        return max(1, int(self._free_kv_bytes(ndev, kv_frac)
                          // self._kv_block_bytes))

    def blocks_for(self, num_tokens: int) -> int:
        # the engine's exact admission granularity (kv_blocks.blocks_for)
        return kv_blocks_for(int(num_tokens), self.kv_block_size)


@dataclasses.dataclass
class SimRoutingModel:
    """Synthesized router telemetry for a Zipf-skewed expert workload.

    The roofline model has no router, so for rebalancer experiments the
    sim draws per-(layer, expert) token counts from a Zipf(``skew``)
    share, permuted per layer with a seeded RNG so layers disagree about
    *which* experts are hot (exactly the shape the real histograms show).
    ``stats()`` matches ``InferenceEngine.routing_stats()`` key-for-key,
    so the shared ``RebalancePolicy`` and ``metrics.summarize`` consume
    either backend's telemetry unchanged."""
    num_moe_layers: int
    num_experts: int
    skew: float = 1.2
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        ranks = np.arange(1, self.num_experts + 1,
                          dtype=np.float64) ** -self.skew
        share = ranks / ranks.sum()
        self._share = np.stack([share[rng.permutation(self.num_experts)]
                                for _ in range(self.num_moe_layers)])
        self._counts = np.zeros_like(self._share)
        self.samples = 0

    def observe(self, tokens: int) -> None:
        """Account one sampled decode tick routing ``tokens`` tokens."""
        if tokens <= 0:
            return
        self._counts += self._share * tokens
        self.samples += 1

    def stats(self) -> Optional[dict]:
        if self.samples == 0:
            return None
        tot = self._counts.sum(axis=1, keepdims=True)
        share = self._counts / np.maximum(tot, 1.0)
        mean = share.mean(axis=1)
        return {"samples": self.samples, "counts": self._counts.copy(),
                "top_expert_share": float(share.max(axis=1).mean()),
                "expert_cv": float((share.std(axis=1)
                                    / np.maximum(mean, 1e-12)).mean())}

    def reset(self) -> None:
        """Same contract as ``InferenceEngine.reset_routing_stats``."""
        self._counts[:] = 0.0
        self.samples = 0


@dataclasses.dataclass
class SimScaleEvent:
    t_command: float
    t_ready: float
    downtime_until: float
    old_ndev: int
    new_ndev: int
    cost: ScalingCost
    # zero-drain scale-down (scaledown="migrate", paged KV): live KV blocks
    # modelled as moving off doomed partitions (shared policy:
    # driver.projected_migration_blocks); 0 for scale-up / drain mode
    migrated_blocks: int = 0
    migration_bytes: int = 0
    # serving-latency snapshot at command time (finished requests so far;
    # NaN until the first finish): metrics.latency_percentiles
    ttft_p50: float = float("nan")
    ttft_p99: float = float("nan")
    itl_p50: float = float("nan")
    itl_p99: float = float("nan")


class SimScalingTask:
    """driver.ScalingTask over modelled time — already the poll semantics
    the protocol specifies: ``advance`` never performs work, it observes
    modelled time, stays in STAGING until the cost model's ``t_ready`` and
    then commits instantaneously.  The same object is advanced by a
    ClusterDriver (closed loop) or by the simulator itself (scripted
    ``command_scale`` benchmarks) — whichever observes ``t_ready`` first.

    ``stall_s`` / ``overlap_efficiency`` mirror the real engine task's
    completion metrics: the modelled decode stall (whole transfer time for
    serial staging, the HBM-contention share when overlapped) and the
    Σ-op-time / staging-window ratio from the cost breakdown."""

    def __init__(self, sim: "ServingSimulator", target: ElasticConfig,
                 event: SimScaleEvent):
        self.sim = sim
        self.target = target
        self.event = event
        self.phase = ScalePhase.STAGING
        # plan_cost zeroes decode_stall_s on downtime transitions (the
        # outage subsumes the stall), so no re-guarding here
        self.stall_s = event.cost.decode_stall_s
        # mirror the engine task's completion metrics (DriverEvent fill-in)
        self.migrated_blocks = event.migrated_blocks
        self.migration_bytes = event.migration_bytes

    @property
    def done(self) -> bool:
        return self.phase.terminal

    @property
    def overlap_efficiency(self) -> Optional[float]:
        op = self.event.cost.breakdown.get("op_s", 0.0)
        if not op:
            return None
        return op / max(self.event.cost.scale_time_s, 1e-9)

    def advance(self, now: float) -> ScalePhase:
        if self.phase is ScalePhase.STAGING and now >= self.event.t_ready:
            self.phase = ScalePhase.COMMITTING
            # sim-time span, explicit timestamps (tracer clock domain is
            # whatever the installed clock reads — see DESIGN.md §9)
            obs.get_tracer().complete(
                "scale.STAGING", self.event.t_command, self.event.t_ready,
                cat="scale", tid="sim-scale",
                args={"old_ndev": self.event.old_ndev,
                      "new_ndev": self.event.new_ndev})
        if self.phase is ScalePhase.COMMITTING:
            self.sim.ndev = self.event.new_ndev
            self.sim.extra_devices_during_scale = 0
            self.sim.scale = None
            if self.sim.expert_pages is not None \
                    and self.sim.strategy == "elastic":
                # track the placement the pooled engine would commit:
                # min-move remap keeps experts via ANY resident copy and
                # retires the losing replicas (expert_pages.commit)
                self.sim.expert_pages.stage_remap(self.target, min_move=True)
                self.sim.expert_pages.commit()
            if self.sim.routing is not None:
                # same staleness rule as ElasticServer.switchover: the
                # histogram described the old placement
                self.sim.routing.reset()
            self.phase = ScalePhase.DONE
            obs.get_tracer().instant(
                "scale.commit", cat="scale", t=now, tid="sim-scale",
                args={"new_ndev": self.event.new_ndev})
        return self.phase


class SimUnparkTask:
    """driver.ScalingTask for a modelled cold start from the pinned-host
    tier (scale-from-zero, DESIGN.md §12).  STAGING until the unpark cost
    model's ``t_ready`` — the whole-snapshot H2D window priced at
    ``hw.h2d_bw`` with the AOT compile hidden underneath (overlap mode) —
    then an instantaneous commit: devices return, a fresh expert placement
    is laid out, and admission resumes.  Mirrors the real ``UnparkTask``
    phase-for-phase so a fleet loop drives either backend unchanged."""

    def __init__(self, sim: "ServingSimulator", target: ElasticConfig,
                 event: SimScaleEvent):
        self.sim = sim
        self.target = target
        self.event = event
        self.phase = ScalePhase.STAGING
        self.stall_s = 0.0

    @property
    def done(self) -> bool:
        return self.phase.terminal

    def advance(self, now: float) -> ScalePhase:
        if self.phase is ScalePhase.STAGING and now >= self.event.t_ready:
            self.phase = ScalePhase.COMMITTING
            obs.get_tracer().complete(
                "unpark.STAGING", self.event.t_command, self.event.t_ready,
                cat="scale", tid="sim-scale",
                args={"new_ndev": self.event.new_ndev})
        if self.phase is ScalePhase.COMMITTING:
            sim = self.sim
            sim.ndev = self.event.new_ndev
            sim.parked = False
            sim.scale = None
            if sim.expert_pages is not None:
                # nothing survived the park on-device: fresh table, fresh
                # balanced placement at the cold-start width (the real HMM
                # initial_places the unpark table the same way)
                n_moe = sim.mcfg.num_layers - sim.mcfg.first_k_dense
                sim.expert_pages = ExpertPageTable(
                    n_moe, sim.mcfg.num_experts,
                    host_pool_pages=sim._expert_host_pages)
                sim.expert_pages.initial_place(sim.current_config())
            if sim.routing is not None:
                sim.routing.reset()
            self.phase = ScalePhase.DONE
            obs.get_tracer().instant(
                "unpark.commit", cat="scale", t=now, tid="sim-scale",
                args={"new_ndev": self.event.new_ndev})
        return self.phase


class ServingSimulator:
    """One logical serving instance with strategy-dependent scaling."""

    def __init__(self, mcfg: ModelConfig, tp: int, ndev: int, *,
                 strategy: str = "elastic", perf: Optional[PerfModel] = None,
                 hw: Optional[HardwareModel] = None, kv_seq_len: int = 4096,
                 preinit: bool = True, kv_mode: str = "dense",
                 pool_blocks: Optional[int] = None,
                 expert_mode: str = "dense", staging: str = "serial",
                 scaledown: str = "migrate",
                 prefill_chunk: Optional[int] = None,
                 prefill_budget: Optional[int] = None,
                 rebalance: Optional[RebalancePolicy] = None,
                 routing_skew: Optional[float] = None,
                 routing_seed: int = 0,
                 expert_slot_slack: Optional[int] = None,
                 expert_host_pages: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 expert_dtype: Optional[str] = None):
        self.mcfg = mcfg
        self.tp = tp
        self.ndev = ndev
        self.strategy = strategy
        # quantized pools (mirrors ElasticServer(kv_dtype/expert_dtype)):
        # KV and expert-page bytes are sized at the int8 storage width (plus
        # scale sidecars), so modelled admission capacity roughly doubles
        # and scale events move ~half the expert/KV bytes
        assert kv_dtype in (None, "int8") and expert_dtype in (None, "int8")
        self.kv_dtype = kv_dtype
        self.expert_dtype = expert_dtype
        self.perf = perf or PerfModel(mcfg, kv_seq_len=kv_seq_len,
                                      kv_dtype=kv_dtype)
        self.hw = hw or DEFAULT_HW
        # 'overlap' models the background TransferEngine (mirrors
        # ElasticServer(staging="overlap")): scale events are costed with
        # the overlap pipeline — warmup hidden under the transfer window,
        # decode stall reduced to the HBM-contention share (DESIGN.md §3)
        assert staging in ("serial", "overlap")
        self.staging_mode = staging
        # 'pooled' models the min-move vpage remap: elastic scale events are
        # costed with plan_elastic_paged via the shared transition_cost path
        # (mirrors ElasticServer(expert_mode="pooled"); DESIGN.md §2)
        assert expert_mode in ("dense", "pooled")
        self.expert_mode = expert_mode
        # KV admission: 'dense' reserves a full-length row per admitted
        # request (PerfModel.max_batch); 'paged' admits by block occupancy —
        # a request holds blocks for its *current* tokens, growing as it
        # decodes, and the youngest lowest-priority request is preempted
        # (re-queued, recomputed) when the pool overflows.  Mirrors the real
        # engine's kv_blocks-gated admission so the closed-loop driver sees
        # the same memory-pressure signal on both backends.
        assert kv_mode in ("dense", "paged")
        self.kv_mode = kv_mode
        # scale-down policy, mirroring ElasticServer(scaledown=...):
        # 'migrate' (default, paged only) costs scale-downs as live
        # KV-block migration bytes via the shared
        # projected_migration_blocks policy; 'drain' extends t_ready until
        # the doomed share of in-flight requests would have finished —
        # latency bounded by the longest evicted sequence, the behaviour
        # migration replaces.  Dense KV is coerced to 'drain' exactly like
        # the engine (no block indirection to migrate), so projection and
        # execution report — and cost — the same policy.
        assert scaledown in ("migrate", "drain")
        self.scaledown_mode = scaledown if kv_mode == "paged" else "drain"
        self._pool_blocks_override = pool_blocks
        self.preemptions = 0
        # note: baselines also run with a warm engine (pre-provisioned
        # instance); the '-PreInit' ablation isolates the cold-boot add-on
        self.preinit = preinit
        # colocated keeps a resident standby copy -> halved KV capacity and
        # degraded stability (paper §7.6: memory pressure)
        self.kv_frac = 0.5 if strategy == "colocated" else 1.0
        if strategy == "colocated":
            self.perf = dataclasses.replace(self.perf,
                                            sys_eff=self.perf.sys_eff * 0.6)
        # continuous batching (mirrors InferenceEngine.prefill_chunk):
        #   None -> legacy instant-prefill admission (bit-identical to the
        #           pre-chunking simulator; no token_times synthesized);
        #   0    -> monolithic prefill with decode-stall modelling: admitting
        #           a prompt stalls every running decode for the whole
        #           prefill_s(prompt_len) — the ITL spike chunking removes;
        #   > 0  -> chunked prefill through the SAME TokenBudgetScheduler
        #           the real engine runs (serving/scheduler.py), stalling
        #           decodes one token-budget chunk at a time.
        self.prefill_chunk = prefill_chunk
        self.scheduler = (TokenBudgetScheduler(prefill_chunk, prefill_budget)
                          if prefill_chunk else None)
        self._prefilling: List[PrefillJob] = []
        self._prefill_reqs: Dict[int, Request] = {}
        self._itl_base: Dict[int, float] = {}
        self._stall_gaps: Dict[int, List[float]] = {}
        self._pending: List[Request] = []
        self._pi = 0
        self.t = 0.0
        self.queue: List[Request] = []
        # (finish_est, rid, req, t_decode_start) — t_decode_start tracks the
        # *current* attempt (reset when a preempted request is re-admitted)
        self.running: List[Tuple[float, int, Request, float]] = []
        self.finished: List[Request] = []
        self.scale: Optional[SimScalingTask] = None
        self.events: List[SimScaleEvent] = []
        self.extra_devices_during_scale = 0
        # skew-aware expert rebalancing, sim side (DESIGN.md §10): the
        # SAME RebalancePolicy the engine runs decides over a synthesized
        # Zipf routing histogram and applies its actions to a sim-owned
        # ExpertPageTable (stage + commit in one quantum — the byte cost
        # of a rebalance pass is negligible at model scale), so allocator
        # behaviour (replica sets, host tier, pool conservation, min-move
        # over replicas at scale events) is testable with no devices.
        self.rebalance_policy = rebalance
        if rebalance is not None and routing_skew is None:
            routing_skew = 1.2      # rebalancing needs telemetry to read
        n_moe = mcfg.num_layers - mcfg.first_k_dense
        self.routing = (SimRoutingModel(n_moe, mcfg.num_experts,
                                        skew=routing_skew, seed=routing_seed)
                        if routing_skew is not None and mcfg.num_experts
                        else None)
        if expert_slot_slack is None:
            expert_slot_slack = 1 if rebalance is not None else 0
        self.expert_slot_slack = expert_slot_slack
        self.expert_pages: Optional[ExpertPageTable] = None
        if expert_mode == "pooled" and mcfg.num_experts:
            self.expert_pages = ExpertPageTable(
                n_moe, mcfg.num_experts,
                host_pool_pages=expert_host_pages)
            self.expert_pages.initial_place(self.current_config())
        self.rebalance_events: List[dict] = []
        self._expert_host_pages = expert_host_pages
        # scale-to-zero (DESIGN.md §12): parked = whole model lives in the
        # pinned-host tier, ndev == 0, queue accrues, nothing serves until
        # a SimUnparkTask commits.  park_events: {"t", "kind", ["wall_s"]}.
        self.parked = False
        self.park_events: List[dict] = []
        # one expert page across the three banks: bf16 (PerfModel's bpe) or
        # int8 + three per-page f32 scales when the pool is quantized
        ebpe = 1 if expert_dtype == "int8" else 2
        escale = 3 * 4 if expert_dtype == "int8" else 0
        self._expert_page_bytes = (3 * mcfg.d_model * mcfg.moe_d_ff * ebpe
                                   + escale)

    # ------------------------------------------------------------- scaling
    def start_scale(self, target: ElasticConfig) -> SimScalingTask:
        """Open a scaling task toward ``target`` (driver.ServingBackend).
        Byte counts come from the real planner; durations from the cost
        model.  The task commits when modelled time reaches ``t_ready``."""
        assert self.scale is None, "scaling already in flight"
        assert not self.parked, "parked: use start_unpark, not start_scale"
        old = ElasticConfig(self.ndev // self.tp, self.tp,
                            tuple(range(self.ndev)))
        if self.strategy in ("extravagant", "horizontal"):
            self.extra_devices_during_scale = target.ndev
        down = target.ndev < self.ndev
        mig_blocks = 0
        if down and self.kv_mode == "paged" \
                and self.scaledown_mode == "migrate":
            mig_blocks = projected_migration_blocks(
                self.used_blocks(), old.dp, target.dp)
        mig_bytes = mig_blocks * self.perf._kv_block_bytes
        cost = transition_cost(self.mcfg, self.tp, old, target,
                               strategy=self.strategy, hw=self.hw,
                               preinit=self.preinit,
                               kv_seq_len=self.perf.kv_seq_len,
                               expert_mode=self.expert_mode,
                               # cost from the sim's live placement: replica
                               # keeps are zero-copy, host-tier experts
                               # stream H2D instead of P2P (DESIGN.md §10)
                               page_table=self.expert_pages,
                               staging=self.staging_mode,
                               kv_migration_bytes=mig_bytes,
                               kv_dtype=self.kv_dtype,
                               expert_dtype=self.expert_dtype)
        t_ready = self.t + cost.scale_time_s
        if down and self.scaledown_mode == "drain" and self.running:
            # legacy drain: the doomed share of in-flight requests (the
            # youngest, mirroring eviction order) must run to completion
            # before their devices release — overlapping the staging window
            n_doomed = math.ceil(len(self.running)
                                 * (old.dp - target.dp) / old.dp)
            doomed = sorted(self.running, key=lambda e: -e[1])[:n_doomed]
            if doomed:
                # the doomed sequences' finishes are about to be shifted by
                # the modelled decode stall (below) — drain must wait for
                # the SHIFTED completion, or devices release early
                t_ready = max(t_ready,
                              max(f for f, _, _, _ in doomed)
                              + cost.decode_stall_s)
        event = SimScaleEvent(
            t_command=self.t, t_ready=t_ready,
            downtime_until=self.t + cost.downtime_s if cost.downtime_s else 0,
            old_ndev=self.ndev, new_ndev=target.ndev, cost=cost,
            migrated_blocks=mig_blocks, migration_bytes=mig_bytes,
            **latency_percentiles(self.finished))
        self.events.append(event)
        if cost.downtime_s:
            # in-flight requests are stalled for the whole outage (§3 L2)
            self.running = [(f + cost.scale_time_s, rid, r,
                             s + cost.scale_time_s)
                            for f, rid, r, s in self.running]
            heapq.heapify(self.running)
            if self.prefill_chunk is not None:
                for _, rid, _, _ in self.running:
                    self._stall_gaps.setdefault(rid, []).append(
                        cost.scale_time_s)
        elif cost.decode_stall_s:
            # decode stalls while staging contends for HBM/links: serial
            # staging blocks a serve-loop quantum per increment (the whole
            # transfer time); overlapped staging only the contention share.
            # Modelled as a finish-time shift of the in-flight requests.
            self._stall_running(cost.decode_stall_s)
        self.scale = SimScalingTask(self, target, event)
        return self.scale

    # -------------------------------------------------------- scale-to-zero
    def park(self) -> None:
        """Scale to ZERO devices: the model's snapshot moves to the
        pinned-host tier and every device releases.  Legal only when fully
        drained (no running/prefilling/queued requests) and no scale event
        is in flight — the same preconditions as ``ElasticServer.park``."""
        assert self.scale is None, "cannot park during a scale event"
        assert not self.parked, "already parked"
        assert not self.running and not self._prefilling and not self.queue, \
            "park requires a drained instance"
        self.parked = True
        self.ndev = 0
        self.park_events.append({"t": self.t, "kind": "park"})
        obs.get_tracer().instant("park", cat="scale", t=self.t,
                                 tid="sim-scale")

    def start_unpark(self, target: ElasticConfig) -> SimUnparkTask:
        """Open a modelled cold start toward ``target`` — the shared
        ``unpark_transition_cost`` pricing (whole snapshot H2D at
        ``h2d_bw``, fresh KV INIT, compile hidden under the transfer in
        overlap mode) sets ``t_ready``; until then ndev stays 0 and the
        queue accrues (the cold-start wall the fleet benchmark reports)."""
        assert self.parked, "not parked"
        assert self.scale is None
        cost = unpark_transition_cost(
            self.mcfg, self.tp, target, hw=self.hw, preinit=self.preinit,
            staging=self.staging_mode, kv_seq_len=self.perf.kv_seq_len,
            kv_dtype=self.kv_dtype, expert_dtype=self.expert_dtype)
        t_ready = self.t + cost.scale_time_s
        event = SimScaleEvent(
            t_command=self.t, t_ready=t_ready,
            downtime_until=self.t + cost.downtime_s if cost.downtime_s else 0,
            old_ndev=0, new_ndev=target.ndev, cost=cost,
            **latency_percentiles(self.finished))
        self.events.append(event)
        self.park_events.append({"t": self.t, "kind": "unpark",
                                 "wall_s": cost.scale_time_s})
        self.scale = SimUnparkTask(self, target, event)
        return self.scale

    def command_scale(self, new_ndev: int) -> SimScalingTask:
        """Scripted-benchmark entry point: scale to ``new_ndev`` devices
        (extravagant/horizontal get a disjoint device range)."""
        base = self.ndev if self.strategy in ("extravagant",
                                              "horizontal") else 0
        target = ElasticConfig(new_ndev // self.tp, self.tp,
                               tuple(range(base, base + new_ndev)))
        return self.start_scale(target)

    # -------------------------------------------------------------- engine
    def _serving_capacity(self) -> Tuple[int, bool]:
        """(effective ndev, admitting_new) given any in-flight scale.
        Gating policy is the shared ``driver.admission_during_scale`` — the
        exact code the real-engine driver applies."""
        if self.scale is not None:
            self.scale.advance(self.t)        # commits at/after t_ready
        if self.scale is None:
            return self.ndev, True
        mode, admit = admission_during_scale(self.strategy)
        return (0 if mode == "none" else self.ndev), admit

    # ------------------------------------------------- paged KV occupancy
    def pool_blocks(self, ndev: Optional[int] = None) -> int:
        if self._pool_blocks_override is not None:
            return self._pool_blocks_override
        return self.perf.pool_blocks(ndev if ndev is not None else self.ndev,
                                     self.kv_frac)

    def _tokens_now(self, finish: float, req: Request, t_start: float) -> int:
        """Tokens a running request currently holds: prompt + the fraction
        of its output generated so far (decode progresses linearly between
        ``t_start`` and its estimated finish)."""
        if finish <= t_start:
            return req.prompt_len + req.output_len
        frac = min(max((self.t - t_start) / (finish - t_start), 0.0), 1.0)
        return req.prompt_len + int(req.output_len * frac)

    def used_blocks(self) -> int:
        live = sum(self.perf.blocks_for(self._tokens_now(f, r, s))
                   for f, _, r, s in self.running)
        # chunked mode: sequences mid-prefill already hold their prompt's
        # blocks (the engine allocates at admission and registers chunks as
        # they are written; serving/kv_blocks.py)
        live += sum(self.perf.blocks_for(j.total) for j in self._prefilling)
        return live

    def _preempt_for_pressure(self, pool: int) -> None:
        """Evict lowest-priority / youngest running requests until the pool
        fits (recompute mode: back to the queue front, restarted on
        re-admission).  The last running request is never evicted — an
        oversubscribed singleton must be allowed to finish."""
        while len(self.running) > 1 and self.used_blocks() > pool:
            victim = min(self.running,
                         key=lambda e: (e[2].priority, -e[2].rid))
            self.running.remove(victim)
            heapq.heapify(self.running)
            self.queue.insert(0, victim[2])
            self._itl_base.pop(victim[2].rid, None)
            self._stall_gaps.pop(victim[2].rid, None)
            self.preemptions += 1
            obs.get_tracer().instant("preempt", cat="serve", t=self.t,
                                     tid="sim", args={"rid": victim[2].rid})

    def _stall_running(self, delta: float) -> None:
        """Shift every in-flight finish by ``delta`` (a modelled decode
        stall — prefill compute or staging contention) and record the gap
        per request so synthesized token_times carry the ITL spike."""
        if delta <= 0 or not self.running:
            return
        self.running = [(f + delta, rid, r, s)
                        for f, rid, r, s in self.running]
        heapq.heapify(self.running)
        if self.prefill_chunk is not None:
            for _, rid, _, _ in self.running:
                self._stall_gaps.setdefault(rid, []).append(delta)

    def _synth_token_times(self, req: Request) -> None:
        """Reconstruct per-token wall-clock times from the modelled decode
        rate plus any recorded stall gaps, so ``metrics.iter_itls`` sees
        the same ITL surface the real engine measures."""
        base = self._itl_base.pop(req.rid, None)
        gaps = self._stall_gaps.pop(req.rid, [])
        if base is None or req.first_token_s is None:
            return
        n = max(req.output_len - 1, 0)
        deltas = [base + g for g in gaps[:n]]
        deltas += [base] * (n - len(deltas))
        times = [req.first_token_s]
        for d in deltas:
            times.append(times[-1] + d)
        req.token_times = times

    def scaling_summary(self) -> Optional[Dict[str, float]]:
        """Modelled staging-overlap metrics over completed scale events
        (mirrors ``ElasticServer.scaling_summary``; metrics.summarize)."""
        if not self.events:
            return None
        effs = [e.cost.breakdown["op_s"] / max(e.cost.scale_time_s, 1e-9)
                for e in self.events if e.cost.breakdown.get("op_s")]
        return {"staging_mode": self.staging_mode,
                "scaledown_mode": self.scaledown_mode,
                "decode_stall_s": sum(e.cost.decode_stall_s
                                      for e in self.events),
                "overlap_efficiency":
                    sum(effs) / len(effs) if effs else None,
                "migrated_blocks": sum(e.migrated_blocks
                                       for e in self.events),
                "migration_bytes": sum(e.migration_bytes
                                       for e in self.events)}

    def routing_stats(self) -> Optional[Dict[str, float]]:
        """ServingBackend parity with ``ElasticServer.routing_stats``:
        with a ``SimRoutingModel`` (``routing_skew=``) the synthesized
        Zipf histogram, key-compatible with the engine's; otherwise None
        (the driver and ``metrics.summarize`` treat None as
        telemetry-absent)."""
        if self.routing is None:
            return None
        return self.routing.stats()

    def _elm(self) -> int:
        """Compiled table width per rank (mirrors HMM._pooled_index_arrays:
        ceil(E / ndev) + slack) — the replication slot budget."""
        return (math.ceil(self.mcfg.num_experts / max(self.ndev, 1))
                + self.expert_slot_slack)

    def _drive_rebalance(self, now: float) -> None:
        """Modelled rebalance pass: the shared policy decides over the
        synthesized histogram and the actions commit on the sim-owned page
        table within the quantum (rebalance bytes are negligible next to a
        scale event, so no modelled latency) — then the histogram restarts,
        exactly like the engine's RebalanceTask commit."""
        if (self.rebalance_policy is None or self.expert_pages is None
                or self.routing is None or self.scale is not None):
            return
        actions = self.rebalance_policy.decide(
            self.routing.stats(), self.expert_pages, self.current_config(),
            now, slots_per_rank=self._elm())
        if not actions:
            return
        try:
            ops = self.expert_pages.stage_rebalance(actions)
        except MemoryError:
            return                      # pool full this pass; retry later
        self.expert_pages.commit_rebalance()
        self.routing.reset()
        kinds = [op.kind for op in ops]
        page = self._expert_page_bytes
        self.rebalance_events.append(
            {"t": now, "actions": len(ops),
             "replicated": kinds.count("replicate"),
             "demoted": kinds.count("demote"),
             "dropped": kinds.count("drop_replica"),
             "promoted": kinds.count("promote"),
             "replica_bytes": kinds.count("replicate") * page,
             "d2h_bytes": kinds.count("demote") * page})
        obs.get_tracer().instant(
            "rebalance.commit", cat="rebalance", t=now, tid="sim",
            args={"actions": len(ops)})

    def rebalance_summary(self) -> Optional[dict]:
        """Mirror of ``ElasticServer.rebalance_summary`` over the modelled
        passes (None before the first one)."""
        if not self.rebalance_events:
            return None
        evs = self.rebalance_events
        return {"passes": len(evs), "aborted": 0,
                "replicated": sum(e["replicated"] for e in evs),
                "demoted": sum(e["demoted"] for e in evs),
                "dropped": sum(e["dropped"] for e in evs),
                "promoted": sum(e["promoted"] for e in evs),
                "replica_bytes": sum(e["replica_bytes"] for e in evs),
                "d2h_bytes": sum(e["d2h_bytes"] for e in evs),
                "host_tier_bytes": (len(self.expert_pages.host)
                                    * self._expert_page_bytes
                                    if self.expert_pages else 0)}

    def kv_stats(self) -> Optional[Dict[str, float]]:
        """Block-pool stats (None in dense mode); serving/metrics.py."""
        if self.kv_mode != "paged":
            return None
        pool = self.pool_blocks()
        used = self.used_blocks()
        return {"num_blocks": pool, "used_blocks": used,
                "utilization": used / max(pool, 1),
                "preemptions": self.preemptions,
                "live_seqs": len(self.running) + len(self._prefilling),
                "block_bytes": self.perf._kv_block_bytes,
                "migrated_blocks": sum(e.migrated_blocks
                                       for e in self.events)}

    def step(self, now: float) -> List[Request]:
        """One simulation quantum at time ``now`` (driver.ServingBackend):
        admit from the queue under the shared gating policy, then complete
        any requests whose modelled finish time has passed.  Paged mode
        first resolves pool pressure by preemption, then admits by block
        occupancy instead of the fixed ``max_batch``."""
        self.t = now
        done: List[Request] = []
        ndev, admit = self._serving_capacity()
        tr = obs.get_tracer()
        if self.routing is not None and ndev > 0 and self.running:
            # synthesized router telemetry: one sampled tick per quantum,
            # one token per running decode (matches the real sampler's
            # batch-token granularity)
            self.routing.observe(len(self.running))
        self._drive_rebalance(now)
        if tr.enabled and ndev > 0 and self.running:
            # one modelled decode step per quantum — explicit sim-time span
            # at the roofline-modelled duration, so an overlap trace reads
            # the same on both backends (DESIGN.md §9)
            tr.complete(
                "srv.step", now,
                now + self.perf.decode_step_s(len(self.running), ndev),
                cat="serve", tid="sim",
                args={"batch": len(self.running), "ndev": ndev})
        if ndev > 0:
            slot_cap = int(self.perf.max_batch_per_dev * ndev * self.kv_frac)
            if self.kv_mode == "paged":
                pool = self.pool_blocks(ndev)
                self._preempt_for_pressure(pool)
                used = self.used_blocks()
            # admit from queue
            while admit and self.queue \
                    and len(self.running) + len(self._prefilling) < slot_cap:
                req = self.queue[0]
                if self.kv_mode == "paged":
                    need = self.perf.blocks_for(req.prompt_len + 1)
                    if used + need > pool:
                        break
                    used += need
                elif (len(self.running) + len(self._prefilling)
                      >= self.perf.max_batch(ndev, self.kv_frac)):
                    break
                self.queue.pop(0)
                tr.instant("req.admit", cat="req", t=self.t, tid="sim",
                           args={"rid": req.rid})
                if self.scheduler is not None:
                    # chunked: prefill advances chunk-by-chunk below; the
                    # first token only lands when the last chunk does
                    self._prefilling.append(PrefillJob(
                        slot=req.rid, rid=req.rid, pos=0,
                        total=req.prompt_len))
                    self._prefill_reqs[req.rid] = req
                    continue
                t_first = self.t + self.perf.prefill_s(req.prompt_len, ndev)
                if req.first_token_s is None:
                    req.first_token_s = t_first
                    tr.instant("req.first_token", cat="req", t=t_first,
                               tid="sim", args={"rid": req.rid})
                base = self.perf.decode_step_s(
                    max(len(self.running) + 1, 1), ndev)
                if self.prefill_chunk == 0:
                    # monolithic prefill blocks the serve loop: every
                    # running decode stalls for the whole prompt — the
                    # long-tail ITL spike chunked prefill bounds
                    self._stall_running(t_first - self.t)
                    self._itl_base[req.rid] = base
                heapq.heappush(self.running,
                               (t_first + req.output_len * base,
                                req.rid, req, t_first))
            # chunked prefill: run this quantum's token-budget plan (the
            # SAME scheduler.plan the engine tick uses).  Each chunk's
            # compute stalls the running decodes for one chunk — not a
            # whole prompt — and a job landing its final chunk starts
            # decoding immediately (engine._run_prefill_chunks cadence).
            if self.scheduler is not None and self._prefilling:
                plans = self.scheduler.plan(self._prefilling)
                jobs = {j.rid: j for j in self._prefilling}
                self._stall_running(sum(self.perf.prefill_s(p.take, ndev)
                                        for p in plans))
                done_t = self.t
                for plan in plans:
                    done_t += self.perf.prefill_s(plan.take, ndev)
                    job = jobs[plan.rid]
                    job.pos = plan.start + plan.take
                    if plan.final:
                        self._prefilling.remove(job)
                        req = self._prefill_reqs.pop(plan.rid)
                        if req.first_token_s is None:
                            req.first_token_s = done_t
                            tr.instant("req.first_token", cat="req",
                                       t=done_t, tid="sim",
                                       args={"rid": req.rid})
                        base = self.perf.decode_step_s(
                            max(len(self.running) + 1, 1), ndev)
                        self._itl_base[req.rid] = base
                        heapq.heappush(
                            self.running,
                            (done_t + req.output_len * base,
                             req.rid, req, done_t))
            # complete requests
            while self.running and self.running[0][0] <= self.t:
                _, _, req, _ = heapq.heappop(self.running)
                req.finish_s = self.t
                tr.instant("req.finish", cat="req", t=self.t, tid="sim",
                           args={"rid": req.rid})
                if self.prefill_chunk is not None:
                    self._synth_token_times(req)
                done.append(req)
        self.finished.extend(done)
        return done

    def run(self, requests: List[Request], until: float, dt: float = 0.05):
        """Advance to ``until``; ``requests`` are *added* to the pending set
        (arrivals persist across calls)."""
        if requests:
            self._pending = merge_arrivals(self._pending, self._pi, requests)
            self._pi = 0
        while self.t < until:
            while self._pi < len(self._pending) \
                    and self._pending[self._pi].arrival_s <= self.t:
                self.submit(self._pending[self._pi])
                self._pi += 1
            t = self.t
            self.step(t)
            self.t = t + dt
        return self.finished

    # --------------------------------------------- ServingBackend protocol
    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def queue_depth(self) -> int:
        return len(self.queue)

    def utilization(self) -> float:
        if self.kv_mode == "paged":
            return self.used_blocks() / max(self.pool_blocks(), 1)
        cap = self.perf.max_batch(self.ndev, self.kv_frac)
        return (len(self.running) + len(self._prefilling)) / max(cap, 1)

    def current_config(self) -> ElasticConfig:
        return ElasticConfig(self.ndev // self.tp, self.tp,
                             tuple(range(self.ndev)))

    def prewarm(self, target: ElasticConfig) -> None:
        pass  # modelled: pre-init cost is already a plan_cost flag

    def capacity(self, cfg: ElasticConfig) -> int:
        if self.kv_mode == "paged":
            # conservative: full-length sequences; the real paged win shows
            # up in admission (occupancy-based) rather than this bound
            per_seq = self.perf.blocks_for(self.perf.kv_seq_len)
            return max(1, min(
                int(self.perf.max_batch_per_dev * cfg.ndev * self.kv_frac),
                self.pool_blocks(cfg.ndev) // per_seq))
        return self.perf.max_batch(cfg.ndev, self.kv_frac)

    def throughput(self, t0: float, t1: float) -> float:
        n = sum(1 for r in self.finished
                if r.finish_s is not None and t0 <= r.finish_s < t1)
        return n / max(t1 - t0, 1e-9)
