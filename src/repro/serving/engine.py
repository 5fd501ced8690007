"""Inference engine: continuous batching over an elastic instance.

The engine executes *real* JAX on the instance's mesh.  Two KV layouts:

* **dense** (``kv_mode='dense'``): decode slots are rows of the HMM-owned
  global ``[L, B, max_len, ...]`` cache; every admitted request reserves a
  full-length row.
* **paged** (``kv_mode='paged'``): the cache is a block *pool*
  ``[L, NB, bs, ...]`` and each slot holds a block table
  (``serving/kv_blocks.py``).  Admission is gated by free blocks, shared
  prompt prefixes are copy-on-write, and when a partition's pool runs dry
  the lowest-priority sequence is preempted (freed + re-queued; recomputed
  on resume).  Decode attention gathers K/V through the block table
  (``kernels.ops.block_paged_decode_attention``).

Scaling grows the slot count (dense) or appends pool partitions (paged) and
the surviving slots' state is reused zero-copy (the paper's "seamless
handoff, same KV cache", §5.2) — with paged KV the survivors' block tables
stay valid *verbatim*, and the determinism test asserts that tokens
generated across a scale-up event are identical to an unscaled run.

Step functions are AOT-compiled per (ElasticConfig, shape bucket); the IMM
caches them — compilation is the JAX analogue of instance pre-initialization.

The engine is parameter-layout agnostic: with the HMM's pooled expert store
(``expert_mode='pooled'``, DESIGN.md §2) the params pytree it binds carries
page pools + table index arrays instead of dense expert banks, the decode/
prefill functions route the MoE through the paged-GMM path, and a scale
event rebind only swaps tables — the engine code is unchanged either way.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.topology import ElasticConfig
from repro.distributed.sharding import ParallelCtx
from repro.models import model as M
from repro.serving.kv_blocks import KVBlockManager, MigrationTicket
from repro.serving.scheduler import (PrefillJob, TokenBudgetScheduler,
                                     prefix_skip)


def engine_parallel_ctx(mesh) -> ParallelCtx:
    return ParallelCtx(mesh=mesh, ep_axes=("dp", "tp"), tp_axis="tp",
                       dp_axes=("dp",), moe_tp=False)


# the step executables' names: module ``jit_<name>`` in HLO dumps and in
# the profiler's trace (monolithic prefill buckets are ``prefill_<S_pad>``)
DECODE_STEP = "decode_step"
DECODE_STEP_ROUTED = "decode_step_routed"
CHUNK_PREFILL = "chunk_prefill"


def _named(name: str, fn, *args):
    """``partial(fn, *args)`` that jits as ``jit_<name>`` (a bare partial
    jits as ``jit__unknown``); the computation is the same."""
    p = partial(fn, *args)
    p.__name__ = name
    return p


def _sample(logits, tokens, active, rng, temperature):
    if temperature and temperature > 0:
        nxt = jax.random.categorical(
            rng, logits.astype(jnp.float32) / temperature, axis=-1
        ).astype(jnp.int32)
    else:
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(active, nxt, tokens)


def _decode_fn(mcfg: ModelConfig, parallel, temperature, params, cache,
               tokens, lengths, active, rng):
    logits, cache = M.decode_step(mcfg, params, tokens[:, None], cache,
                                  lengths, parallel=parallel)
    return _sample(logits, tokens, active, rng, temperature), cache


def _paged_decode_fn(mcfg: ModelConfig, parallel, temperature, params, cache,
                     tokens, lengths, active, block_tables, rng):
    """Paged decode: block_tables [B, MB]; the write block is derived from
    each sequence's length; inactive slots write to the NB sentinel row
    (dropped)."""
    NB, bs = cache["k"].shape[1], cache["k"].shape[2]
    wb = jnp.take_along_axis(block_tables, (lengths // bs)[:, None], 1)[:, 0]
    wb = jnp.where(active, wb, NB)
    logits, cache = M.paged_decode_step(mcfg, params, tokens[:, None], cache,
                                        lengths, block_tables, wb,
                                        parallel=parallel)
    return _sample(logits, tokens, active, rng, temperature), cache


def _decode_routed_fn(mcfg: ModelConfig, parallel, temperature, params,
                      cache, tokens, lengths, active, rng):
    """Routing-telemetry decode: identical math plus per-(layer, expert)
    token counts [L_moe, E] from the MoE routers (models/moe.py)."""
    logits, cache, counts = M.decode_step(
        mcfg, params, tokens[:, None], cache, lengths, parallel=parallel,
        collect_routing=True)
    return _sample(logits, tokens, active, rng, temperature), cache, counts


def _paged_decode_routed_fn(mcfg: ModelConfig, parallel, temperature,
                            params, cache, tokens, lengths, active,
                            block_tables, rng):
    NB, bs = cache["k"].shape[1], cache["k"].shape[2]
    wb = jnp.take_along_axis(block_tables, (lengths // bs)[:, None], 1)[:, 0]
    wb = jnp.where(active, wb, NB)
    logits, cache, counts = M.paged_decode_step(
        mcfg, params, tokens[:, None], cache, lengths, block_tables, wb,
        parallel=parallel, collect_routing=True)
    return _sample(logits, tokens, active, rng, temperature), cache, counts


def _prefill_fn(mcfg: ModelConfig, parallel, max_len, params, cache, tokens,
                length, slot):
    """Prefill one request (padded to a bucket) into cache row ``slot``."""
    logits, small = M.prefill(mcfg, params,
                              {"tokens": tokens, "lengths": length[None]},
                              max_len=max_len, parallel=parallel)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]

    def put(big, new):
        # big: [L, B, ...]; new: [L, 1, ...] -> overwrite row `slot`
        idx = (0, slot) + (0,) * (big.ndim - 2)
        return jax.lax.dynamic_update_slice(big, new.astype(big.dtype), idx)

    cache = jax.tree.map(put, cache, small)
    return first, cache


def _paged_prefill_fn(mcfg: ModelConfig, parallel, params, cache, tokens,
                      length, block_ids):
    """Prefill one request and scatter its KV into pool blocks.

    ``block_ids`` [S_pad/bs]: pool row per prompt chunk; the NB sentinel
    marks both padding chunks and CoW-shared prefix blocks (already resident
    with identical contents — rewriting them would clobber a co-owner's
    tokens beyond this prompt's length)."""
    S_pad = tokens.shape[1]
    logits, small = M.prefill(mcfg, params,
                              {"tokens": tokens, "lengths": length[None]},
                              max_len=S_pad, parallel=parallel)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
    cache = M.write_prefill_to_blocks(cache, small, block_ids)
    return first, cache


def _chunk_prefill_fn(mcfg: ModelConfig, parallel, params, cache, tokens,
                      start, length, slot):
    """One dense-KV prefill chunk: tokens [1, C] are prompt positions
    [start, start+C) of cache row ``slot``; ``length`` is the prompt length
    covered so far (start + valid tokens in this chunk).  The returned token
    is the argmax at the last valid position — only meaningful on the final
    chunk (continuous batching, serving/scheduler.py)."""
    logits, cache = M.chunk_prefill_step(mcfg, params, tokens, cache, start,
                                         length, slot, parallel=parallel)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
    return first, cache


def _paged_chunk_prefill_fn(mcfg: ModelConfig, parallel, params, cache,
                            tokens, start, length, block_tables, chunk_ids):
    """One paged prefill chunk: the chunk's KV scatters into pool rows
    ``chunk_ids`` (NB sentinel = padding or CoW-shared block; dropped) and
    attention reads the whole context through ``block_tables`` [1, MB] via
    the mixed prefill/decode kernel."""
    logits, cache = M.paged_chunk_prefill_step(mcfg, params, tokens, cache,
                                               start, length, block_tables,
                                               chunk_ids, parallel=parallel)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
    return first, cache


@partial(jax.jit, donate_argnums=(0,))
def _cow_copy(cache, src, dst):
    """Copy pool block row ``src`` -> ``dst`` in every layer of every pool
    tensor; donation lets XLA alias the buffers (in-place on the pool)."""
    return jax.tree.map(
        lambda p: p.at[:, dst].set(
            jax.lax.dynamic_index_in_dim(p, src, axis=1, keepdims=False)),
        cache)


@dataclasses.dataclass
class SlotState:
    rid: int = -1
    remaining: int = 0
    active: bool = False
    priority: int = 0
    # live KV-block migration (scale-down): a migrating slot's sequence is
    # paused (its blocks are frozen while copies are in flight); a reserved
    # slot is the migration's destination and must not admit anything else
    migrating: bool = False
    reserved: bool = False
    # chunked prefill: admitted but not fully prefilled — occupies the slot
    # (and its KV blocks) but is excluded from decode until the final chunk
    prefilling: bool = False


@dataclasses.dataclass
class MigrationJob:
    """One in-flight slot migration: a sharing component of doomed slots
    moving to reserved survivor slots.  ``ticket.pairs`` is the device copy
    list; ``moves`` maps each sequence to its (src_slot, dst_slot)."""
    ticket: MigrationTicket
    moves: List[Tuple[int, int, int]]      # (rid, src_slot, dst_slot)


class InferenceEngine:
    """Continuous-batching engine bound to one (cfg, mesh, compiled steps).

    The engine object survives scaling: ``bind`` swaps in the new
    instance's mesh/cache/compiled functions while preserving slot states
    (and, in paged mode, block tables — the pool only grows/shrinks whole
    partitions, so surviving tables need no translation).
    """

    #: max lazily-compiled prefill buckets retained (satellite fix: the
    #: bucket cache used to grow without bound as preemption resumes pushed
    #: effective prompt lengths through ever-new buckets — each entry is a
    #: full XLA executable, and via the IMM's aliased ``compiled`` dict the
    #: leak outlived rebinds; AOT-precompiled buckets are never evicted)
    MAX_LAZY_PREFILL = 8

    def __init__(self, mcfg: ModelConfig, *, batch_per_replica: int,
                 max_len: int, prefill_bucket: int = 64,
                 prefill_chunk: int = 0,
                 prefill_budget: Optional[int] = None,
                 routing_sample_every: int = 0):
        self.mcfg = mcfg
        self.batch_per_replica = batch_per_replica
        self.max_len = max_len
        self.prefill_bucket = prefill_bucket
        # routing telemetry: every Nth decode tick runs the counts-emitting
        # "decode_routed" executable (when the bound instance compiled one)
        # and accumulates host-side per-(layer, expert) histograms
        self.routing_sample_every = routing_sample_every
        self._routing_counts: Optional[np.ndarray] = None
        self._routing_samples = 0
        # continuous batching: >0 splits prefill into fixed `prefill_chunk`-
        # token buckets interleaved with decode ticks under a per-tick token
        # budget (serving/scheduler.py); 0 = monolithic prefill at admission
        self.prefill_chunk = prefill_chunk
        self.scheduler = (TokenBudgetScheduler(prefill_chunk, prefill_budget)
                          if prefill_chunk > 0 else None)
        self._prefilling: List[PrefillJob] = []       # FIFO, admission order
        # slot -> (full prompt, resumed): host-side context for chunk jobs
        self._chunk_ctx: Dict[int, Tuple[np.ndarray, bool]] = {}
        self._lazy_prefill: "OrderedDict[str, None]" = OrderedDict()
        self.cfg: Optional[ElasticConfig] = None
        self.params = None
        self.cache = None
        self.compiled: Dict[str, Any] = {}
        self.slots: List[SlotState] = []
        self.lengths: Optional[np.ndarray] = None
        self.tokens: Optional[np.ndarray] = None
        self.generated: Dict[int, List[int]] = {}
        self.admit_limit: Optional[int] = None  # scale-down drain barrier
        # paged-KV state (kv_mode='paged'); see serving/kv_blocks.py
        self.kv: Optional[KVBlockManager] = None
        self.block_tables: Optional[np.ndarray] = None
        self._preempted_pending: List[int] = []   # rids awaiting re-queue
        self._resume_rids: set = set()            # preempted at least once
        self._finished_at_admission: List[int] = []
        self.preemptions = 0
        # serializes every mutation of ``self.cache`` (the compiled steps
        # donate it, so the handle is replaced each call): decode/prefill on
        # the serve thread vs per-block migration copies on the
        # TransferEngine workers (copy_block)
        self._cache_lock = threading.Lock()

    # ------------------------------------------------------------- binding
    @property
    def num_slots(self) -> int:
        return 0 if self.cfg is None else self.cfg.dp * self.batch_per_replica

    @property
    def paged(self) -> bool:
        return self.kv is not None

    def bind(self, cfg: ElasticConfig, mesh, params, cache, compiled,
             kv: Optional[KVBlockManager] = None):
        old_slots = self.slots
        old_lengths = self.lengths
        old_tokens = self.tokens
        old_tables = self.block_tables
        self.cfg, self.mesh = cfg, mesh
        self.params, self.cache = params, cache
        self.compiled = compiled
        self.kv = kv
        n = self.num_slots
        self.slots = [SlotState() for _ in range(n)]
        self.lengths = np.zeros((n,), np.int32)
        self.tokens = np.zeros((n,), np.int32)
        if self.prefill_chunk:
            assert M.chunk_prefill_supported(self.mcfg), \
                "chunked prefill unsupported for this model config"
        if self.paged:
            bs = self.kv.block_size
            assert self.max_len % bs == 0 and self.prefill_bucket % bs == 0, \
                "max_len and prefill buckets must be block-size multiples"
            assert self.prefill_chunk % bs == 0, \
                "prefill_chunk must be a block-size multiple (paged KV)"
            # padding rows hold the NB sentinel (never block id 0, which is
            # a valid pool row); NB tracks the *current* pool size, so
            # tables are rebuilt from the block manager on every rebind
            self.block_tables = np.full((n, self.max_len // bs),
                                        self.kv.num_blocks, np.int32)
        # surviving slots keep their requests (zero-copy KV reuse)
        for i in range(min(len(old_slots), n)):
            self.slots[i] = old_slots[i]
            self.lengths[i] = old_lengths[i]
            self.tokens[i] = old_tokens[i]
            if self.paged and old_tables is not None \
                    and self.slots[i].active:
                tbl = self.kv.block_table(self.slots[i].rid)
                self.block_tables[i, :len(tbl)] = tbl
        # chunk jobs survive rebinds slot-for-slot (scale-down migrates or
        # drains their slots first, so none can reference a dropped slot)
        self._prefilling = [j for j in self._prefilling if j.slot < n]
        self._chunk_ctx = {s: c for s, c in self._chunk_ctx.items() if s < n}
        # the new instance's compiled dict may not carry the old lazily-
        # compiled buckets; keep LRU bookkeeping consistent with it
        self._lazy_prefill = OrderedDict(
            (k, None) for k in self._lazy_prefill if k in compiled)

    def unbind(self):
        """Drop every device-array reference (park / scale-to-zero,
        DESIGN.md §12): the HMM has snapshotted the weights host-side, and
        the engine holding the old handles would keep the device buffers
        alive past the release.  Callers drain first — refusing to unbind
        under live sequences keeps park from silently killing requests."""
        assert self.active_count() == 0, "unbind with active sequences"
        self.cfg = None
        self.mesh = None
        self.params = None
        self.cache = None
        self.compiled = {}
        self.kv = None
        self.block_tables = None
        self.slots = []
        self.lengths = None
        self.tokens = None
        self._prefilling = []
        self._chunk_ctx = {}
        self._lazy_prefill = OrderedDict()
        self.admit_limit = None

    def free_slots(self) -> List[int]:
        lim = self.admit_limit if self.admit_limit is not None else len(self.slots)
        return [i for i, s in enumerate(self.slots)
                if not s.active and not s.reserved and i < lim]

    def drained(self, keep: int) -> bool:
        """True when all slots >= keep are inactive (scale-down ready)."""
        return all(not s.active for s in self.slots[keep:])

    def active_count(self) -> int:
        return sum(1 for s in self.slots if s.active)

    def utilization(self) -> float:
        """Occupied fraction of *admissible* serving capacity (drives the
        load estimator): slot occupancy dense, block-pool occupancy paged.

        During a scale-down, capacity is what survives the transition
        (``admit_limit`` slots / partitions) — counting doomed slots would
        deflate the load signal exactly while the estimator is judging
        whether the shrink was a good idea."""
        if self.paged:
            cap = self.kv.num_blocks
            if self.admit_limit is not None:
                parts = max(1, self.admit_limit // self.batch_per_replica)
                cap = min(cap, parts * self.kv.blocks_per_partition)
            return self.kv.used_blocks() / max(cap, 1)
        lim = (len(self.slots) if self.admit_limit is None
               else max(1, min(self.admit_limit, len(self.slots))))
        return self.active_count() / max(lim, 1)

    def kv_stats(self) -> Optional[Dict[str, float]]:
        if not self.paged:
            return None
        st = self.kv.stats()
        st["preemptions"] = self.preemptions
        st["block_bytes"] = self.block_nbytes()
        # single source of truth: the manager counts committed migrations
        # (kv.stats already reports migrated_blocks); bytes are derived
        st["migration_bytes"] = (self.kv.migrated_blocks
                                 * self.block_nbytes())
        return st

    # ------------------------------------------------------------- serving
    def _partition(self, slot: int) -> int:
        return slot // self.batch_per_replica

    def _full_prompt(self, req, prompt: np.ndarray) -> np.ndarray:
        """Preemption resume (recompute mode): the effective prompt is the
        original prompt plus everything generated before eviction."""
        if req.rid in self._resume_rids and self.generated.get(req.rid):
            return np.concatenate(
                [np.asarray(prompt, np.int32),
                 np.asarray(self.generated[req.rid], np.int32)])
        return np.asarray(prompt, np.int32)

    def can_admit(self, req, prompt: np.ndarray, slot: int) -> bool:
        if not self.paged:
            return True
        full = self._full_prompt(req, prompt)
        # +1: the first decode token must be appendable without preemption
        return self.kv.can_allocate(len(full) + 1, self._partition(slot),
                                    tokens=[int(t) for t in full])

    def preferred_slots(self, req, prompt: np.ndarray,
                        free: List[int]) -> List[int]:
        """Prefix-cache-aware admission order: free slots sorted so
        partitions already holding the longest registered prefix of this
        prompt come first — binding there turns the shared prefix into a
        refcount bump plus a prefill skip instead of recomputation (sharing
        is partition-local, kv_blocks.py).  Ties keep slot order, so the
        dense layout and prefix-free workloads are byte-identical to the
        old first-free-slot policy."""
        if not self.paged or len(free) <= 1:
            return list(free)
        full = self._full_prompt(req, prompt)
        toks = [int(t) for t in full]
        score = {p: len(self.kv.prefix_match_blocks(p, toks))
                 for p in {self._partition(s) for s in free}}
        return sorted(free, key=lambda s: (-score[self._partition(s)], s))

    def start_request(self, req, prompt: np.ndarray, slot: int):
        """Admit ``req`` into ``slot``.  Monolithic mode (prefill_chunk=0)
        runs the whole padded prompt here and returns the first generated
        token; chunked mode only allocates KV + enqueues a PrefillJob and
        returns None — the first token arrives from ``decode_tick`` when the
        final chunk lands."""
        if self.prefill_chunk:
            return self._start_request_chunked(req, prompt, slot)
        resume = req.rid in self._resume_rids
        full = self._full_prompt(req, prompt)
        S = len(full)
        bucket = self.prefill_bucket
        S_pad = max(bucket, -(-S // bucket) * bucket)
        toks = np.zeros((1, S_pad), np.int32)
        toks[0, :S] = full
        if self.paged:
            alloc = self.kv.allocate(req.rid, S,
                                     partition=self._partition(slot),
                                     priority=getattr(req, "priority", 0),
                                     tokens=[int(t) for t in full])
            bs = self.kv.block_size
            ids = np.full((S_pad // bs,), self.kv.num_blocks, np.int32)
            for j, b in enumerate(alloc.blocks):
                if j >= alloc.num_shared:      # shared prefix: don't rewrite
                    ids[j] = b
            with self._cache_lock:
                first, self.cache = self._prefill(S_pad)(
                    self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(S, jnp.int32), jnp.asarray(ids))
            # clear the previous occupant's rows with the NB sentinel, NOT
            # 0 — block 0 is a valid pool row, and a stale row beyond this
            # request's (possibly shorter) table must never alias a block
            # another sequence owns (module docstring: NB marks padding)
            self.block_tables[slot, :] = self.kv.num_blocks
            self.block_tables[slot, :len(alloc.blocks)] = alloc.blocks
        else:
            with self._cache_lock:
                first, self.cache = self._prefill(S_pad)(
                    self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(S, jnp.int32), jnp.asarray(slot, jnp.int32))
        produced = len(self.generated.get(req.rid, [])) if resume else 0
        remaining = req.output_len - produced - 1
        self.slots[slot] = SlotState(rid=req.rid, remaining=remaining,
                                     active=remaining > 0,
                                     priority=getattr(req, "priority", 0))
        self.lengths[slot] = S
        first = int(first)
        self.tokens[slot] = first
        if resume:
            self._resume_rids.discard(req.rid)
            self.generated[req.rid].append(first)
        else:
            self.generated[req.rid] = [first]
        if remaining <= 0:
            # the prefill token was the last one (output_len 1, or a
            # preemption resume that only had its final token left): the
            # request never reaches decode_tick, so completion must be
            # reported here or the caller waits on it forever
            self.slots[slot].active = False
            if self.paged:
                self.kv.free(req.rid)
            self._finished_at_admission.append(req.rid)
        return first

    def _start_request_chunked(self, req, prompt: np.ndarray, slot: int):
        """Chunked admission: no model compute runs here.  Paged KV is
        allocated up-front (occupancy-gated exactly like the monolithic
        path, so ``can_admit`` is unchanged) but prefix chains register only
        as chunks are written (``register_written``) — a matching arrival
        must never bind to blocks whose contents are still pending.  The
        job starts past the CoW-shared prefix (``prefix_skip``), charging
        only the non-shared tail against the token budget."""
        resume = req.rid in self._resume_rids
        full = self._full_prompt(req, prompt)
        S = len(full)
        start = 0
        if self.paged:
            alloc = self.kv.allocate(req.rid, S,
                                     partition=self._partition(slot),
                                     priority=getattr(req, "priority", 0),
                                     tokens=[int(t) for t in full],
                                     register=False)
            self.block_tables[slot, :] = self.kv.num_blocks
            self.block_tables[slot, :len(alloc.blocks)] = alloc.blocks
            start = prefix_skip(alloc.num_shared, self.kv.block_size, S)
        produced = len(self.generated.get(req.rid, [])) if resume else 0
        remaining = req.output_len - produced - 1
        self.slots[slot] = SlotState(rid=req.rid, remaining=remaining,
                                     active=True, prefilling=True,
                                     priority=getattr(req, "priority", 0))
        self.lengths[slot] = S
        if resume:
            self._resume_rids.discard(req.rid)
        self._chunk_ctx[slot] = (full,
                                 resume and bool(self.generated.get(req.rid)))
        self._prefilling.append(PrefillJob(slot=slot, rid=req.rid,
                                           pos=start, total=S))
        return None

    def drain_finished_at_admission(self) -> List[int]:
        """Requests whose prefill produced their final token this tick."""
        out, self._finished_at_admission = self._finished_at_admission, []
        return out

    def _prefill(self, S_pad: int):
        """Compiled prefill for a bucket; paged mode lazily compiles unseen
        buckets (preemption resume grows effective prompts past the
        pre-compiled set).  Lazy buckets are LRU-bounded at
        ``MAX_LAZY_PREFILL`` — AOT-precompiled buckets are never evicted
        (regression test: tests/test_paged_engine.py)."""
        key = f"prefill_{S_pad}"
        if key in self.compiled:
            if key in self._lazy_prefill:
                self._lazy_prefill.move_to_end(key)
            return self.compiled[key]
        assert self.paged, f"no compiled {key}"
        parallel = engine_parallel_ctx(self.mesh)
        repl = NamedSharding(self.mesh, P())
        cache_out = jax.tree.map(lambda x: x.sharding, self.cache)
        pf = jax.jit(_named(key, _paged_prefill_fn, self.mcfg, parallel),
                     donate_argnums=(1,),
                     out_shardings=(repl, cache_out))
        bs = self.kv.block_size
        self.compiled[key] = pf.lower(
            as_sds(self.params), as_sds(self.cache),
            jax.ShapeDtypeStruct((1, S_pad), jnp.int32, sharding=repl),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
            jax.ShapeDtypeStruct((S_pad // bs,), jnp.int32,
                                 sharding=repl)).compile()
        self._lazy_prefill[key] = None
        while len(self._lazy_prefill) > self.MAX_LAZY_PREFILL:
            old, _ = self._lazy_prefill.popitem(last=False)
            self.compiled.pop(old, None)
        return self.compiled[key]

    def _chunk_prefill(self):
        """Compiled chunk-prefill executable (one bucket: ``prefill_chunk``
        tokens).  AOT-compiled by ``compile_step_functions`` when the
        instance was built with ``prefill_chunk``; compiled lazily here
        otherwise (never evicted — there is exactly one chunk shape)."""
        key = f"chunk_prefill_{self.prefill_chunk}"
        if key not in self.compiled:
            parallel = engine_parallel_ctx(self.mesh)
            repl = NamedSharding(self.mesh, P())
            cache_out = jax.tree.map(lambda x: x.sharding, self.cache)
            C = self.prefill_chunk

            def sd(shape):
                return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=repl)

            if self.paged:
                pf = jax.jit(
                    _named(CHUNK_PREFILL, _paged_chunk_prefill_fn, self.mcfg,
                           parallel),
                    donate_argnums=(1,), out_shardings=(repl, cache_out))
                bs = self.kv.block_size
                self.compiled[key] = pf.lower(
                    as_sds(self.params), as_sds(self.cache), sd((1, C)),
                    sd(()), sd(()), sd((1, self.max_len // bs)),
                    sd((C // bs,))).compile()
            else:
                pf = jax.jit(_named(CHUNK_PREFILL, _chunk_prefill_fn,
                                    self.mcfg, parallel),
                             donate_argnums=(1,),
                             out_shardings=(repl, cache_out))
                self.compiled[key] = pf.lower(
                    as_sds(self.params), as_sds(self.cache), sd((1, C)),
                    sd(()), sd(()), sd(())).compile()
        return self.compiled[key]

    # -------------------------------------------------- paged bookkeeping
    def _slot_of(self, rid: int) -> int:
        for i, s in enumerate(self.slots):
            if s.rid == rid and s.active:
                return i
        raise KeyError(rid)

    def _preempt_slot(self, slot: int) -> None:
        """Evict a sequence under pool pressure: free its blocks, park the
        rid for the server to re-queue; it restarts in recompute mode."""
        s = self.slots[slot]
        if s.prefilling:
            # mid-prefill eviction: drop the chunk job — recompute mode
            # restarts the prompt from scratch on re-admission
            self._prefilling = [j for j in self._prefilling
                                if j.slot != slot]
            self._chunk_ctx.pop(slot, None)
        self.kv.preempt(s.rid)
        self.preemptions += 1
        obs.get_tracer().instant("preempt", cat="serve",
                                 args={"rid": s.rid, "slot": slot})
        self._resume_rids.add(s.rid)
        self._preempted_pending.append(s.rid)
        self.slots[slot] = SlotState()

    def drain_preempted(self) -> List[int]:
        out, self._preempted_pending = self._preempted_pending, []
        return out

    def _copy_block(self, src: int, dst: int) -> None:
        """Physical copy-on-write: duplicate pool row ``src`` into ``dst``
        across all layers.  Jitted with the cache donated so XLA updates
        the pool buffers in place (one block row moved, not a pool copy)."""
        with self._cache_lock:
            self.cache = _cow_copy(self.cache, jnp.asarray(src, jnp.int32),
                                   jnp.asarray(dst, jnp.int32))

    def _ensure_append(self, slot: int) -> bool:
        """Reserve the write slot for this sequence's next token, preempting
        lower-priority sequences in the same partition when the pool is dry.
        Returns False if the sequence itself was preempted."""
        rid = self.slots[slot].rid
        while True:
            try:
                r = self.kv.append(rid)
                break
            except MemoryError:
                part = self._partition(slot)
                cands = [s.rid for i, s in enumerate(self.slots)
                         if s.active and self._partition(i) == part]
                victim = self.kv.victim(candidates=cands)
                if victim is None or victim == rid:
                    self._preempt_slot(slot)
                    return False
                self._preempt_slot(self._slot_of(victim))
        if r is not None:
            if r.cow_src is not None:
                self._copy_block(r.cow_src, r.block)
                obs.get_tracer().instant(
                    "kv.cow_copy", cat="serve",
                    args={"src": r.cow_src, "dst": r.block})
            j = int(self.lengths[slot]) // self.kv.block_size
            self.block_tables[slot, j] = r.block
        return True

    # ------------------------------------- live migration (scale-down)
    def block_nbytes(self) -> int:
        """Device bytes of ONE pool block across all layers/tensors — the
        unit of migration byte accounting."""
        assert self.paged and self.cache is not None
        return sum(leaf.nbytes // leaf.shape[1]
                   for leaf in jax.tree.leaves(self.cache))

    def doomed_active_slots(self) -> List[int]:
        """Active slots that will be evicted by the pending scale-down
        (at or above ``admit_limit``), including ones mid-migration."""
        assert self.admit_limit is not None
        return [i for i, s in enumerate(self.slots)
                if s.active and i >= self.admit_limit]

    def copy_block(self, src: int, dst: int) -> None:
        """One migration device copy (pool row ``src`` -> ``dst``), safe to
        run on a TransferEngine worker: the jit-donated CoW copy under the
        cache lock, serialized against decode/prefill cache swaps.  Call
        once from the serve thread first (``prewarm_block_copy``) so the
        compile never happens on a worker."""
        self._copy_block(src, dst)

    def prewarm_block_copy(self) -> None:
        """Compile the block-copy executable on the serve thread (a
        self-copy is a content no-op) before workers start issuing it."""
        self._copy_block(0, 0)

    def plan_migration(self) -> Optional[MigrationJob]:
        """Plan ONE component move off a doomed partition, or None.

        Picks the first doomed partition with unmigrated live sequences,
        groups them into block-sharing components (the unit that preserves
        CoW refcounts), and best-effort places each component onto a
        survivor partition with enough free *slots* and free *blocks*.  A
        component no survivor can hold block-wise falls back to
        recompute-preemption (freed + re-queued, restarted after
        switchover); one that is merely waiting on survivor slots is left
        for a later call (survivors only finish during a scale — admission
        is paused — so slots free up monotonically)."""
        assert self.paged and self.admit_limit is not None
        keep_parts = self.admit_limit // self.batch_per_replica
        bpr = self.batch_per_replica
        slot_of = {s.rid: i for i, s in enumerate(self.slots) if s.active}
        for part in range(keep_parts, self.kv.num_partitions):
            for comp in self.kv.share_components(part):
                if any(self.kv.migrating(s) for s in comp):
                    continue
                if any(r not in slot_of for r in comp):
                    continue            # finishing this tick; skip
                need = self.kv.migration_need(comp)
                placed = None
                for q in range(keep_parts):
                    free = [i for i in range(q * bpr, (q + 1) * bpr)
                            if not self.slots[i].active
                            and not self.slots[i].reserved
                            and i < self.admit_limit]
                    if len(free) >= len(comp) \
                            and self.kv.free_blocks(q) >= need:
                        placed = (q, free)
                        break
                if placed is None:
                    if len(comp) <= bpr and any(
                            self.kv.free_blocks(q) >= need
                            for q in range(keep_parts)):
                        continue        # blocks exist; waiting on slots
                    # no survivor can ever hold this component: recompute
                    for rid in sorted(comp):
                        self._preempt_slot(slot_of[rid])
                    continue
                q, free = placed
                ticket = self.kv.begin_migration(comp, q)
                moves = []
                for rid, dst in zip(sorted(comp), free):
                    src = slot_of[rid]
                    self.slots[src].migrating = True
                    self.slots[dst] = SlotState(reserved=True)
                    moves.append((rid, src, dst))
                return MigrationJob(ticket=ticket, moves=moves)
        return None

    def finish_migration(self, job: MigrationJob) -> None:
        """Cut-over after every pair in ``job.ticket`` was device-copied:
        commit the block-table rewrite, re-home each slot's state to its
        survivor slot, and resume decoding there."""
        obs.get_tracer().instant(
            "kv.migrate", cat="serve",
            args={"rids": sorted(r for r, _, _ in job.moves),
                  "blocks": len(job.ticket.pairs)})
        self.kv.commit_migration(job.ticket)
        NB = self.kv.num_blocks
        for rid, src, dst in job.moves:
            st = self.slots[src]
            assert st.rid == rid and st.migrating
            st.migrating = False
            self.slots[dst] = st
            self.slots[src] = SlotState()
            self.lengths[dst] = self.lengths[src]
            self.tokens[dst] = self.tokens[src]
            tbl = self.kv.block_table(rid)
            self.block_tables[dst, :] = NB
            self.block_tables[dst, :len(tbl)] = tbl
            self.block_tables[src, :] = NB
            # a mid-prefill sequence resumes chunking on its survivor slot
            # (chunk ids are re-derived from the committed block table at
            # execution time, so the move is transparent to the job)
            for j in self._prefilling:
                if j.slot == src:
                    j.slot = dst
            if src in self._chunk_ctx:
                self._chunk_ctx[dst] = self._chunk_ctx.pop(src)

    def cancel_migration(self, job: MigrationJob) -> None:
        """Abort an in-flight migration: the reservation unwinds, source
        tables were never touched (device truth unchanged), and the paused
        sequences resume decoding in place."""
        self.kv.abort_migration(job.ticket)
        for _, src, dst in job.moves:
            if self.slots[src].migrating:
                self.slots[src].migrating = False
            if self.slots[dst].reserved:
                self.slots[dst] = SlotState()

    @obs.traced("srv.prefill", cat="serve")
    def _run_prefill_chunks(self) -> List[Tuple[int, int, bool]]:
        """The tick's prefill phase (continuous batching): consume at most
        ``prefill_budget`` prompt tokens as ``prefill_chunk``-token buckets
        in admission order.  Chunk block ids are re-derived from the block
        manager at execution time (not admission time) so live migration
        re-homing is transparent.  Returns first-token events for jobs whose
        final chunk landed this tick.

        Each chunk is one ``srv.prefill.chunk`` span holding ``prep`` (the
        host arrays), ``dispatch`` (uploads and the executable call),
        ``register`` (paged: written blocks become matchable) and, on a
        final chunk, ``read`` (the first token's device sync)."""
        for job in self._prefilling:
            job.paused = self.slots[job.slot].migrating
        plans = self.scheduler.plan(self._prefilling)
        out: List[Tuple[int, int, bool]] = []
        C = self.prefill_chunk
        jobs = {j.slot: j for j in self._prefilling}
        tr = obs.get_tracer()
        for plan in plans:
            slot = plan.slot
            job = jobs[slot]
            with tr.span("srv.prefill.chunk", cat="serve",
                         args={"rid": job.rid, "start": plan.start,
                               "take": plan.take}):
                full, resumed = self._chunk_ctx[slot]
                upto = plan.start + plan.take
                with tr.span("srv.prefill.prep", cat="serve"):
                    toks = np.zeros((1, C), np.int32)
                    toks[0, :plan.take] = full[plan.start:upto]
                    if self.paged:
                        tbl, ids = self._chunk_tables(job.rid, plan.start)
                with tr.span("srv.prefill.dispatch", cat="serve"):
                    where = ((jnp.asarray(tbl), jnp.asarray(ids))
                             if self.paged
                             else (jnp.asarray(slot, jnp.int32),))
                    with self._cache_lock:
                        first, self.cache = self._chunk_prefill()(
                            self.params, self.cache, jnp.asarray(toks),
                            jnp.asarray(plan.start, jnp.int32),
                            jnp.asarray(upto, jnp.int32), *where)
                job.pos = upto
                if self.paged:
                    # written blocks become matchable for later arrivals
                    with tr.span("srv.prefill.register", cat="serve"):
                        self.kv.register_written(
                            job.rid, [int(t) for t in full], upto)
                if plan.final:
                    with tr.span("srv.prefill.read", cat="serve"):
                        first = int(first)
                    out.append(self._finish_prefill(slot, job, first,
                                                    resumed))
        return out

    def _chunk_tables(self, rid: int, start: int):
        """A paged chunk's block table ``[1, max_len // bs]`` and the pool
        rows it writes ``[C // bs]``: the NB sentinel drops writes to
        padding, CoW-shared prefix blocks, and (on the rounded-down
        prefix_skip start) recomputed rows."""
        bs = self.kv.block_size
        NB = self.kv.num_blocks
        sb = self.kv.seq(rid)
        j0 = start // bs
        ids = np.full((self.prefill_chunk // bs,), NB, np.int32)
        for k in range(len(ids)):
            j = j0 + k
            if j < len(sb.blocks) and j >= sb.num_shared:
                ids[k] = sb.blocks[j]
        tbl = np.full((1, self.max_len // bs), NB, np.int32)
        bt = self.kv.block_table(rid)
        tbl[0, :len(bt)] = bt
        return tbl, ids

    def _finish_prefill(self, slot: int, job: PrefillJob, first: int,
                        resumed: bool) -> Tuple[int, int, bool]:
        """Final chunk landed: record the first generated token and move the
        slot into the decode pool (it decodes this same tick — the same
        cadence as monolithic admission, whose first decode follows the
        admission-tick prefill immediately)."""
        s = self.slots[slot]
        s.prefilling = False
        self._prefilling.remove(job)
        self._chunk_ctx.pop(slot, None)
        self.tokens[slot] = first
        if resumed:
            self.generated[s.rid].append(first)
        else:
            self.generated[s.rid] = [first]
        fin = s.remaining <= 0
        if fin:
            # output_len 1 (or a resume with only its final token left):
            # never reaches decode — reported through this tick's events
            s.active = False
            if self.paged:
                self.kv.free(s.rid)
        return (s.rid, first, fin)

    @obs.traced("srv.step", cat="serve")
    def decode_tick(self) -> List[Tuple[int, int, bool]]:
        """One engine tick.  With chunked prefill enabled the tick is a
        token-budget schedule: first the prefill phase (at most
        ``prefill_budget`` prompt tokens as fixed-size chunks, admission
        order), then one decode step for all runnable slots — decode runs
        every tick regardless of prefill backlog, which is the
        no-starvation guarantee (serving/scheduler.py).  Runnable = active,
        not mid-prefill, and not paused by an in-flight migration (a
        migrating sequence's blocks are frozen until the copies land, then
        it resumes on its survivor slot).  Returns [(rid, token, finished)]
        for slots that produced a token; prefill completions come first.

        Spans: ``srv.step`` holds ``srv.prefill`` and ``srv.decode``, whose
        children are ``prep`` (append reservations and the runnable mask),
        ``dispatch`` (uploads and the executable call), ``read`` (the
        tokens' device sync) and ``commit`` (per-slot bookkeeping)."""
        pre: List[Tuple[int, int, bool]] = []
        if self.scheduler is not None and self._prefilling:
            pre = self._run_prefill_chunks()
        runnable = [s.active and not s.migrating and not s.prefilling
                    for s in self.slots]
        if not any(runnable):
            return pre
        with obs.get_tracer().span("srv.decode", cat="serve") as span:
            return pre + self._decode_step(runnable, span)

    def _decode_step(self, runnable: List[bool],
                     span) -> List[Tuple[int, int, bool]]:
        """The decode step inside the open ``srv.decode`` ``span``, which
        gets the ``rows`` it decodes."""
        tr = obs.get_tracer()
        with tr.span("srv.decode.prep", cat="serve"):
            if self.paged:
                # highest priority first, oldest first on ties: pressure
                # evicts from the low-priority/young end before it reaches
                # them
                order = sorted(
                    (i for i in range(len(self.slots)) if runnable[i]),
                    key=lambda i: (-self.slots[i].priority,
                                   self.slots[i].rid))
                for slot in order:
                    if self.slots[slot].active:
                        self._ensure_append(slot)
                runnable = [s.active and not s.migrating and not s.prefilling
                            for s in self.slots]
            active = np.array(runnable)
        rows = int(active.sum())
        span.set_metadata(rows=rows)
        if rows == 0:
            return []
        with tr.span("srv.decode.dispatch", cat="serve"):
            self._step_count = getattr(self, "_step_count", 0) + 1
            # routing telemetry: every Nth tick runs the counts-emitting
            # twin executable (same math — only an extra histogram output)
            routed = (self.routing_sample_every > 0
                      and "decode_routed" in self.compiled
                      and self._step_count % self.routing_sample_every == 0)
            key = "decode_routed" if routed else "decode"
            rng = jax.random.key_data(jax.random.PRNGKey(self._step_count))
            tables = (jnp.asarray(self.block_tables),) if self.paged else ()
            with self._cache_lock:
                res = self.compiled[key](
                    self.params, self.cache, jnp.asarray(self.tokens),
                    jnp.asarray(self.lengths), jnp.asarray(active), *tables,
                    rng)
                if routed:
                    nxt, self.cache, counts = res
                else:
                    nxt, self.cache = res
        with tr.span("srv.decode.read", cat="serve"):
            nxt = np.asarray(nxt)
            if routed:
                self._accumulate_routing(counts)
        with tr.span("srv.decode.commit", cat="serve"):
            out = []
            for i, s in enumerate(self.slots):
                if not active[i]:
                    continue
                self.lengths[i] += 1
                self.tokens[i] = nxt[i]
                self.generated[s.rid].append(int(nxt[i]))
                s.remaining -= 1
                fin = s.remaining <= 0 or self.lengths[i] >= self.max_len - 1
                if fin:
                    s.active = False
                    if self.paged:
                        self.kv.free(s.rid)
                out.append((s.rid, int(nxt[i]), fin))
        return out

    # --------------------------------------------------- routing telemetry
    def _accumulate_routing(self, counts) -> None:
        """Fold one sampled tick's [L_moe, E] expert counts into the
        host-side histogram and emit a skew counter sample."""
        c = np.asarray(counts, np.int64)
        if self._routing_counts is None or \
                self._routing_counts.shape != c.shape:
            # shape change = a different routed executable (rebind): the
            # accumulator AND the sample count restart together — zeroing
            # only the counts would leave routing_stats()["samples"]
            # overcounting and skew averages dividing by the wrong
            # denominator
            self._routing_counts = np.zeros_like(c)
            self._routing_samples = 0
        self._routing_counts += c
        self._routing_samples += 1
        tr = obs.get_tracer()
        if tr.enabled:
            tot = np.maximum(c.sum(axis=-1), 1)
            tr.counter("routing.top_expert_share",
                       float((c.max(axis=-1) / tot).mean()), cat="routing")

    def reset_routing_stats(self) -> None:
        """Restart the routing histogram (counts AND sample count together).

        Invoked at scale-event commit (``ElasticServer.switchover``) and at
        rebalance commit: counts accumulated under the *old* placement
        describe traffic the new placement no longer sees, so letting them
        survive would bias the rebalancer's first post-reconfiguration
        decisions toward stale skew."""
        self._routing_counts = None
        self._routing_samples = 0

    def routing_stats(self) -> Optional[dict]:
        """Accumulated per-expert routing histogram (None until a sampled
        tick has landed).  ``counts`` is [L_moe, E] token counts;
        ``top_expert_share`` / ``expert_cv`` are layer-averaged skew
        metrics (heavy-tailed routing shows up as share >> 1/E and
        cv >> 0) — the signal the skew-aware expert rebalancer
        (serving/rebalance.py, DESIGN.md §10) acts on."""
        if self._routing_counts is None or self._routing_samples == 0:
            return None
        c = self._routing_counts.astype(np.float64)
        tot = np.maximum(c.sum(axis=-1), 1.0)
        share = c.max(axis=-1) / tot
        mean = np.maximum(c.mean(axis=-1), 1e-9)
        cv = c.std(axis=-1) / mean
        return {"samples": self._routing_samples,
                "counts": self._routing_counts.copy(),
                "top_expert_share": float(share.mean()),
                "expert_cv": float(cv.mean())}


# ------------------------------------------------------------- compilation

def as_sds(tree):
    """pytree of arrays (or SDS) -> pytree of sharded ShapeDtypeStructs."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        tree)


def compile_step_functions(mcfg: ModelConfig, cfg: ElasticConfig, mesh,
                           params_sds, cache_sds, *,
                           batch_per_replica: int, max_len: int,
                           prefill_buckets=(64,),
                           temperature: float = 0.0,
                           kv_mode: str = "dense",
                           kv_block_size: int = 0,
                           prefill_chunk: int = 0,
                           collect_routing: bool = False
                           ) -> Tuple[Dict[str, Any], Dict[str, float]]:
    """AOT-compile decode + prefill executables for an instance.

    ``params_sds``/``cache_sds``: pytrees of sharded ShapeDtypeStructs (no
    weights needed — pre-initialization works without the HMM, exactly the
    paper's CPU-standby instances, §4.5).  ``kv_mode='paged'`` compiles the
    block-table variants (cache_sds is then the pool layout).
    ``prefill_chunk > 0`` compiles the continuous-batching chunk-prefill
    executable (one shape — the chunk bucket) in place of the monolithic
    prefill buckets.
    ``collect_routing`` additionally compiles the "decode_routed" twin that
    also returns per-(layer, expert) routing counts (obs telemetry); the
    default decode path is byte-identical either way.
    Returns (executables, lower+compile seconds per executable).
    """
    parallel = engine_parallel_ctx(mesh)
    B = cfg.dp * batch_per_replica
    repl = NamedSharding(mesh, P())
    paged = kv_mode == "paged"

    out: Dict[str, Any] = {}
    seconds: Dict[str, float] = {}

    def aot(name, fn, out_shardings, *args):
        t0 = time.perf_counter()
        out[name] = jax.jit(fn, donate_argnums=(1,),
                            out_shardings=out_shardings
                            ).lower(*args).compile()
        seconds[name] = time.perf_counter() - t0

    def sd(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    cache_out = jax.tree.map(lambda s: s.sharding, cache_sds)
    step_out, routed_out = (repl, cache_out), (repl, cache_out, repl)
    tok_sd, rng_sd, act_sd = sd((B,)), sd((2,), jnp.uint32), \
        sd((B,), jnp.bool_)
    if collect_routing:
        assert M.routing_stats_supported(mcfg), \
            f"{mcfg.name}: routing telemetry unsupported"
    if paged:
        assert kv_block_size > 0 and max_len % kv_block_size == 0
        MB = max_len // kv_block_size
        step = (params_sds, cache_sds, tok_sd, tok_sd, act_sd, sd((B, MB)),
                rng_sd)
        aot("decode", _named(DECODE_STEP, _paged_decode_fn, mcfg, parallel,
                             temperature),
            step_out, *step)
        if collect_routing:
            aot("decode_routed", _named(DECODE_STEP_ROUTED,
                                        _paged_decode_routed_fn, mcfg,
                                        parallel, temperature),
                routed_out, *step)
    else:
        step = (params_sds, cache_sds, tok_sd, tok_sd, act_sd, rng_sd)
        aot("decode", _named(DECODE_STEP, _decode_fn, mcfg, parallel,
                             temperature),
            step_out, *step)
        if collect_routing:
            aot("decode_routed", _named(DECODE_STEP_ROUTED, _decode_routed_fn,
                                        mcfg, parallel, temperature),
                routed_out, *step)
    # chunked mode admits every prompt through the chunk executable; the
    # monolithic buckets would be compiled and never run
    for S_pad in () if prefill_chunk else prefill_buckets:
        if paged:
            aot(f"prefill_{S_pad}", _named(f"prefill_{S_pad}",
                                           _paged_prefill_fn, mcfg, parallel),
                step_out, params_sds, cache_sds, sd((1, S_pad)), sd(()),
                sd((S_pad // kv_block_size,)))
        else:
            aot(f"prefill_{S_pad}", _named(f"prefill_{S_pad}", _prefill_fn,
                                           mcfg, parallel, max_len),
                step_out, params_sds, cache_sds, sd((1, S_pad)), sd(()),
                sd(()))
    if prefill_chunk:
        assert M.chunk_prefill_supported(mcfg), \
            "chunked prefill unsupported for this model config"
        C = prefill_chunk
        if paged:
            assert C % kv_block_size == 0
            aot(f"chunk_prefill_{C}", _named(CHUNK_PREFILL,
                                             _paged_chunk_prefill_fn, mcfg,
                                             parallel),
                step_out, params_sds, cache_sds, sd((1, C)), sd(()), sd(()),
                sd((1, max_len // kv_block_size)), sd((C // kv_block_size,)))
        else:
            aot(f"chunk_prefill_{C}", _named(CHUNK_PREFILL, _chunk_prefill_fn,
                                             mcfg, parallel),
                step_out, params_sds, cache_sds, sd((1, C)), sd(()), sd(()),
                sd(()))
    return out, seconds
