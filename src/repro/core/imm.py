"""Inference Management Module (paper §4.5).

Keeps an LRU cache of *pre-initialized* inference instances.  In the paper a
standby instance is a CPU-resident vLLM process that has done every one-time
setup except binding weights; the JAX analogue of that expensive boot step is
AOT compilation of the instance's step functions for its (mesh, shapes) —
so a standby instance here is a set of compiled executables with **no
weights attached** (built purely from ShapeDtypeStructs).

``activate`` binds a standby instance to the HMM's zero-copy array handles —
a metadata-only operation (the ZeroCopyLoader replacing vLLM's DiskLoader).

With overlapped staging (``staging="overlap"``, DESIGN.md §3) the IMM's
AOT compile runs on the serving thread *while* the HMM's background
``TransferEngine`` moves bytes — STAGING ∥ COMPILING, so a cold compile
hides under the transfer window instead of following it.  Compilation is
pure tracing over ShapeDtypeStructs (no weight reads), so it races with
nothing the transfer ops touch.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import jax

from repro.configs.base import ModelConfig
from repro.core.hmm import HMM, make_instance_mesh
from repro.core.topology import ElasticConfig
from repro.serving.engine import as_sds, compile_step_functions


@dataclasses.dataclass
class StandbyInstance:
    cfg: ElasticConfig
    mesh: Any
    compiled: Dict[str, Any]
    compile_s: float
    # lower+compile seconds per executable (decode, chunk_prefill_<C>, ...)
    compile_times: Dict[str, float] = dataclasses.field(default_factory=dict)
    activations: int = 0


class IMM:
    def __init__(self, mcfg: ModelConfig, hmm: HMM, *,
                 batch_per_replica: int, max_len: int,
                 prefill_buckets=(64,), prefill_chunk: int = 0,
                 lru_capacity: int = 4, collect_routing: bool = False,
                 shared_cache: Optional[
                     "OrderedDict[Tuple, StandbyInstance]"] = None):
        self.mcfg = mcfg
        self.hmm = hmm
        self.batch_per_replica = batch_per_replica
        self.max_len = max_len
        self.prefill_buckets = tuple(prefill_buckets)
        # continuous batching: >0 also pre-compiles the chunk-prefill
        # executable per instance (engine.prefill_chunk)
        self.prefill_chunk = prefill_chunk
        # routing telemetry: also pre-compile the count-returning decode
        # twin ("decode_routed"; DESIGN.md §9)
        self.collect_routing = collect_routing
        self.lru_capacity = lru_capacity
        # A fleet shares one standby LRU across models (pass the same
        # OrderedDict to every IMM) so total cached executables stay
        # bounded by one capacity, not N of them; keys carry the full
        # model identity so same-mesh models can never collide.
        self._cache: "OrderedDict[Tuple, StandbyInstance]" = (
            shared_cache if shared_cache is not None else OrderedDict())
        self.stats = {"preinit_hits": 0, "preinit_misses": 0,
                      "compile_s_total": 0.0}

    def _key(self, cfg: ElasticConfig) -> Tuple:
        # Standby executables are specialized on everything that shapes the
        # traced program, not just the mesh: two fleet models with the same
        # (dp, tp, devices) must not collide on a cached executable, so the
        # key carries the model config and every compile-affecting knob.
        return (repr(self.mcfg),
                self.batch_per_replica, self.max_len,
                self.prefill_buckets, self.prefill_chunk,
                self.collect_routing,
                self.hmm.kv_mode, self.hmm.kv_block_size,
                self.hmm.kv_blocks_per_replica,
                self.hmm.expert_mode, self.hmm.expert_pool_pages,
                self.hmm.expert_slot_slack,
                self.hmm.kv_dtype, self.hmm.expert_dtype,
                cfg.dp, cfg.tp, cfg.devices)

    def has(self, cfg: ElasticConfig) -> bool:
        """True if a standby instance for ``cfg`` is already compiled (an
        imminent ``preinitialize``/``activate`` will be a metadata-only hit).
        Does not touch LRU order or hit/miss counters."""
        return self._key(cfg) in self._cache

    # ------------------------------------------------------------ pre-init
    def preinitialize(self, cfg: ElasticConfig) -> StandbyInstance:
        """Build (or fetch) a standby instance for ``cfg`` — compile only,
        no weights.  Corresponds to IMM pre-initialization (§4.5)."""
        key = self._key(cfg)
        if key in self._cache:
            self._cache.move_to_end(key)
            return self._cache[key]
        mesh = make_instance_mesh(cfg, self.hmm.all_devices)
        params_sds, cache_sds = self._shape_templates(cfg, mesh)
        t0 = time.perf_counter()
        compiled, times = compile_step_functions(
            self.mcfg, cfg, mesh, params_sds, cache_sds,
            batch_per_replica=self.batch_per_replica, max_len=self.max_len,
            prefill_buckets=self.prefill_buckets,
            prefill_chunk=self.prefill_chunk,
            kv_mode=self.hmm.kv_mode,
            kv_block_size=self.hmm.kv_block_size,
            collect_routing=self.collect_routing)
        dt = time.perf_counter() - t0
        inst = StandbyInstance(cfg, mesh, compiled, dt, times)
        self._cache[key] = inst
        self.stats["compile_s_total"] += dt
        while len(self._cache) > self.lru_capacity:
            self._cache.popitem(last=False)
        return inst

    def _shape_templates(self, cfg: ElasticConfig, mesh):
        """Sharded ShapeDtypeStructs for params+cache — no allocation.
        The param layout comes from the HMM (dense, or the pooled expert
        store whose pool/table shapes depend on ``cfg``)."""
        params_shape = self.hmm.params_template(cfg)
        pshard = self.hmm.param_shardings(params_shape, mesh)
        params_sds = jax.tree.map(
            lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=s),
            params_shape, pshard)
        cache_shape = self.hmm.cache_template(cfg)
        cshard = self.hmm.cache_shardings(cache_shape, mesh)
        cache_sds = jax.tree.map(
            lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=s),
            cache_shape, cshard)
        return params_sds, cache_sds

    # ------------------------------------------------------------ activate
    def activate(self, cfg: ElasticConfig, staged: bool = False):
        """Attach a standby instance to HMM memory (zero-copy).  Returns
        (instance, params, cache, was_preinitialized)."""
        key = self._key(cfg)
        hit = key in self._cache
        if hit:
            self.stats["preinit_hits"] += 1
        else:
            self.stats["preinit_misses"] += 1
        inst = self.preinitialize(cfg)
        inst.activations += 1
        if staged:
            scfg, _, params, cache = self.hmm.attach_staged()
            assert self._key(scfg) == key
        else:
            acfg, _, params, cache = self.hmm.attach_active()
            assert self._key(acfg) == key
        return inst, params, cache, hit
