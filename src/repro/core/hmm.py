"""HBM Management Module (paper §4.4) — the decoupled memory layer.

The HMM owns model weights and KV caches *independently of inference
instances*.  Weights live as per-device buffers; an instance receives
assembled global ``jax.Array`` views built with
``jax.make_array_from_single_device_arrays``, which **aliases** the existing
per-device buffers — the JAX-native zero-copy handle (Ascend IPC in the
paper).

``scale()`` implements the paper's minimal-cost reconfiguration:
* shards whose (content, device) are unchanged are *reused* (zero-copy),
* shards that exist on another device are moved with ``jax.device_put``
  (device-to-device DMA — the p2p-copy primitive),
* expert banks are re-grouped at page (single-expert) granularity so only
  migrated experts cross devices — and with ``expert_mode='pooled'`` the
  page pools + tables ARE the weight representation: scaling migrates
  exactly the min-move Migration list and commit only swaps tables
  (vpage-remap; see expert_pages.py for the O(1) table mechanics and
  DESIGN.md §2 for the pooled store / dense-buffer history),
* KV caches of surviving DP replicas are reused as-is; new replicas get
  zero-initialized state.

Byte accounting (zero_copy / p2p / local / init) is exact and is asserted
against the logical planner (scaling_plan.py) in tests.

Staging runs in one of two modes (DESIGN.md §3): ``staging="serial"`` (the
default) moves one tensor per ``stage_increment`` call on the caller's
thread; ``staging="overlap"`` submits the whole work list to a background
``TransferEngine`` (core/transfer.py) at ``begin_scale`` and the caller
polls completion with ``poll_staging`` — same bytes, field-by-field equal
``TransferStats``, strictly less wall-clock because decode ticks run
concurrently with the transfers instead of between them.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.expert_pages import ExpertPageTable
from repro.core.topology import ElasticConfig


def _idx_key(index) -> tuple:
    return tuple((s.start, s.stop, s.step) for s in index)


@dataclasses.dataclass
class TransferStats:
    zero_copy_bytes: int = 0
    p2p_bytes: int = 0
    local_bytes: int = 0
    init_bytes: int = 0
    zero_copy_count: int = 0
    p2p_count: int = 0
    wall_s: float = 0.0
    # Σ per-transfer-op execution time.  Serial staging: ~= the transfer
    # share of wall_s.  Overlapped staging: ops run concurrently on the
    # TransferEngine, so op_s / wall_s > 1 is the measured overlap
    # efficiency (metrics.summarize).  Timing fields (wall_s, op_s) are
    # excluded from the serial-vs-overlap byte-equality assertions.
    op_s: float = 0.0
    # expert-weight sub-accounting (included in the totals above): what the
    # vpage remap moved vs reused — pooled mode asserts expert_p2p_bytes ==
    # sum of Migration page sizes, and commit adds zero to it
    expert_p2p_bytes: int = 0
    expert_zero_copy_bytes: int = 0
    expert_local_bytes: int = 0
    # skew-rebalance sub-accounting (DESIGN.md §10).  replica: bytes copied
    # to create extra device copies of hot experts; d2h: cold-expert bytes
    # demoted into the pinned-host tier; h2d: host-tier bytes streamed back
    # to devices at scale events (replaces expert P2P for the cold set —
    # these rows are deliberately NOT counted in p2p_bytes).
    expert_replica_bytes: int = 0
    expert_d2h_bytes: int = 0
    expert_h2d_bytes: int = 0
    # whole-model pinned-host tier (scale-to-zero, DESIGN.md §12).  d2h:
    # bytes park() snapshots host-side; h2d: bytes unpark streams back to
    # devices.  The expert_* fields above remain the per-page
    # sub-accounting; these count every bank — attention, dense MLP,
    # embeddings, experts — of the parked model.
    d2h_bytes: int = 0
    h2d_bytes: int = 0

    #: the additive byte/count fields that must agree exactly between
    #: staging="serial" and staging="overlap" (same reshard calls, same
    #: bytes — only wall-clock differs); tests iterate this list
    BYTE_FIELDS = ("zero_copy_bytes", "p2p_bytes", "local_bytes",
                   "init_bytes", "zero_copy_count", "p2p_count",
                   "expert_p2p_bytes", "expert_zero_copy_bytes",
                   "expert_local_bytes", "expert_replica_bytes",
                   "expert_d2h_bytes", "expert_h2d_bytes",
                   "d2h_bytes", "h2d_bytes")

    def merge(self, o: "TransferStats"):
        self.zero_copy_bytes += o.zero_copy_bytes
        self.p2p_bytes += o.p2p_bytes
        self.local_bytes += o.local_bytes
        self.init_bytes += o.init_bytes
        self.zero_copy_count += o.zero_copy_count
        self.p2p_count += o.p2p_count
        self.wall_s += o.wall_s
        self.op_s += o.op_s
        self.expert_p2p_bytes += o.expert_p2p_bytes
        self.expert_zero_copy_bytes += o.expert_zero_copy_bytes
        self.expert_local_bytes += o.expert_local_bytes
        self.expert_replica_bytes += o.expert_replica_bytes
        self.expert_d2h_bytes += o.expert_d2h_bytes
        self.expert_h2d_bytes += o.expert_h2d_bytes
        self.d2h_bytes += o.d2h_bytes
        self.h2d_bytes += o.h2d_bytes


def make_instance_mesh(cfg: ElasticConfig, all_devices=None) -> Mesh:
    devs = all_devices or jax.devices()
    grid = np.array([devs[i] for i in cfg.devices]).reshape(cfg.dp, cfg.tp)
    return Mesh(grid, ("dp", "tp"))


# --------------------------------------------------------- reshard-with-reuse

def reshard_with_reuse(arr: jax.Array, new_sharding: NamedSharding,
                       stats: TransferStats,
                       expert_dim: Optional[int] = None) -> jax.Array:
    """Rebuild ``arr`` under ``new_sharding`` reusing existing per-device
    buffers wherever the required shard already lives on the right device.

    ``expert_dim``: if set, allows piecewise assembly along that dim at
    single-row ("page") granularity when slice boundaries change.
    """
    shape = arr.shape
    old = {}
    for sh in arr.addressable_shards:
        old.setdefault(_idx_key(sh.index), []).append((sh.device, sh.data))

    target = new_sharding.devices_indices_map(shape)
    out = []
    for dev in new_sharding.addressable_devices:
        index = target[dev]
        key = _idx_key(index)
        holders = old.get(key, [])
        same = [d for d in holders if d[0] == dev]
        if same:
            data = same[0][1]
            stats.zero_copy_bytes += data.nbytes
            stats.zero_copy_count += 1
        elif holders:
            src_dev, src_data = holders[0]
            data = jax.device_put(src_data, dev)
            stats.p2p_bytes += src_data.nbytes
            stats.p2p_count += 1
        elif expert_dim is not None:
            data = _assemble_rows(arr, index, expert_dim, dev, stats)
        else:
            raise ValueError(f"no source for shard {key} of {shape}")
        out.append(data)
    return jax.make_array_from_single_device_arrays(shape, new_sharding, out)


def _assemble_rows(arr, index, dim, dev, stats: TransferStats):
    """Piecewise (per-page) assembly of one target shard along ``dim``.

    Pure memory ops only: pieces are sliced/concatenated host-side with
    numpy and shipped with one ``jax.device_put`` — no jit-compiled
    primitives (slice/concatenate executables), so this is safe on
    TransferEngine worker threads concurrently with main-thread tracing
    and compilation (core/transfer.py).  Byte accounting is unchanged:
    the same sub-slices are counted local vs p2p."""
    want = index[dim]
    lo = want.start or 0
    hi = want.stop if want.stop is not None else arr.shape[dim]
    pieces = []
    for sh in arr.addressable_shards:
        s = sh.index[dim]
        slo = s.start or 0
        shi = s.stop if s.stop is not None else arr.shape[dim]
        olo, ohi = max(lo, slo), min(hi, shi)
        if olo >= ohi:
            continue
        data = np.asarray(sh.data)
        if (olo - slo, ohi - slo) != (0, shi - slo):
            sub = data[(slice(None),) * dim
                       + (slice(olo - slo, ohi - slo),)]
        else:
            sub = data
        if sh.device == dev:
            stats.local_bytes += sub.nbytes
        else:
            stats.p2p_bytes += sub.nbytes
            stats.p2p_count += 1
        pieces.append((olo, sub))
    pieces.sort(key=lambda t: t[0])
    out = pieces[0][1] if len(pieces) == 1 else \
        np.concatenate([p for _, p in pieces], axis=dim)
    return jax.device_put(out, dev)


# ---------------------------------------------------------------------- HMM

class HMM:
    """Holds weights + KV caches; instances attach via zero-copy handles.

    ``kv_mode='paged'``: the KV cache is a block pool ``[L, NB, bs, ...]``
    partitioned per DP replica (block axis sharded over 'dp'), and the HMM
    also owns the host-side :class:`~repro.serving.kv_blocks.KVBlockManager`.
    ``commit`` grows/shrinks the pool by whole partitions: surviving
    partitions' shards are reused zero-copy (same device, same shard index),
    so every live block table stays valid verbatim across the scale event —
    the KV-side vpage-remap (DESIGN.md §7).

    ``expert_mode='pooled'`` (MoE models; DESIGN.md §2): each expert weight
    bank lives as a per-device page *pool* — one global array
    ``[ndev * pages_per_device, D, F]``, page axis sharded one fixed-size
    slice per device — plus the ``ExpertPageTable``-derived index arrays
    (``core/expert_pages.pooled_layout``) that the pooled MoE execution path
    consumes.  Scaling then migrates exactly the ``stage_remap(min_move=
    True)`` Migration list (one ``jax.device_put`` per page, accounted in
    ``TransferStats.expert_p2p_bytes``) and ``commit`` is an O(table) swap:
    no expert-bank reshard, no ``_assemble_rows`` concatenation, no weight
    bytes at switchover.  The dense layout stays the default.
    """

    def __init__(self, mcfg: ModelConfig, tp: int, *,
                 batch_per_replica: int, max_len: int,
                 all_devices=None, seed: int = 0,
                 kv_mode: str = "dense", kv_block_size: int = 16,
                 kv_blocks_per_replica: Optional[int] = None,
                 expert_mode: str = "dense",
                 expert_pool_pages: Optional[int] = None,
                 expert_slot_slack: int = 0,
                 expert_host_pages: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 expert_dtype: Optional[str] = None,
                 staging: str = "serial", transfer_workers: int = 4):
        self.mcfg = mcfg
        self.tp = tp
        self.batch_per_replica = batch_per_replica
        self.max_len = max_len
        self.all_devices = list(all_devices or jax.devices())
        self.seed = seed
        assert kv_mode in ("dense", "paged")
        assert expert_mode in ("dense", "pooled")
        # staging="serial": stage_increment() moves one tensor per call on
        # the caller's thread (byte-exact legacy path, the default).
        # staging="overlap": begin_scale submits the whole work list to a
        # background TransferEngine and callers poll_staging()/join_staging()
        # — same bytes, less wall-clock (DESIGN.md §3).
        assert staging in ("serial", "overlap")
        self.staging_mode = staging
        self.transfer_workers = transfer_workers
        self._transfer = None            # TransferEngine, created lazily
        self._stage_session = None       # TransferSession (overlap only)
        self._stage_lock = threading.Lock()
        self._stage_t0 = 0.0
        if expert_mode == "pooled":
            assert mcfg.is_moe, \
                f"{mcfg.name}: expert_mode='pooled' requires a MoE model"
        self.expert_mode = expert_mode
        # Quantized storage knobs (ISSUE 9).  ``kv_dtype='int8'`` stores the
        # paged KV pool as int8 entries plus per-token-row f32 scale pools
        # that ride the same block axis (remap/migration correctness by
        # construction); ``expert_dtype='int8'`` stores the pooled expert
        # banks as int8 pages plus per-page f32 scale banks addressed by the
        # same page table.  None keeps the model dtype (f32 parity oracle).
        assert kv_dtype in (None, "int8"), kv_dtype
        assert expert_dtype in (None, "int8"), expert_dtype
        if kv_dtype is not None:
            assert kv_mode == "paged", \
                "kv_dtype='int8' requires kv_mode='paged' (block-wise scales)"
        if expert_dtype is not None:
            assert expert_mode == "pooled", \
                "expert_dtype='int8' requires expert_mode='pooled' " \
                "(per-page scales live beside the pool banks)"
        self.kv_dtype = kv_dtype
        self.expert_dtype = expert_dtype
        # per-device pool capacity in pages ((layer, expert) granularity,
        # one free list per device); None resolves at boot to twice the boot
        # config's per-device expert load — headroom for staging (active +
        # migrated-in pages coexist until commit) and for scaling down to
        # half the boot device count.  Scaling below that raises a clear
        # MemoryError from the page allocator: pass a larger value here.
        self.expert_pool_pages: Optional[int] = expert_pool_pages
        # extra compiled table-width slots per rank beyond ceil(E/ndev):
        # replication headroom for the skew rebalancer (DESIGN.md §10).
        # The width is AOT-baked into every pooled executable, so it is
        # fixed for the HMM's lifetime; 0 keeps shapes byte-identical to
        # the pre-rebalance layout (and forbids net replica skew that
        # would overflow a rank's table).
        self.expert_slot_slack = int(expert_slot_slack)
        # pinned-host cold tier capacity in pages (None: ExpertPageTable
        # default — every (layer, expert) once, the scale-to-zero limit)
        self.expert_host_pages = expert_host_pages
        # host-side bytes of demoted experts: (layer, expert) -> {bank: row}.
        # The page table accounts the tier; this dict holds the bytes.
        self._expert_host_pool: Dict[Tuple[int, int],
                                     Dict[str, np.ndarray]] = {}
        # whole-model pinned-host tier (scale-to-zero, DESIGN.md §12):
        # park() snapshots every bank here and releases the devices;
        # begin_unpark() streams it back through the staging session.
        self._parked: Optional[Dict[str, Any]] = None
        self._unpark = False                  # current session is an unpark
        self._unpark_table = None             # fresh page table for unpark
        # rebalance session state (begin_rebalance/.../abort_rebalance)
        self._rebalance_ops = None       # List[RebalanceOp]
        self._rebalance_session = None   # TransferSession
        self._rebalance_stats: Optional[TransferStats] = None
        self._rebalance_load = None      # [L_moe, E] routing snapshot
        self._rebalance_t0 = 0.0
        self.last_rebalance_stats: Optional[TransferStats] = None
        self.kv_mode = kv_mode
        self.kv_block_size = kv_block_size
        if kv_mode == "paged":
            from repro.models.model import paged_cache_supported
            assert paged_cache_supported(mcfg), \
                f"{mcfg.name} does not support the paged KV layout"
            assert max_len % kv_block_size == 0
            # dense-equivalent capacity by default; pressure experiments
            # pass a smaller pool to force preemption
            self.kv_blocks_per_replica = (
                kv_blocks_per_replica
                or batch_per_replica * (max_len // kv_block_size))
        else:
            self.kv_blocks_per_replica = 0
        self.kv_blocks = None  # KVBlockManager, created at boot (paged only)
        self.active_cfg: Optional[ElasticConfig] = None
        self.params: Any = None
        self.cache: Any = None
        self.staged: Optional[Tuple] = None
        if mcfg.is_moe:
            self.page_table = ExpertPageTable(
                mcfg.num_layers - mcfg.first_k_dense, mcfg.num_experts,
                pool_pages_per_device=(self.expert_pool_pages or 0
                                       if expert_mode == "pooled" else 0),
                host_pool_pages=self.expert_host_pages)
        else:
            self.page_table = None
        self.last_stats: Optional[TransferStats] = None
        self.last_migrations: Optional[List] = None  # pooled: last staged set
        # incremental staging session (begin_scale / stage_increment)
        self._stage_work: Optional[List[Tuple]] = None
        self._stage_cursor = 0
        self._stage_out: List[Any] = []
        self._stage_treedef = None
        self._stage_target: Optional[Tuple] = None
        self._stage_stats: Optional[TransferStats] = None
        self._stage_layout: Optional[Dict[str, np.ndarray]] = None

    # ----------------------------------------------------------- shardings
    def param_shardings(self, params, mesh: Mesh):
        """TP over 'tp'; experts over ('dp','tp') = EP; rest replicated over
        'dp' (attention replicas)."""
        def spec(path_tuple, leaf):
            path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in path_tuple)
            shape = leaf.shape
            stacked = 1 if ("blocks/" in path or "cross_blocks/" in path) else 0
            ntp = mesh.shape["tp"]
            nep = mesh.shape["dp"] * mesh.shape["tp"]
            s = [None] * len(shape)
            import re
            if re.search(r"moe/w[igo]$", path):
                if shape[stacked] % nep == 0:
                    s[stacked] = ("dp", "tp")
                return P(*s)
            # pooled expert store: page pools carved one slice per device
            # (quantized scale banks shard the same page axis); per-layer
            # kernel tables one row per device; the other index arrays
            # (edest/eslot/gtable) replicated like the router
            if re.search(r"moe_pool/w[igo](_scale)?$", path):
                if shape[0] % nep == 0:
                    s[0] = ("dp", "tp")
                return P(*s)
            if re.search(r"moe/tables$", path):
                if shape[stacked] % nep == 0:
                    s[stacked] = ("dp", "tp")
                return P(*s)
            if re.search(r"moe/(edest|eslot|gtable)$", path):
                return P(*s)
            rules = [
                (r"attn/q/w$|attn/q_up/w$|xattn/q/w$", stacked + 1),
                (r"attn/(k|v)/w$|xattn/(k|v)/w$", stacked + 1),
                (r"attn/o/w$|xattn/o/w$", stacked + 0),
                (r"attn/(k|v)_up/w$", stacked + 1),
                (r"(mlp|shared)/(up|gate)/w$", stacked + 1),
                (r"(mlp|shared)/down/w$", stacked + 0),
                (r"lm_head/w$", 1),
                (r"embed$", 0),
            ]
            for pat, dim in rules:
                if re.search(pat, path) and dim < len(shape) \
                        and shape[dim] % ntp == 0 and shape[dim] >= ntp:
                    s[dim] = "tp"
                    return P(*s)
            return P(*s)
        specs = jax.tree_util.tree_map_with_path(spec, params)
        return jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs)

    def cache_shardings(self, cache, mesh: Mesh):
        def spec(path_tuple, leaf):
            # [L, B, ...]: batch over 'dp'
            s = [None] * leaf.ndim
            if leaf.ndim >= 2 and leaf.shape[1] % mesh.shape["dp"] == 0:
                s[1] = "dp"
            return P(*s)
        specs = jax.tree_util.tree_map_with_path(spec, cache)
        return jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs)

    def make_cache(self, cfg: ElasticConfig):
        """Freshly initialized decode cache for ``cfg`` (dense rows or the
        paged block pool, per ``kv_mode``)."""
        from repro.models.model import init_cache, init_paged_cache
        if self.kv_mode == "paged":
            return init_paged_cache(
                self.mcfg, cfg.dp * self.kv_blocks_per_replica,
                self.kv_block_size, kv_dtype=self.kv_dtype)
        return init_cache(self.mcfg, cfg.dp * self.batch_per_replica,
                          self.max_len)

    def cache_template(self, cfg: ElasticConfig):
        """Shape/dtype pytree of the cache for ``cfg`` (no allocation)."""
        return jax.eval_shape(lambda: self.make_cache(cfg))

    # -------------------------------------------------- pooled expert store
    @property
    def _n_moe_layers(self) -> int:
        return self.mcfg.num_layers - self.mcfg.first_k_dense

    def expert_page_nbytes(self) -> int:
        """Bytes of ONE (layer, expert) page across all three banks — the
        unit of vpage migration accounting.  Quantized pools count the int8
        entries plus the three per-page f32 scales that travel with them."""
        from repro.core.costmodel import dtype_bytes
        bpe = dtype_bytes(self.expert_dtype or self.mcfg.dtype)
        scale = 3 * 4 if self.expert_dtype is not None else 0
        return 3 * self.mcfg.d_model * self.mcfg.moe_d_ff * bpe + scale

    def _pooled_index_arrays(self, table, cfg: ElasticConfig,
                             replicas=None, load=None):
        """Host index arrays for the pooled MoE path from a page-table dict.
        ``replicas``/``load``: least-loaded replica-aware serving assignment
        (expert_pages.pooled_layout); scale staging passes neither — the
        staged table already names each expert's kept copy."""
        import math as _math
        from repro.core.expert_pages import pooled_layout
        elm = (_math.ceil(self.mcfg.num_experts / cfg.ndev)
               + self.expert_slot_slack)
        return pooled_layout(table, cfg, self._n_moe_layers,
                             self.mcfg.num_experts, self.expert_pool_pages,
                             replicas=replicas, load=load,
                             slots_per_rank=elm)

    def _pooled_host_params(self, params, cfg: ElasticConfig):
        """Convert freshly initialized dense params to the pooled layout:
        scatter each (layer, expert) bank into its ``initial_place`` page and
        replace the dense [L, E, D, F] banks with index arrays + one global
        pool per bank.  Host-side; the caller device_puts the result."""
        moe = params["blocks"]["moe"]
        banks = {k: np.asarray(moe.pop(k)) for k in ("wi", "wg", "wo")}
        ppd = self.expert_pool_pages
        scales: Dict[str, np.ndarray] = {}
        if self.expert_dtype is not None:
            # symmetric per-page int8: one f32 scale per (layer, expert)
            # page, stored in sidecar banks addressed by the same table
            from repro.kernels.quant import quantize_rows
            for k in list(banks):
                q, s = quantize_rows(jnp.asarray(banks[k]), (-2, -1))
                banks[k] = np.asarray(q)
                scales[k] = np.asarray(s, np.float32)
        pools = {k: np.zeros((cfg.ndev * ppd,) + b.shape[2:], b.dtype)
                 for k, b in banks.items()}
        for k in scales:
            pools[k + "_scale"] = np.zeros((cfg.ndev * ppd,), np.float32)
        for (l, e), ref in self.page_table.active.items():
            row = cfg.slot(ref.device) * ppd + ref.page
            for k in banks:
                pools[k][row] = banks[k][l, e]
            for k in scales:
                pools[k + "_scale"][row] = scales[k][l, e]
        moe.update(self._pooled_index_arrays(self.page_table.active, cfg))
        params["moe_pool"] = pools
        return params

    def params_template(self, cfg: ElasticConfig):
        """Shape/dtype pytree of the parameters an instance for ``cfg``
        binds (dense layout, or the pooled expert store) — what the IMM
        AOT-compiles against, no allocation."""
        from repro.models.model import init_params
        dense = jax.eval_shape(
            lambda: init_params(self.mcfg, jax.random.PRNGKey(0),
                                jnp.dtype(self.mcfg.dtype)))
        if self.expert_mode != "pooled":
            return dense
        if self.expert_pool_pages is None:
            raise RuntimeError(
                "pooled parameter shapes are fixed by the boot config's "
                "pool size — boot() the HMM (or pass expert_pool_pages) "
                "before pre-initializing instances")
        import math as _math
        mcfg = self.mcfg
        moe = dense["blocks"]["moe"]
        shapes = {k: moe.pop(k).shape for k in ("wi", "wg", "wo")}
        dt = jnp.dtype(mcfg.dtype)
        ppd = self.expert_pool_pages
        L, E = self._n_moe_layers, mcfg.num_experts
        elm = _math.ceil(E / cfg.ndev) + self.expert_slot_slack
        i32 = jnp.dtype(jnp.int32)
        moe["tables"] = jax.ShapeDtypeStruct((L, cfg.ndev, elm), i32)
        for k in ("edest", "eslot", "gtable"):
            moe[k] = jax.ShapeDtypeStruct((L, E), i32)
        if self.expert_dtype is not None:
            dt = jnp.dtype(self.expert_dtype)
        dense["moe_pool"] = {
            k: jax.ShapeDtypeStruct((cfg.ndev * ppd,) + shapes[k][2:], dt)
            for k in shapes}
        if self.expert_dtype is not None:
            for k in shapes:
                dense["moe_pool"][k + "_scale"] = jax.ShapeDtypeStruct(
                    (cfg.ndev * ppd,), jnp.dtype(jnp.float32))
        return dense

    # ----------------------------------------------------------------- boot
    @obs.traced("hmm.boot", cat="hmm")
    def boot(self, cfg: ElasticConfig) -> TransferStats:
        """First boot: 'disk load' = host init + device_put (counted as disk
        bytes by the caller's cost model)."""
        from repro.models.model import init_params
        t0 = time.perf_counter()
        assert cfg.tp == self.tp
        mesh = make_instance_mesh(cfg, self.all_devices)
        # one executable: the random draws and their scaling fuse, so the
        # default device holds the weights without per-op temporaries
        params = jax.jit(init_params, static_argnums=(0, 2))(
            self.mcfg, jax.random.PRNGKey(self.seed),
            jnp.dtype(self.mcfg.dtype))
        if self.expert_mode == "pooled" and self.expert_pool_pages is None:
            # fixed for the HMM's lifetime: page indices and pool shapes
            # must agree across every later scale event
            per_dev = self._n_moe_layers * (
                -(-self.mcfg.num_experts // cfg.ndev))
            self.expert_pool_pages = min(
                2 * per_dev, self._n_moe_layers * self.mcfg.num_experts)
            self.page_table.pool_pages = self.expert_pool_pages
        if self.page_table is not None and not self.page_table.active:
            self.page_table.initial_place(cfg)
        if self.expert_mode == "pooled":
            params = self._pooled_host_params(params, cfg)
        shardings = self.param_shardings(params, mesh)
        self.params = jax.tree.map(
            lambda x, s: jax.device_put(x, s), params, shardings)
        cache = self.make_cache(cfg)
        cshard = self.cache_shardings(cache, mesh)
        self.cache = jax.tree.map(lambda x, s: jax.device_put(x, s),
                                  cache, cshard)
        self.active_cfg = cfg
        if self.kv_mode == "paged" and self.kv_blocks is None:
            from repro.serving.kv_blocks import KVBlockManager
            self.kv_blocks = KVBlockManager(cfg.dp,
                                            self.kv_blocks_per_replica,
                                            self.kv_block_size)
        st = TransferStats(wall_s=time.perf_counter() - t0)
        self.last_stats = st
        return st

    # ---------------------------------------------------------------- scale
    def scale(self, new_cfg: ElasticConfig) -> TransferStats:
        """Stage the new configuration's *weights* while the old instance
        keeps serving (the expensive, concurrent part: zero-copy reuse +
        P2P transfers + expert-page remap).  KV-cache growth is deferred to
        ``commit`` — the cache keeps being written by the live instance and,
        per the paper (§5.2), is handed over *shared*, never copied.

        Monolithic wrapper over the incremental/async API (``begin_scale``
        then ``stage_increment`` loop or ``join_staging``, per the staging
        mode).  Byte accounting is identical either way — the same reshard
        calls execute, only the thread they run on differs (asserted in
        tests).

        Returns transfer stats; staged params are attached by the IMM via
        ``attach_staged`` and made active by ``commit``."""
        self.begin_scale(new_cfg)
        if self.staging_mode == "overlap":
            self.join_staging()
        else:
            while self.stage_increment():
                pass
        return self.last_stats

    @obs.traced("hmm.begin_scale", cat="hmm")
    def begin_scale(self, new_cfg: ElasticConfig) -> int:
        """Open a staging session toward ``new_cfg``.

        Builds the per-tensor work list (one unit per parameter leaf — the
        per-layer chunk analogue under this repo's stacked-block layout) and
        returns the number of work units.

        * ``staging="serial"``: no bytes move yet; drive the units with
          ``stage_increment`` — the engine may run decode ticks between
          calls (the legacy tick-interleaved path).
        * ``staging="overlap"``: every unit is submitted to the background
          ``TransferEngine`` immediately and starts moving bytes off-thread;
          drive completion with the non-blocking ``poll_staging`` (or block
          on ``join_staging``).  Staging only *reads* immutable live
          weights, so serving ticks concurrent with in-flight ops are safe
          by construction (core/transfer.py).

        Pooled expert mode stages the page remap here (``stage_remap(
        min_move=True)``) so the pool-bank work units know the exact
        Migration list; each pool bank then moves only those pages.
        """
        assert self.active_cfg is not None
        assert self._stage_work is None, "staging already in progress"
        assert new_cfg.tp == self.tp, "TP is fixed during scaling (§4.1)"
        import re
        t0 = time.perf_counter()
        mesh = make_instance_mesh(new_cfg, self.all_devices)
        if self.expert_mode == "pooled":
            self.last_migrations = self.page_table.stage_remap(
                new_cfg, min_move=True)
            # one layout pass per session; the index work units each pick
            # their array out of it
            self._stage_layout = self._pooled_index_arrays(
                self.page_table.staged, new_cfg)
        shardings = self.param_shardings(self.params, mesh)
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.params)
        shard_leaves = jax.tree.leaves(shardings)
        work = []
        for (path_tuple, leaf), sh in zip(flat, shard_leaves):
            path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in path_tuple)
            kind, expert_dim = "reshard", None
            if re.search(r"moe/w[igo]$", path):
                stacked = 1 if "blocks/" in path else 0
                expert_dim = stacked  # regroup experts at page granularity
                kind = "expert_bank"
            elif re.search(r"moe_pool/(w[igo](?:_scale)?)$", path):
                kind = "pool:" + path.rsplit("/", 1)[1]
            elif re.search(r"moe/(tables|edest|eslot|gtable)$", path):
                kind = "index:" + path.rsplit("/", 1)[1]
            work.append((path, leaf, sh, expert_dim, kind))
        self._stage_work = work
        self._stage_cursor = 0
        self._stage_out = []
        self._stage_treedef = treedef
        self._stage_target = (new_cfg, mesh)
        # prep (mesh + shardings + tree walk) counts toward staged wall time,
        # matching the pre-incremental scale() accounting
        self._stage_stats = TransferStats(wall_s=time.perf_counter() - t0)
        if self.staging_mode == "overlap":
            from repro.core.transfer import TransferOp
            self._stage_t0 = t0
            ops = [TransferOp(index=i, label=path,
                              fn=self._make_stage_op(leaf, sh, expert_dim,
                                                     kind, new_cfg, mesh))
                   for i, (path, leaf, sh, expert_dim, kind)
                   in enumerate(work)]
            self._stage_session = self.transfer_engine().submit(ops)
        return len(work)

    def transfer_engine(self):
        """The HMM's background TransferEngine (created lazily, persistent
        across scale events).  Staging ops ride it with
        ``staging="overlap"``; the engine's live KV-block migration copies
        ride it in EVERY staging mode — migration is asynchronous by
        design (DESIGN.md §3, §7)."""
        if self._transfer is None:
            from repro.core.transfer import TransferEngine
            self._transfer = TransferEngine(self.transfer_workers)
        return self._transfer

    @property
    def staging_remaining(self) -> int:
        if self._stage_work is None:
            return 0
        if self._stage_session is not None:
            return self._stage_session.remaining()
        return len(self._stage_work) - self._stage_cursor

    @property
    def staging_in_flight(self) -> bool:
        """True while an overlapped session has transfer ops still pending
        or running on the background engine."""
        return (self._stage_session is not None
                and not self._stage_session.finished())

    def _stage_unit(self, leaf, sh, expert_dim, kind,
                    new_cfg: ElasticConfig, mesh, stats: TransferStats):
        """Execute ONE unit of staging work; returns the staged leaf and
        accumulates byte/count accounting into ``stats``.  Shared verbatim
        by the serial path (caller thread) and the overlapped path
        (TransferEngine workers) so the two modes cannot drift."""
        if kind == "unpark":
            # whole-model cold start: the leaf is a pinned-host array —
            # stream it to its device placement (the H2D lane, priced at
            # hw.h2d_bw by the cost model)
            return self._put_host_leaf(leaf, sh, stats)
        if kind.startswith("pool:"):
            return self._migrate_pool_bank(leaf, new_cfg, mesh, stats,
                                           bank=kind.split(":", 1)[1])
        if kind.startswith("index:"):
            # O(table): the staged index arrays were rebuilt once in
            # begin_scale — no weight bytes move here (host numpy ->
            # device_put, no compiled primitives: worker-thread safe)
            name = kind.split(":", 1)[1]
            arr = np.asarray(self._stage_layout[name], np.int32)
            spec = (P(None, ("dp", "tp"), None) if name == "tables"
                    else P())
            return jax.device_put(arr, NamedSharding(mesh, spec))
        if kind == "expert_bank":
            # dense mode: piecewise regroup; track the expert sub-bytes
            # so dense-reshard vs pooled-remap is directly comparable
            sub = TransferStats()
            out = reshard_with_reuse(leaf, sh, sub, expert_dim=expert_dim)
            sub.expert_p2p_bytes = sub.p2p_bytes
            sub.expert_zero_copy_bytes = sub.zero_copy_bytes
            sub.expert_local_bytes = sub.local_bytes
            stats.merge(sub)
            return out
        return reshard_with_reuse(leaf, sh, stats, expert_dim=expert_dim)

    def _make_stage_op(self, leaf, sh, expert_dim, kind,
                       new_cfg: ElasticConfig, mesh):
        """Closure for one background TransferOp: runs ``_stage_unit`` with
        a private TransferStats, then merges it into the session stats under
        the lock (thread-safe accumulation; addition commutes, so the final
        totals are byte-identical to the serial order)."""
        session_stats = self._stage_stats

        def run():
            sub = TransferStats()
            t0 = time.perf_counter()
            out = self._stage_unit(leaf, sh, expert_dim, kind, new_cfg,
                                   mesh, sub)
            sub.op_s = time.perf_counter() - t0
            with self._stage_lock:
                session_stats.merge(sub)
            return out

        return run

    @obs.traced("hmm.stage_increment", cat="hmm")
    def stage_increment(self, max_tensors: int = 1) -> bool:
        """Serial mode: reshard up to ``max_tensors`` parameter tensors
        toward the target opened by ``begin_scale``.  Safe to interleave
        with serving: staging only *reads* live params (weights are
        immutable during serving; the KV cache is not touched until
        ``commit``).

        Returns True while more increments remain; on the last increment the
        staged tree is assembled, the expert-page remap is staged, and
        ``attach_staged``/``commit`` become legal.

        With ``staging="overlap"`` the work already runs on the background
        TransferEngine — use ``poll_staging``/``join_staging`` instead."""
        assert self._stage_work is not None, "no staging session open"
        if self._stage_session is not None:
            raise RuntimeError(
                "staging session is overlapped (background TransferEngine); "
                "drive it with poll_staging()/join_staging(), not "
                "stage_increment()")
        t0 = time.perf_counter()
        stats = self._stage_stats
        new_cfg, mesh = self._stage_target
        end = min(self._stage_cursor + max(1, max_tensors),
                  len(self._stage_work))
        for path, leaf, sh, expert_dim, kind in self._stage_work[
                self._stage_cursor:end]:
            u0 = time.perf_counter()
            self._stage_out.append(
                self._stage_unit(leaf, sh, expert_dim, kind, new_cfg, mesh,
                                 stats))
            stats.op_s += time.perf_counter() - u0
        self._stage_cursor = end
        stats.wall_s += time.perf_counter() - t0
        if self._stage_cursor < len(self._stage_work):
            return True
        self._finalize_staging()
        return False

    def poll_staging(self) -> bool:
        """Overlap mode: bounded completion poll (<= ~2 ms).  Returns True
        once every background transfer op has finished AND the staged tree
        has been assembled (``attach_staged``/``commit`` legal); False
        while ops are still in flight.  A failed op aborts the whole
        session (staged pages unwound) and re-raises.

        The poll donates a tiny bounded wait rather than returning
        instantly: a serve loop spinning on an *idle* engine is a pure
        Python busy-loop that would otherwise starve the worker threads of
        the GIL (real decode ticks release it inside XLA, so a busy engine
        needs no such courtesy)."""
        if self._stage_work is None:
            return self.staged is not None
        if self._stage_session is None:
            raise RuntimeError(
                "staging session is serial; drive it with stage_increment()")
        sess = self._stage_session
        if not sess.finished():
            sess.join(timeout=0.002)   # bounded yield to the workers
            if not sess.finished():
                return False
        failed = sess.failed_ops()
        if failed:
            err = failed[0].error
            self.abort()
            raise RuntimeError(
                f"staging transfer op {failed[0].label!r} failed "
                f"({len(failed)} op(s) total); session aborted") from err
        self._stage_out = [op.result for op in sess.ops]
        # overlap wall-clock = begin_scale() -> last op completion: the
        # staging *window* the background engine shrinks (op_s holds the
        # serial-equivalent Σ of per-op times for the efficiency ratio)
        self._stage_stats.wall_s = max(sess.last_done_t - self._stage_t0,
                                       self._stage_stats.wall_s)
        self._finalize_staging()
        return True

    def join_staging(self) -> bool:
        """Overlap mode: block until the session completes, then finalize
        (the COMMITTING/monolithic barrier).  Returns True if a staged tree
        is ready, False if no session was open and nothing is staged."""
        if self._stage_work is None:
            return self.staged is not None
        if self._stage_session is None:
            raise RuntimeError(
                "staging session is serial; drive it with stage_increment()")
        self._stage_session.join()
        return self.poll_staging()

    def _finalize_staging(self):
        """Assemble the staged tree + stage the page remap (dense
        bookkeeping only — pooled staged it in begin_scale; dense arrays
        take the contiguous expert_owner layout, so the table records
        min_move=False placement to stay truthful)."""
        t0 = time.perf_counter()
        stats = self._stage_stats
        new_cfg, mesh = self._stage_target
        new_params = jax.tree_util.tree_unflatten(
            self._stage_treedef, self._stage_out)
        if (self.page_table is not None and self.page_table.staged is None
                and not self._unpark):
            # unpark built a FRESH table (initial_place at the target) in
            # begin_unpark — there is no live placement to remap from
            self.page_table.stage_remap(new_cfg, min_move=False)
        self.staged = (new_cfg, mesh, new_params)
        stats.wall_s += time.perf_counter() - t0
        self.last_stats = stats
        self._reset_stage_session()

    def _migrate_pool_bank(self, leaf, new_cfg: ElasticConfig, mesh,
                           stats: TransferStats, bank: str = ""):
        """Rebuild one pooled weight bank for ``new_cfg``: surviving devices'
        pool slices are reused (migrated-in pages written at their staged
        slots), new devices start from zeros, and exactly the staged
        Migration list crosses devices — one ``jax.device_put`` per page,
        the paper's p2p-copy primitive at vpage granularity.  A migration
        whose ``src`` lives in the pinned-host tier (``src.device == HOST``)
        reads its row from the HMM host pool instead: those bytes ride the
        H2D path and are accounted in ``expert_h2d_bytes``, NOT
        ``p2p_bytes`` — the cold set costs zero expert P2P (DESIGN.md §10).

        Pure memory ops only (host numpy assembly + device_put, no compiled
        scatter/stack): worker-thread safe on the TransferEngine.  A device
        slice that receives no migrated pages keeps its live buffer — the
        zero-copy alias is preserved."""
        from repro.core.expert_pages import HOST
        ppd = self.expert_pool_pages
        row_shape = leaf.shape[1:]
        row_bytes = int(np.prod(row_shape)) * leaf.dtype.itemsize
        # keyed by physical device object: page-table/config device ints are
        # LOGICAL indices into all_devices, which need not be jax.devices()
        old_shard = {sh.device: sh.data for sh in leaf.addressable_shards}
        migs_by_dst: Dict[int, List] = defaultdict(list)
        for m in self.last_migrations:
            migs_by_dst[m.dst.device].append(m)
        # pages that stay put are this bank's zero-copy reuse — an expert
        # kept in place via any already-resident copy (primary OR replica)
        staged, active = self.page_table.staged, self.page_table.active
        replicas = self.page_table.replicas
        unchanged = sum(
            1 for k, r in active.items()
            if staged.get(k) == r or staged.get(k) in replicas.get(k, ()))
        stats.zero_copy_bytes += unchanged * row_bytes
        stats.zero_copy_count += unchanged
        stats.expert_zero_copy_bytes += unchanged * row_bytes

        shape = (new_cfg.ndev * ppd,) + row_shape
        sharding = NamedSharding(mesh, P(("dp", "tp"), *([None] *
                                                         len(row_shape))))
        target = sharding.devices_indices_map(shape)
        src_rows: Dict[int, np.ndarray] = {}   # host view of source slices

        def rows_of(logical_dev: int) -> np.ndarray:
            if logical_dev not in src_rows:
                src_rows[logical_dev] = np.asarray(
                    old_shard[self.all_devices[logical_dev]])
            return src_rows[logical_dev]

        out = []
        for dev in sharding.addressable_devices:
            rank = (target[dev][0].start or 0) // ppd
            logical = new_cfg.devices[rank]    # dev == all_devices[logical]
            local = old_shard.get(dev)
            migs = migs_by_dst.get(logical)
            if migs:
                base = (np.array(local) if local is not None
                        else np.zeros((ppd,) + row_shape, leaf.dtype))
                for m in migs:
                    if m.src.device == HOST:
                        base[m.dst.page] = \
                            self._expert_host_pool[(m.layer, m.expert)][bank]
                        stats.expert_h2d_bytes += row_bytes
                    else:
                        base[m.dst.page] = rows_of(m.src.device)[m.src.page]
                        stats.p2p_bytes += row_bytes
                        stats.p2p_count += 1
                        stats.expert_p2p_bytes += row_bytes
                local = jax.device_put(base, dev)
            elif local is None:
                local = jax.device_put(
                    np.zeros((ppd,) + row_shape, leaf.dtype), dev)
            out.append(local)
        return jax.make_array_from_single_device_arrays(shape, sharding, out)

    def _put_host_leaf(self, arr: np.ndarray, sh: NamedSharding,
                       stats: TransferStats):
        """Stream ONE pinned-host array to devices under ``sh`` — the unpark
        work unit.  Pure memory ops (numpy slicing + one ``jax.device_put``
        per device shard, no compiled primitives), so it is safe on
        TransferEngine worker threads concurrently with the IMM's AOT
        compile on the serve thread (STAGING ∥ COMPILING)."""
        arr = np.asarray(arr)
        shape = arr.shape
        target = sh.devices_indices_map(shape)
        out = []
        for dev in sh.addressable_devices:
            sub = np.ascontiguousarray(arr[target[dev]])
            stats.h2d_bytes += sub.nbytes
            out.append(jax.device_put(sub, dev))
        return jax.make_array_from_single_device_arrays(shape, sh, out)

    def _reset_stage_session(self):
        self._stage_work = None
        self._stage_cursor = 0
        self._stage_out = []
        self._stage_treedef = None
        self._stage_target = None
        self._stage_layout = None
        self._stage_session = None

    def _grow_cache(self, new_cfg: ElasticConfig, mesh: Mesh,
                    stats: TransferStats):
        """Reuse surviving replicas' KV shards; zero-init new replicas.

        Works unchanged for both layouts: dense rows shard the batch axis,
        the paged pool shards the block axis — either way surviving shards
        keep their (index, device) key and are adopted zero-copy."""
        template = self.cache_template(new_cfg)
        cshard = self.cache_shardings(template, mesh)

        def grow(old_leaf, tmpl, sh):
            shape = tmpl.shape
            target = sh.devices_indices_map(shape)
            old_by_idx = {}
            for s in old_leaf.addressable_shards:
                old_by_idx.setdefault(_idx_key(s.index), []).append(
                    (s.device, s.data))
            out = []
            for dev in sh.addressable_devices:
                key = _idx_key(target[dev])
                holders = old_by_idx.get(key, [])
                same = [h for h in holders if h[0] == dev]
                if same and same[0][1].shape == tuple(
                        (i.stop or shape[n]) - (i.start or 0)
                        for n, i in enumerate(target[dev])):
                    data = same[0][1]
                    stats.zero_copy_bytes += data.nbytes
                    stats.zero_copy_count += 1
                else:
                    shard_shape = tuple(
                        (i.stop if i.stop is not None else shape[n])
                        - (i.start or 0)
                        for n, i in enumerate(target[dev]))
                    data = jax.device_put(
                        jnp.zeros(shard_shape, tmpl.dtype), dev)
                    stats.init_bytes += data.nbytes
                out.append(data)
            return jax.make_array_from_single_device_arrays(shape, sh, out)

        return jax.tree.map(grow, self.cache, template, cshard)

    # --------------------------------------------------------------- attach
    def attach_staged(self):
        """Zero-copy handles for the staged instance (IMM open_tensor)."""
        assert self.staged is not None
        new_cfg, mesh, params = self.staged
        return new_cfg, mesh, params, self.cache

    def attach_active(self):
        return (self.active_cfg,
                make_instance_mesh(self.active_cfg, self.all_devices),
                self.params, self.cache)

    @obs.traced("hmm.commit", cat="hmm")
    def commit(self, live_cache=None) -> TransferStats:
        """Switchover: staged weights become active, and the *live* KV cache
        (surviving slots' buffers reused as-is, new slots zero-init) is grown
        to the new slot count.  Old-only buffers become unreferenced — the
        paper's deferred FREE.

        Overlap mode: committing is a barrier — any transfer ops still in
        flight are joined (and the tree finalized) before the switchover."""
        if self._stage_session is not None:
            self.join_staging()
        assert self.staged is not None
        new_cfg, mesh, params = self.staged
        stats = TransferStats()
        t0 = time.perf_counter()
        if self._unpark:
            return self._commit_unpark(new_cfg, mesh, params, stats, t0)
        if live_cache is not None:
            self.cache = live_cache
        self.cache = self._grow_cache(new_cfg, mesh, stats)
        if self.kv_blocks is not None:
            # pool partitions track DP replicas; block ids of survivors are
            # unchanged, so live block tables need no translation.  Shrink
            # is only legal once scale-down evacuation is complete (live
            # blocks migrated onto survivors or drained) — the manager
            # refuses while partitions hold blocks or migrations are
            # pending, so commit cannot strand a live sequence.
            if new_cfg.dp >= self.kv_blocks.num_partitions:
                self.kv_blocks.grow_partitions(new_cfg.dp)
            else:
                self.kv_blocks.shrink_partitions(new_cfg.dp)
        self.active_cfg = new_cfg
        self.params = params
        self.staged = None
        if self.page_table is not None and self.page_table.staged is not None:
            self.page_table.commit()
        stats.wall_s = time.perf_counter() - t0
        if self.last_stats is not None:
            self.last_stats.merge(stats)
        return stats

    @obs.traced("hmm.abort", cat="hmm")
    def abort(self):
        """Abandon any staged state — including a staging session with
        transfer ops still in flight on the background engine.

        Cancel-or-join: pending ops never start, running ops are joined
        *before* the page table unwinds, so no worker can observe the
        post-abort table.  Idempotent; leaves zero staged-page leaks
        (``ExpertPageTable.abort`` frees staged-only pages exactly once)."""
        if self._stage_session is not None:
            self._stage_session.cancel()
        self.staged = None
        self.last_migrations = None
        self._reset_stage_session()
        self._unpark = False
        self._unpark_table = None
        if self.page_table is not None:
            self.page_table.abort()

    # -------------------------------------------------------- scale-to-zero
    @obs.traced("hmm.park", cat="hmm")
    def park(self) -> TransferStats:
        """Scale to ZERO devices: snapshot EVERY weight bank into the
        pinned-host tier and drop all device state — the whole-model
        generalization of the PR-8 cold-expert host pool (DESIGN.md §12).

        Dense banks are pulled back as full logical host arrays; pooled
        expert banks are snapshotted per (layer, expert) page (already-
        demoted host-tier experts are absorbed from ``_expert_host_pool``
        without re-copying), so unpark can rebuild the pools at ANY target
        device count.  The KV cache is DISCARDED — park is only legal once
        in-flight sequences have drained (asserted by the callers); unpark
        allocates a fresh pool.

        Requires no staging/rebalance session in flight.  Returns stats
        with the snapshot accounted in ``d2h_bytes``."""
        assert self.active_cfg is not None, "nothing to park"
        assert self._stage_work is None and self.staged is None, \
            "park is mutually exclusive with scale staging"
        assert self._rebalance_ops is None, \
            "park is mutually exclusive with rebalancing"
        from repro.core.expert_pages import HOST
        t0 = time.perf_counter()
        stats = TransferStats()
        cfg = self.active_cfg
        pooled = self.expert_mode == "pooled"
        pages: Optional[Dict[Tuple[int, int], Dict[str, np.ndarray]]] = None
        if pooled:
            # per-page extraction: only live rows cross D2H, never the pool
            # zeros (accounting mirrors _make_rebalance_fetch: one
            # expert_page_nbytes per device-resident page)
            pages = {}
            ppd = self.expert_pool_pages
            pools = self.params["moe_pool"]
            shards = {k: {sh.device: sh.data for sh in l.addressable_shards}
                      for k, l in pools.items()}
            host_view: Dict[Tuple[str, int], np.ndarray] = {}

            def bank_rows(k: str, logical: int) -> np.ndarray:
                if (k, logical) not in host_view:
                    host_view[(k, logical)] = np.asarray(
                        shards[k][self.all_devices[logical]])
                return host_view[(k, logical)]

            page_bytes = self.expert_page_nbytes()
            for (l, e), ref in self.page_table.active.items():
                if ref.device == HOST:
                    pages[(l, e)] = {k: np.array(v) for k, v
                                     in self._expert_host_pool[(l, e)].items()}
                else:
                    pages[(l, e)] = {
                        k: np.array(bank_rows(k, ref.device)[ref.page])
                        for k in shards}
                    stats.d2h_bytes += page_bytes
                    stats.expert_d2h_bytes += page_bytes
            host_tree = {k: v for k, v in self.params.items()
                         if k != "moe_pool"}
        else:
            host_tree = self.params
        host_tree = jax.tree.map(np.asarray, host_tree)
        for leaf in jax.tree.leaves(host_tree):
            stats.d2h_bytes += leaf.nbytes
        total = (sum(leaf.nbytes for leaf in jax.tree.leaves(host_tree))
                 + (sum(r.nbytes for p in pages.values() for r in p.values())
                    if pages else 0))
        self._parked = {"tree": host_tree, "pages": pages, "cfg": cfg,
                        "bytes": total}
        self.params = None
        self.cache = None
        self.kv_blocks = None
        self.active_cfg = None
        self._expert_host_pool = {}
        if self.page_table is not None:
            # reset to an empty table: no device placement exists while
            # parked; unpark initial_places a fresh one at the target
            self.page_table = ExpertPageTable(
                self._n_moe_layers, self.mcfg.num_experts,
                pool_pages_per_device=(self.expert_pool_pages or 0
                                       if pooled else 0),
                host_pool_pages=self.expert_host_pages)
        stats.wall_s = time.perf_counter() - t0
        self.last_stats = stats
        return stats

    @property
    def parked(self) -> bool:
        return self._parked is not None

    def parked_bytes(self) -> int:
        """Pinned-host bytes held by the whole-model parked snapshot."""
        return self._parked["bytes"] if self._parked is not None else 0

    @obs.traced("hmm.begin_unpark", cat="hmm")
    def begin_unpark(self, cfg: ElasticConfig) -> int:
        """Open a staging session that streams the parked snapshot back to
        devices (cold start from the pinned-host tier).  Exactly the
        ``begin_scale`` discipline — serial mode drives it with
        ``stage_increment``, overlap mode submits every unit to the
        background ``TransferEngine`` and polls with ``poll_staging`` while
        the IMM's AOT compile runs on the serve thread (STAGING ∥
        COMPILING) — so the whole-model H2D window hides the compile.

        ``commit`` then allocates a fresh KV cache/block pool and the model
        is live again; tokens are bit-identical to a never-parked run (the
        snapshot round-trips every byte).  Returns the work-unit count."""
        assert self._parked is not None, "not parked"
        assert self.active_cfg is None
        assert self._stage_work is None, "staging already in progress"
        assert cfg.tp == self.tp, "TP is fixed across park/unpark (§4.1)"
        t0 = time.perf_counter()
        mesh = make_instance_mesh(cfg, self.all_devices)
        snap = self._parked
        # fresh container copy; leaves stay shared with the host snapshot
        params = jax.tree.map(lambda x: x, snap["tree"])
        table = None
        pooled = self.expert_mode == "pooled"
        if self.page_table is not None:
            table = ExpertPageTable(
                self._n_moe_layers, self.mcfg.num_experts,
                pool_pages_per_device=(self.expert_pool_pages or 0
                                       if pooled else 0),
                host_pool_pages=self.expert_host_pages)
            table.initial_place(cfg)
        if pooled:
            pages = snap["pages"]
            ppd = self.expert_pool_pages
            sample = next(iter(pages.values()))
            pools = {k: np.zeros((cfg.ndev * ppd,) + row.shape, row.dtype)
                     for k, row in sample.items()}
            for (l, e), ref in table.active.items():
                row = cfg.slot(ref.device) * ppd + ref.page
                for k in pools:
                    pools[k][row] = pages[(l, e)][k]
            params["moe_pool"] = pools
            moe = params["blocks"]["moe"]
            for name, arr in self._pooled_index_arrays(
                    table.active, cfg).items():
                moe[name] = np.asarray(arr, np.int32)
        shardings = self.param_shardings(params, mesh)
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        shard_leaves = jax.tree.leaves(shardings)
        work = []
        for (path_tuple, leaf), sh in zip(flat, shard_leaves):
            path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in path_tuple)
            work.append((path, leaf, sh, None, "unpark"))
        self._stage_work = work
        self._stage_cursor = 0
        self._stage_out = []
        self._stage_treedef = treedef
        self._stage_target = (cfg, mesh)
        self._unpark = True
        self._unpark_table = table
        self._stage_stats = TransferStats(wall_s=time.perf_counter() - t0)
        if pooled:
            # expert sub-accounting: the live-page share of the pool H2D
            # stream (h2d_bytes counts whole pool slices, zeros included —
            # that is what actually crosses the bus)
            self._stage_stats.expert_h2d_bytes += (
                len(snap["pages"]) * self.expert_page_nbytes())
        if self.staging_mode == "overlap":
            from repro.core.transfer import TransferOp
            self._stage_t0 = t0
            ops = [TransferOp(index=i, label=f"unpark:{path}",
                              fn=self._make_stage_op(leaf, sh, expert_dim,
                                                     kind, cfg, mesh))
                   for i, (path, leaf, sh, expert_dim, kind)
                   in enumerate(work)]
            self._stage_session = self.transfer_engine().submit(ops)
        return len(work)

    def _commit_unpark(self, new_cfg: ElasticConfig, mesh, params,
                       stats: TransferStats, t0: float) -> TransferStats:
        """Commit tail of an unpark session: adopt the streamed weights,
        allocate a FRESH KV cache/block pool (nothing survived the park —
        the INIT lane of the cost model), and swap in the fresh page
        table built at ``begin_unpark``."""
        cache = self.make_cache(new_cfg)
        cshard = self.cache_shardings(cache, mesh)
        self.cache = jax.tree.map(lambda x, s: jax.device_put(x, s),
                                  cache, cshard)
        for leaf in jax.tree.leaves(self.cache):
            stats.init_bytes += leaf.nbytes
        if self.kv_mode == "paged":
            from repro.serving.kv_blocks import KVBlockManager
            self.kv_blocks = KVBlockManager(new_cfg.dp,
                                            self.kv_blocks_per_replica,
                                            self.kv_block_size)
        self.active_cfg = new_cfg
        self.params = params
        self.staged = None
        if self._unpark_table is not None:
            self.page_table = self._unpark_table
        self._unpark = False
        self._unpark_table = None
        self._parked = None
        stats.wall_s = time.perf_counter() - t0
        if self.last_stats is not None:
            self.last_stats.merge(stats)
        return stats

    # ------------------------------------------------------------ rebalance
    def begin_rebalance(self, actions, load=None) -> int:
        """Open a skew-rebalance session (DESIGN.md §10): stage the page
        allocations, then fetch the bytes each replicate/demote op needs on
        the background TransferEngine (D2H row reads of immutable weights —
        safe concurrent with serving, like scale staging).

        ``actions``: see :meth:`ExpertPageTable.stage_rebalance`.
        ``load``: optional [L_moe, E] routing-count snapshot; stored for the
        replica-aware serving assignment rebuilt at commit.

        Returns the number of background transfer ops submitted.  Drive
        with ``poll_rebalance`` then ``commit_rebalance``, or unwind with
        ``abort_rebalance`` — an abort-in-flight conserves both tiers."""
        assert self.expert_mode == "pooled", \
            "rebalance requires expert_mode='pooled'"
        assert self._stage_work is None and self.staged is None, \
            "rebalance is mutually exclusive with scale staging"
        assert self._rebalance_ops is None, "rebalance already in progress"
        from repro.core.transfer import TransferOp
        self._rebalance_t0 = time.perf_counter()
        ops = self.page_table.stage_rebalance(actions)
        self._rebalance_ops = ops
        self._rebalance_load = (np.asarray(load, np.float64)
                                if load is not None else None)
        self._rebalance_stats = TransferStats()
        work = [TransferOp(index=i,
                           label=f"rebalance:{op.kind}:{op.layer}.{op.expert}",
                           fn=self._make_rebalance_fetch(op))
                for i, op in enumerate(ops)
                if op.kind in ("replicate", "demote")]
        self._rebalance_session = (self.transfer_engine().submit(work)
                                   if work else None)
        return len(work)

    def _make_rebalance_fetch(self, op):
        """Closure for one background fetch: D2H-copy the op's source page
        out of every pool bank (pure ``np.asarray`` reads — no compiled
        primitives, worker-thread safe) and return {bank: row}.  Bytes are
        accounted per page (``expert_page_nbytes``), merged under the
        staging lock like scale-staging ops."""
        banks = self.params["moe_pool"]
        page_bytes = self.expert_page_nbytes()
        stats = self._rebalance_stats
        src, kind = op.src, op.kind
        phys = self.all_devices[src.device]

        def run():
            t0 = time.perf_counter()
            rows = {}
            for name, leaf in banks.items():
                shard = next(sh for sh in leaf.addressable_shards
                             if sh.device == phys)
                rows[name] = np.array(np.asarray(shard.data)[src.page])
            sub = TransferStats()
            if kind == "demote":
                sub.expert_d2h_bytes = page_bytes
            else:
                sub.expert_replica_bytes = page_bytes
            sub.op_s = time.perf_counter() - t0
            with self._stage_lock:
                stats.merge(sub)
            return rows

        return run

    @property
    def rebalance_in_flight(self) -> bool:
        return (self._rebalance_session is not None
                and not self._rebalance_session.finished())

    def poll_rebalance(self) -> bool:
        """Bounded completion poll (<= ~2 ms), mirroring ``poll_staging``.
        True once every fetch op has finished (``commit_rebalance`` legal);
        a failed op aborts the session (pools conserved) and re-raises."""
        if self._rebalance_ops is None:
            return False
        sess = self._rebalance_session
        if sess is not None:
            if not sess.finished():
                sess.join(timeout=0.002)
                if not sess.finished():
                    return False
            failed = sess.failed_ops()
            if failed:
                err = failed[0].error
                self.abort_rebalance()
                raise RuntimeError(
                    f"rebalance fetch op {failed[0].label!r} failed "
                    f"({len(failed)} op(s) total); session aborted") from err
        return True

    @obs.traced("hmm.commit_rebalance", cat="hmm")
    def commit_rebalance(self, load=None) -> TransferStats:
        """Serve-thread switchover of a rebalance session: write replica
        rows into the pool banks (one rebuilt slice per receiving device),
        publish demoted rows to the pinned-host pool, free dropped/promoted
        pages, and rebuild the serving index arrays replica-aware
        (least-loaded assignment over ``load`` — defaults to the snapshot
        captured at ``begin_rebalance``).

        The params tree is updated IN PLACE, so the engine bound to it
        picks the new layout up on its next tick; array shapes are
        unchanged (slack-fixed table width), so every AOT-compiled
        executable stays valid.  Every copy is byte-identical, so tokens
        are bit-identical before/after."""
        assert self._rebalance_ops is not None, "no rebalance session open"
        if self._rebalance_session is not None:
            self._rebalance_session.join()
            if not self.poll_rebalance():     # surfaces failed ops
                raise RuntimeError("rebalance session did not finish")
        t0 = time.perf_counter()
        ops = self._rebalance_ops
        results = {}
        if self._rebalance_session is not None:
            for top in self._rebalance_session.ops:
                results[top.index] = top.result
        stats = self._rebalance_stats
        cfg = self.active_cfg
        ppd = self.expert_pool_pages
        if load is None:
            load = self._rebalance_load

        # 0) dry-run the post-commit layout BEFORE mutating anything: a
        # slot-overflow (replication skew beyond the table-width slack)
        # must abort the whole session, never half-commit it
        preview = self.page_table.clone()
        preview.commit_rebalance()
        try:
            layout = self._pooled_index_arrays(
                preview.active, cfg, replicas=preview.replicas, load=load)
        except ValueError:
            self.abort_rebalance()
            raise

        # 1) replica rows -> rebuilt pool-bank slices on receiving devices
        by_dev: Dict[int, List] = defaultdict(list)
        for i, op in enumerate(ops):
            if op.kind == "replicate":
                by_dev[op.dst.device].append((op.dst.page, results[i]))
        if by_dev:
            pools = self.params["moe_pool"]
            for bank in list(pools):
                leaf = pools[bank]
                target = leaf.sharding.devices_indices_map(leaf.shape)
                out = []
                for dev in leaf.sharding.addressable_devices:
                    rank = (target[dev][0].start or 0) // ppd
                    logical = cfg.devices[rank]
                    shard = next(sh.data for sh in leaf.addressable_shards
                                 if sh.device == dev)
                    if logical in by_dev:
                        base = np.array(shard)
                        for page, rows in by_dev[logical]:
                            base[page] = rows[bank]
                        shard = jax.device_put(base, dev)
                    out.append(shard)
                pools[bank] = jax.make_array_from_single_device_arrays(
                    leaf.shape, leaf.sharding, out)

        # 2) demoted bytes -> host pool; promoted entries retire
        for i, op in enumerate(ops):
            if op.kind == "demote":
                self._expert_host_pool[op.key] = results[i]
            elif op.kind == "promote":
                self._expert_host_pool.pop(op.key, None)

        # 3) table switchover (frees drop_replica / promote pages)
        self.page_table.commit_rebalance()

        # 4) replica-aware serving assignment -> fresh index arrays
        # (precomputed in step 0 from the preview table)
        mesh = make_instance_mesh(cfg, self.all_devices)
        moe = self.params["blocks"]["moe"]
        for name, arr in layout.items():
            spec = (P(None, ("dp", "tp"), None) if name == "tables"
                    else P())
            moe[name] = jax.device_put(np.asarray(arr, np.int32),
                                       NamedSharding(mesh, spec))

        sess = self._rebalance_session
        if sess is not None:
            stats.wall_s = max(sess.last_done_t - self._rebalance_t0, 0.0)
        stats.wall_s += time.perf_counter() - t0
        self.last_rebalance_stats = stats
        self._rebalance_ops = None
        self._rebalance_session = None
        self._rebalance_stats = None
        self._rebalance_load = None
        return stats

    @obs.traced("hmm.abort_rebalance", cat="hmm")
    def abort_rebalance(self):
        """Cancel-or-join, then unwind the rebalance session: freshly
        allocated pages return to their pools and no demoted bytes are
        published — device AND host tiers end exactly as before
        ``begin_rebalance``.  Idempotent."""
        if self._rebalance_session is not None:
            self._rebalance_session.cancel()
        self._rebalance_session = None
        self._rebalance_ops = None
        self._rebalance_stats = None
        self._rebalance_load = None
        if self.page_table is not None:
            self.page_table.abort_rebalance()

    def host_tier_bytes(self) -> int:
        """Resident bytes of the pinned-host cold tier: demoted expert
        pages plus, when parked, the whole-model snapshot."""
        return (len(self._expert_host_pool) * self.expert_page_nbytes()
                + self.parked_bytes())

    def update_cache(self, cache):
        """The active instance writes back its KV state after each step."""
        self.cache = cache

    # ------------------------------------------------------------- metrics
    def resident_bytes_per_device(self) -> Dict[int, int]:
        out: Dict[int, int] = defaultdict(int)
        seen = set()
        for tree in (self.params, self.cache):
            if tree is None:
                continue
            for leaf in jax.tree.leaves(tree):
                for sh in leaf.addressable_shards:
                    ptr = sh.data.unsafe_buffer_pointer()
                    if ptr in seen:
                        continue  # aliased buffer counted once
                    seen.add(ptr)
                    out[sh.device.id] += sh.data.nbytes
        return dict(out)
