"""Mixture-of-Experts layer: router + three execution paths.

* ``moe_local``  — single-shard capacity-based dispatch (scatter → grouped
  matmul → combine).  Used by the elastic serving engine, smoke tests, and as
  the oracle for the Pallas paged-GMM kernel.
* ``moe_ep``     — expert-parallel path for production meshes, written with
  ``shard_map``: per-data-shard dispatch into a [n_ep, E_local, C, D] buffer,
  ``all_to_all`` over the EP axis, grouped expert matmul with the expert FFN
  hidden dim TP-sharded over the model axis, reverse ``all_to_all``, combine.
  This is the paper's "unified token routing" (§2.1/§3 L4) mapped onto
  jax-native collectives.
* **pooled** (``expert_mode="pooled"`` on the HMM; DESIGN.md §2) — expert
  weights live as per-device page *pools* ``[pages, D, F]`` plus page-table
  index arrays (``core/expert_pages.pooled_layout``) instead of dense
  ``[E, D, F]`` banks.  ``moe_ep`` detects the pooled parameter layout
  (``"tables" in p``) and dispatches by the table's — possibly
  non-contiguous, min-move — expert placement; the grouped matmul goes
  through ``kernels.ops.paged_expert_ffn`` (Pallas paged GMM on
  accelerators, jnp gather oracle on CPU via ``REPRO_POOLED_IMPL``).
  ``moe_local_pooled`` is the single-shard equivalent over global pool rows.
  Per-expert math is identical to the dense paths, so pooled and dense
  decode agree bit-for-bit at f32 (asserted in tests/test_pooled_experts.py).

Capacity convention: every (expert) gets a fixed per-source-shard capacity
``C = ceil(T_local * top_k / E * capacity_factor)``; overflow tokens are
dropped (standard GShard semantics).  FLOPs therefore track the *active*
parameter count — this is what the roofline's MODEL_FLOPS ratio checks.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models.layers import dot, linear, linear_init

# -------------------------------------------------------------------- router

def router_init(rng, d_model, num_experts, dtype):
    # router math is always f32 for stability
    return {"w": (jax.random.normal(rng, (d_model, num_experts), jnp.float32)
                  * (1.0 / math.sqrt(d_model)))}


def route(p, x, top_k):
    """x [T, D] -> (topk_idx [T,k] int32, topk_w [T,k] f32, aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), p["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, top_k)
    topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)
    # GShard/Switch load-balance auxiliary loss
    E = probs.shape[-1]
    me = jnp.mean(probs, axis=0)
    one = jax.nn.one_hot(topk_idx[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(one, axis=0)
    aux = E * jnp.sum(me * ce)
    return topk_idx.astype(jnp.int32), topk_w, aux


def _dispatch_indices(topk_idx, num_experts, capacity):
    """Flattened (token, k) entries -> (expert, slot) with capacity dropping.

    Returns (expert_flat [T*k], slot [T*k], keep [T*k] bool); dropped entries
    get slot == capacity (out of range -> 'drop' scatter mode discards them).
    """
    Tk = topk_idx.size
    expert_flat = topk_idx.reshape(Tk)
    onehot = jax.nn.one_hot(expert_flat, num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1                    # [Tk, E]
    slot = jnp.sum(pos * onehot, axis=-1)                   # [Tk]
    keep = slot < capacity
    slot = jnp.where(keep, slot, capacity)
    return expert_flat, slot, keep


def _expert_ffn(xg, wi, wg, wo):
    """xg [E, C, D]; wi/wg [E, D, F]; wo [E, F, D] -> [E, C, D]."""
    h = jnp.einsum("ecd,edf->ecf", xg, wi,
                   preferred_element_type=jnp.float32).astype(xg.dtype)
    g = jnp.einsum("ecd,edf->ecf", xg, wg,
                   preferred_element_type=jnp.float32)
    h = h * jax.nn.silu(g).astype(xg.dtype)
    return jnp.einsum("ecf,efd->ecd", h, wo,
                      preferred_element_type=jnp.float32).astype(xg.dtype)


# ---------------------------------------------------------------- moe params

def moe_init(rng, cfg, dtype):
    D, F, E = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    ks = jax.random.split(rng, 5)
    s = 1.0 / math.sqrt(D)
    p = {
        "router": router_init(ks[0], D, E, dtype),
        "wi": jax.random.normal(ks[1], (E, D, F), dtype) * s,
        "wg": jax.random.normal(ks[2], (E, D, F), dtype) * s,
        "wo": jax.random.normal(ks[3], (E, F, D), dtype) * (1.0 / math.sqrt(F)),
    }
    if cfg.num_shared_experts:
        from repro.models.layers import mlp_init
        p["shared"] = mlp_init(ks[4], D, cfg.moe_d_ff * cfg.num_shared_experts,
                               dtype, gated=True)
    return p


def capacity_for(tokens, cfg):
    return max(1, int(math.ceil(tokens * cfg.top_k / cfg.num_experts
                                * cfg.capacity_factor)))


# ------------------------------------------------------------- local path

def routing_counts(topk_idx, num_experts):
    """topk_idx [T, k] -> per-expert routed-token counts [E] int32 — the
    telemetry histogram (obs, DESIGN.md §9).  Pure bincount: capacity
    dropping is intentionally ignored, this measures router demand."""
    return jnp.zeros((num_experts,), jnp.int32).at[
        topk_idx.reshape(-1)].add(1)


def _moe_local_body(cfg, p, x, capacity, expert_ffn, return_counts=False):
    """Shared single-shard dispatch/combine; ``expert_ffn(xg [E, C, D]) ->
    [E, C, D]`` is the only thing that differs between the dense banks and
    the pooled store (which is what makes their outputs bit-identical).

    ``return_counts`` additionally returns the router's per-expert token
    counts [E] (routing telemetry; the default two-tuple return is
    untouched so every existing call site is byte-identical)."""
    T, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    C = capacity or capacity_for(T, cfg)
    topk_idx, topk_w, aux = route(p["router"], x, k)
    expert_flat, slot, keep = _dispatch_indices(topk_idx, E, C)
    token_idx = jnp.repeat(jnp.arange(T), k)

    xg = jnp.zeros((E, C, D), x.dtype).at[expert_flat, slot].set(
        x[token_idx], mode="drop")
    yg = expert_ffn(xg)

    w_flat = topk_w.reshape(T * k).astype(x.dtype)
    gathered = yg.at[expert_flat, slot].get(mode="fill", fill_value=0.0)
    y = jnp.zeros((T, D), x.dtype).at[token_idx].add(
        gathered * (w_flat * keep)[:, None])
    if "shared" in p:
        from repro.models.layers import mlp_apply
        y = y + mlp_apply(p["shared"], x)
    if return_counts:
        return y, aux, routing_counts(topk_idx, E)
    return y, aux


def moe_local(cfg, p, x, capacity=None, return_counts=False):
    """x [T, D] -> ([T, D], aux_loss).  Single-shard dispatch/combine."""
    return _moe_local_body(
        cfg, p, x, capacity,
        lambda xg: _expert_ffn(xg, p["wi"], p["wg"], p["wo"]),
        return_counts=return_counts)


def moe_local_pooled(cfg, p, pool, x, capacity=None, return_counts=False):
    """Single-shard MoE over the pooled weight store.

    ``p`` holds the per-layer index arrays (``gtable`` [E]: global pool row
    per expert) and ``pool`` the three banks ``{wi, wg, wo}`` as
    ``[pages_total, D, F]`` / ``[pages_total, F, D]``.  Dispatch/combine are
    shared with ``moe_local``; only the weight *addressing* differs — the
    grouped matmul reads pages through the table (``ops.paged_expert_ffn``),
    so an expert remap rewrites ``gtable`` and moves no weight bytes."""
    from repro.kernels import ops

    gt = p["gtable"]
    if "wi_scale" in pool:
        # int8 store: per-page f32 scale banks ride beside the pools and are
        # addressed by the same table (kernels/moe_gmm.quant_paged_gmm)
        ffn = lambda xg: ops.quant_paged_expert_ffn(
            gt, gt, gt, pool["wi"], pool["wg"], pool["wo"],
            pool["wi_scale"], pool["wg_scale"], pool["wo_scale"], xg)
    else:
        ffn = lambda xg: ops.paged_expert_ffn(gt, gt, gt, pool["wi"],
                                              pool["wg"], pool["wo"], xg)
    return _moe_local_body(cfg, p, x, capacity, ffn,
                           return_counts=return_counts)


# ---------------------------------------------------------------- EP path

def _moe_ep_shard(cfg, ep_axes, tp_axis, dp_axes, router_w, wi, wg, wo, x,
                  capacity, n_ep):
    """Body run per (ep, tp) shard under shard_map.

    x        [T_local, D]        (token-sharded over ep_axes)
    wi/wg    [E_local, D, F_tp]  wo [E_local, F_tp, D]

    ``n_ep`` is threaded in statically from the mesh (buffer shapes need it
    concrete).
    """
    E, k = cfg.num_experts, cfg.top_k
    E_local = E // n_ep
    T, D = x.shape
    C = capacity

    topk_idx, topk_w, aux = route({"w": router_w}, x, k)
    expert_flat, slot, keep = _dispatch_indices(topk_idx, E, C)
    dest = expert_flat // E_local
    e_loc = expert_flat % E_local
    token_idx = jnp.repeat(jnp.arange(T), k)

    send = jnp.zeros((n_ep, E_local, C, D), x.dtype).at[
        dest, e_loc, slot].set(x[token_idx], mode="drop")
    # all-to-all over the EP axes: rows <-> shards
    recv = jax.lax.all_to_all(send, ep_axes, split_axis=0, concat_axis=0,
                              tiled=False)
    xg = recv.transpose(1, 0, 2, 3).reshape(E_local, n_ep * C, D)
    yg = _expert_ffn(xg, wi, wg, wo)
    if tp_axis is not None:
        # expert hidden dim is TP-sharded -> partial sums over tp_axis
        yg = jax.lax.psum(yg, tp_axis)
    back = yg.reshape(E_local, n_ep, C, D).transpose(1, 0, 2, 3)
    ret = jax.lax.all_to_all(back, ep_axes, split_axis=0, concat_axis=0,
                             tiled=False)

    w_flat = topk_w.reshape(T * k).astype(x.dtype)
    gathered = ret.at[dest, e_loc, slot].get(mode="fill", fill_value=0.0)
    y = jnp.zeros((T, D), x.dtype).at[token_idx].add(
        gathered * (w_flat * keep)[:, None])
    aux = jax.lax.pmean(aux, dp_axes)
    return y, aux


def _moe_ep_shard_packed(cfg, ep_axes, tp_axis, dp_axes, router_w, wi, wg, wo,
                         x, capacity, n_ep):
    """Packed-dispatch variant (beyond-paper, EXPERIMENTS.md §Perf B).

    Buffers are sized per (src, dst) shard pair — [n_ep, C2, D] with
    C2 ~ T*k/n_ep — instead of per (src, dst, expert) slot, which shrinks the
    all-to-all payload by ~E_local/k when experts-per-shard exceed top_k
    (decode: 4-8x on arctic/deepseek).  Expert FFN results return as TP
    partials and are reduced once on the combined [T, D] output instead of
    per capacity slot.  Cost: the expert matmul computes all local experts
    per token (one-hot select) — E_local x FLOP waste, negligible at decode
    arithmetic intensity.  Use for decode; keep expert-slot dispatch for
    train/prefill.
    """
    E, k = cfg.num_experts, cfg.top_k
    E_local = E // n_ep
    T, D = x.shape
    C2 = capacity

    topk_idx, topk_w, aux = route({"w": router_w}, x, k)
    Tk = T * k
    expert_flat = topk_idx.reshape(Tk)
    dest = expert_flat // E_local
    e_loc = expert_flat % E_local
    onehot = jax.nn.one_hot(dest, n_ep, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.sum(pos * onehot, axis=-1)
    keep = slot < C2
    slot = jnp.where(keep, slot, C2)
    token_idx = jnp.repeat(jnp.arange(T), k)

    send_x = jnp.zeros((n_ep, C2, D), x.dtype).at[dest, slot].set(
        x[token_idx], mode="drop")
    send_e = jnp.full((n_ep, C2), E_local, jnp.int32).at[dest, slot].set(
        e_loc, mode="drop")
    recv_x = jax.lax.all_to_all(send_x, ep_axes, 0, 0, tiled=False)
    recv_e = jax.lax.all_to_all(send_e, ep_axes, 0, 0, tiled=False)
    xg = recv_x.reshape(n_ep * C2, D)
    eid = recv_e.reshape(n_ep * C2)

    # all-local-experts compute + one-hot select (E_local x flops, tiny at
    # decode); invalid slots (eid == E_local) select zero
    h = jnp.einsum("sd,edf->esf", xg, wi,
                   preferred_element_type=jnp.float32)
    g = jnp.einsum("sd,edf->esf", xg, wg,
                   preferred_element_type=jnp.float32)
    h = (h * jax.nn.silu(g)).astype(x.dtype)
    y_all = jnp.einsum("esf,efd->esd", h, wo,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    sel = jax.nn.one_hot(eid, E_local, dtype=x.dtype)        # [S2, E_local]
    yg = jnp.einsum("esd,se->sd", y_all, sel)                # TP-partial

    back = yg.reshape(n_ep, C2, D)
    ret = jax.lax.all_to_all(back, ep_axes, 0, 0, tiled=False)
    w_flat = topk_w.reshape(Tk).astype(x.dtype)
    gathered = ret.at[dest, slot].get(mode="fill", fill_value=0.0)
    y = jnp.zeros((T, D), x.dtype).at[token_idx].add(
        gathered * (w_flat * keep)[:, None])
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)      # single reduction on [T, D]
    aux = jax.lax.pmean(aux, dp_axes)
    return y, aux


def _moe_ep_shard_pooled(cfg, ep_axes, tp_axis, dp_axes, router_w, table,
                         edest, eslot, pool_i, pool_g, pool_o, x,
                         capacity, n_ep, scales=None):
    """Pooled-store EP shard body (paper vpage-remap in the serving path).

    Differs from ``_moe_ep_shard`` only in *addressing*: the expert → device
    map comes from the page table's (possibly non-contiguous, min-move)
    placement — ``edest``/``eslot`` [E] replace the contiguous
    ``expert // E_local`` arithmetic — and the grouped matmul reads weight
    pages through the local table instead of a dense [E_local, D, F] bank.
    Per-expert math is unchanged, so tokens match the dense path exactly.

    This same indirection is what makes dispatch *replica-aware* for free
    (DESIGN.md §10): when the skew rebalancer replicates a hot expert onto
    extra devices, ``pooled_layout`` simply points ``edest``/``eslot`` at
    the least-loaded byte-identical copy — this body never knows replicas
    exist, and since every copy holds identical bytes, tokens stay
    bit-identical to the unreplicated layout.  ``elm = table.shape[-1]``
    is read from the array, so replication slack (extra table-width slots
    baked at boot) flows through without any kernel change.

    table  [1, Elm] int32   local pool-page per owned expert (this shard)
    pools  [ppd, D|F, F|D]  this device's page pools (all three banks)
    """
    from repro.kernels import ops

    E, k = cfg.num_experts, cfg.top_k
    elm = table.shape[-1]
    T, D = x.shape
    C = capacity

    topk_idx, topk_w, aux = route({"w": router_w}, x, k)
    expert_flat, slot, keep = _dispatch_indices(topk_idx, E, C)
    dest = edest[expert_flat]
    e_loc = eslot[expert_flat]
    token_idx = jnp.repeat(jnp.arange(T), k)

    send = jnp.zeros((n_ep, elm, C, D), x.dtype).at[
        dest, e_loc, slot].set(x[token_idx], mode="drop")
    recv = jax.lax.all_to_all(send, ep_axes, split_axis=0, concat_axis=0,
                              tiled=False)
    xg = recv.transpose(1, 0, 2, 3).reshape(elm, n_ep * C, D)
    t = table[0]
    if scales is not None:
        yg = ops.quant_paged_expert_ffn(t, t, t, pool_i, pool_g, pool_o,
                                        scales[0], scales[1], scales[2], xg)
    else:
        yg = ops.paged_expert_ffn(t, t, t, pool_i, pool_g, pool_o, xg)
    if tp_axis is not None:
        yg = jax.lax.psum(yg, tp_axis)
    back = yg.reshape(elm, n_ep, C, D).transpose(1, 0, 2, 3)
    ret = jax.lax.all_to_all(back, ep_axes, split_axis=0, concat_axis=0,
                             tiled=False)

    w_flat = topk_w.reshape(T * k).astype(x.dtype)
    gathered = ret.at[dest, e_loc, slot].get(mode="fill", fill_value=0.0)
    y = jnp.zeros((T, D), x.dtype).at[token_idx].add(
        gathered * (w_flat * keep)[:, None])
    aux = jax.lax.pmean(aux, dp_axes)
    return y, aux


def _moe_ep_shard_pooled_quant(cfg, ep_axes, tp_axis, dp_axes, router_w,
                               table, edest, eslot, pool_i, pool_g, pool_o,
                               scale_i, scale_g, scale_o, x, capacity, n_ep):
    """Int8 pooled shard body: the three per-page f32 scale banks arrive as
    extra shard_map operands (page-axis sharded like their pools) and feed
    the fused-dequant paged GMM; dispatch/combine are shared."""
    return _moe_ep_shard_pooled(cfg, ep_axes, tp_axis, dp_axes, router_w,
                                table, edest, eslot, pool_i, pool_g, pool_o,
                                x, capacity, n_ep,
                                scales=(scale_i, scale_g, scale_o))


def moe_ep(cfg, p, x, parallel, capacity=None, pool=None,
           return_counts=False):
    """Expert-parallel MoE over a mesh described by ``parallel``
    (repro.distributed.sharding.ParallelCtx).

    x [B, S, D]; tokens are flattened and sharded over ``parallel.ep_axes``
    for dispatch; expert weights are sharded E over ``ep_axes`` and (if
    ``tp_axis`` is set) F over ``tp_axis``.

    ``pool``: the pooled weight store ``{wi, wg, wo}`` when ``p`` carries
    the pooled index arrays (``expert_mode="pooled"``); pools are page-axis
    sharded over ``ep_axes`` and the pooled shard body is used.  Pooled
    mode keeps the expert FFN dim unsharded (the serving engine's
    ``moe_tp=False`` convention — EP spans every device, paper §4.1).
    """
    from jax.sharding import PartitionSpec as P

    mesh = parallel.mesh
    ep_axes = tuple(a for a in parallel.ep_axes if a in mesh.axis_names)
    tp_axis = parallel.tp_axis if (parallel.tp_axis in mesh.axis_names
                                   and parallel.moe_tp) else None
    B, S, D = x.shape
    n_ep = math.prod(mesh.shape[a] for a in ep_axes)
    T = B * S
    T_pad = -(-T // n_ep) * n_ep          # shard_map needs even token shards
    t_local = max(1, T_pad // n_ep)
    pooled = pool is not None and "tables" in p
    packed = (getattr(parallel, "moe_dispatch", "expert_slots") == "packed"
              and not pooled)
    if packed:
        C = capacity or max(1, math.ceil(t_local * cfg.top_k / n_ep
                                         * cfg.capacity_factor))
        shard_body = _moe_ep_shard_packed
    else:
        C = capacity or capacity_for(t_local, cfg)
        shard_body = _moe_ep_shard

    xf = x.reshape(T, D)
    if T_pad != T:
        xf = jnp.pad(xf, ((0, T_pad - T), (0, 0)))
    x_spec = P(ep_axes, None)
    if pooled:
        assert tp_axis is None, \
            "pooled expert store requires moe_tp=False (EP-only sharding)"
        pool_spec = P(ep_axes, None, None)
        if "wi_scale" in pool:
            body = partial(_moe_ep_shard_pooled_quant, cfg, ep_axes, tp_axis,
                           ep_axes, capacity=C, n_ep=n_ep)
            scale_spec = P(ep_axes)
            y, aux = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(None, None), P(ep_axes, None), P(None), P(None),
                          pool_spec, pool_spec, pool_spec,
                          scale_spec, scale_spec, scale_spec, x_spec),
                out_specs=(x_spec, P()),
                check_vma=False,
            )(p["router"]["w"], p["tables"], p["edest"], p["eslot"],
              pool["wi"], pool["wg"], pool["wo"],
              pool["wi_scale"], pool["wg_scale"], pool["wo_scale"], xf)
        else:
            body = partial(_moe_ep_shard_pooled, cfg, ep_axes, tp_axis,
                           ep_axes, capacity=C, n_ep=n_ep)
            y, aux = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(None, None), P(ep_axes, None), P(None), P(None),
                          pool_spec, pool_spec, pool_spec, x_spec),
                out_specs=(x_spec, P()),
                check_vma=False,
            )(p["router"]["w"], p["tables"], p["edest"], p["eslot"],
              pool["wi"], pool["wg"], pool["wo"], xf)
    else:
        body = partial(shard_body, cfg, ep_axes, tp_axis, ep_axes,
                       capacity=C, n_ep=n_ep)
        w_spec_if = P(ep_axes, None, tp_axis)
        w_spec_of = P(ep_axes, tp_axis, None)
        y, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, None), w_spec_if, w_spec_if, w_spec_of, x_spec),
            out_specs=(x_spec, P()),
            check_vma=False,
        )(p["router"]["w"], p["wi"], p["wg"], p["wo"], xf)
    if T_pad != T:
        y = y[:T]
    y = y.reshape(B, S, D)
    if "shared" in p:
        from repro.models.layers import mlp_apply
        y = y + mlp_apply(p["shared"], x)
    if return_counts:
        # Telemetry replays the router on the replicated activations outside
        # shard_map (one tiny [T, E] matmul; decode T = B).  Restricting to
        # the first T rows excludes the zero-padding rows, whose uniform
        # softmax would otherwise pollute the first-k experts' bins.
        topk_idx, _, _ = route(p["router"], xf, cfg.top_k)
        counts = routing_counts(topk_idx[:T], cfg.num_experts)
        return y, jnp.mean(aux), counts
    return y, jnp.mean(aux)
