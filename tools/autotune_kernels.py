#!/usr/bin/env python
"""Autotune harness for the paged Pallas kernels, pinned to the roofline.

Sweeps the static block/grid knobs of the serving kernels — decode-attention
``block_k``, paged-GMM / expert-FFN ``block_c``/``block_f`` — times each
candidate, and compares achieved HBM throughput against the memory-bound
bound from ``analysis/roofline.py`` (bytes touched / the HBM peak of the
chip it runs on, from the ``PEAKS`` table keyed by ``device_kind``).  The
block-table and mixed prefill+decode kernels have no free knobs (their block
size IS the pool layout's ``bs``), so they are timed and reported against
the roofline without a sweep.  With ``--quant`` the int8 variants run at the
winning f32 knobs and report their (roughly halved) byte traffic.

The winners are persisted as a JSON table (default
``tools/autotune_best.json``) that ``repro.analysis.autotune`` loads and
``repro.kernels.ops`` consults at dispatch time for any block-size kwarg the
caller leaves unset — a one-off offline sweep feeds the serving hot path
with no runtime tuning machinery.

On the CPU the kernels execute in Pallas interpret mode, so timings rank
Python emulation, not Mosaic code — useful as a dry run of the sweep
mechanics (CI runs ``--trials 2`` and asserts the table parses), not as
tuning data, and no roofline share is reported ("not measured").  On a TPU
the kernels compile to Mosaic and a chip missing from ``PEAKS`` is an
error.

Usage:
  python tools/autotune_kernels.py --trials 5 --out tools/autotune_best.json
  python tools/autotune_kernels.py --kernels paged_gmm --quant --trials 2
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from repro.analysis.autotune import TUNABLE_KEYS, load_best_configs  # noqa: E402
from repro.analysis.roofline import peaks_for               # noqa: E402
from repro.kernels import ops                               # noqa: E402
from repro.kernels.quant import quantize_rows               # noqa: E402

RNG = np.random.default_rng(7)


def _time_call(fn, trials: int) -> float:
    jax.block_until_ready(fn())          # compile + warmup, untimed
    best = math.inf
    for _ in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _f32(*shape):
    return jnp.asarray(RNG.standard_normal(shape), jnp.float32)


# --------------------------------------------------------------- kernel rigs
# Each rig returns (candidates, make_fn(knobs), bytes_f32, quant_entry|None)
# where bytes_f32 is the kernel's minimum HBM traffic (inputs read once +
# outputs written once) — the roofline memory-bound numerator.

def rig_paged_decode(a):
    B, QH, KVH, hd, S = a.batch, a.q_heads, a.kv_heads, a.head_dim, a.seq_len
    q = _f32(B, QH, hd)
    k = _f32(B, S, KVH, hd)
    v = _f32(B, S, KVH, hd)
    lengths = jnp.full((B,), S, jnp.int32)
    nbytes = (q.nbytes + k.nbytes + v.nbytes) + q.nbytes   # out == q shape
    cands = [{"block_k": bk} for bk in (64, 128, 256, 512)
             if bk <= S and S % bk == 0]

    def make(knobs):
        return lambda: ops.paged_decode_attention(q, k, v, lengths, **knobs)

    return cands, make, nbytes, None


def rig_block_paged(a):
    B, QH, KVH, hd = a.batch, a.q_heads, a.kv_heads, a.head_dim
    bs, MB = a.kv_block_size, a.seq_len // a.kv_block_size
    NB = B * MB
    kp = _f32(NB, bs, KVH, hd)
    vp = _f32(NB, bs, KVH, hd)
    q = _f32(B, QH, hd)
    bt = jnp.asarray(RNG.permutation(NB).reshape(B, MB), jnp.int32)
    lengths = jnp.full((B,), a.seq_len, jnp.int32)
    nbytes = q.nbytes * 2 + kp.nbytes + vp.nbytes

    def make(knobs):
        return lambda: ops.block_paged_decode_attention(
            q, kp, vp, bt, lengths, impl="kernel", **knobs)

    def quant():
        kq, ks = quantize_rows(kp, (-2, -1))
        vq, vs = quantize_rows(vp, (-2, -1))
        qb = (q.nbytes * 2 + kq.nbytes + vq.nbytes
              + ks.nbytes + vs.nbytes)
        return (lambda: ops.quant_block_paged_decode_attention(
            q, kq, ks, vq, vs, bt, lengths, impl="kernel")), qb

    return [{}], make, nbytes, quant


def rig_mixed(a):
    B, QH, KVH, hd = a.batch, a.q_heads, a.kv_heads, a.head_dim
    bs, MB = a.kv_block_size, a.seq_len // a.kv_block_size
    NB, G = B * MB, a.chunk
    kp = _f32(NB, bs, KVH, hd)
    vp = _f32(NB, bs, KVH, hd)
    q = _f32(B, G, QH, hd)
    bt = jnp.asarray(RNG.permutation(NB).reshape(B, MB), jnp.int32)
    ctx = jnp.full((B,), a.seq_len, jnp.int32)
    qlen = jnp.full((B,), G, jnp.int32)
    nbytes = q.nbytes * 2 + kp.nbytes + vp.nbytes

    def make(knobs):
        return lambda: ops.mixed_block_paged_attention(
            q, kp, vp, bt, ctx, qlen, impl="kernel", **knobs)

    def quant():
        kq, ks = quantize_rows(kp, (-2, -1))
        vq, vs = quantize_rows(vp, (-2, -1))
        qb = (q.nbytes * 2 + kq.nbytes + vq.nbytes
              + ks.nbytes + vs.nbytes)
        return (lambda: ops.quant_mixed_block_paged_attention(
            q, kq, ks, vq, vs, bt, ctx, qlen, impl="kernel")), qb

    return [{}], make, nbytes, quant


def _gmm_cands(C, F):
    out = []
    for bc in (64, 128, 256):
        if bc > C or C % bc:
            continue
        for bf in (128, 256):
            if bf > F or F % bf:
                continue
            out.append({"block_c": bc, "block_f": bf})
    return out or [{"block_c": min(128, C), "block_f": min(128, F)}]


def rig_paged_gmm(a):
    E, C, D, F = a.experts, a.tokens, a.d_model, a.d_ff
    pool = _f32(a.pool_pages, D, F)
    x = _f32(E, C, D)
    table = jnp.asarray(RNG.choice(a.pool_pages, E, replace=False), jnp.int32)
    nbytes = x.nbytes + E * D * F * 4 + E * C * F * 4

    def make(knobs):
        return lambda: ops.paged_gmm(table, pool, x, **knobs)

    def quant():
        pq, ps = quantize_rows(pool, (-2, -1))
        qb = x.nbytes + E * (D * F + 4) + E * C * F * 4
        return (lambda: ops.quant_paged_gmm(table, pq, ps, x,
                                            impl="kernel")), qb

    return _gmm_cands(C, F), make, nbytes, quant


def rig_paged_ffn(a):
    E, C, D, F = a.experts, a.tokens, a.d_model, a.d_ff
    pi, pg = _f32(a.pool_pages, D, F), _f32(a.pool_pages, D, F)
    po = _f32(a.pool_pages, F, D)
    x = _f32(E, C, D)
    table = jnp.asarray(RNG.choice(a.pool_pages, E, replace=False), jnp.int32)
    # 2 up-GMMs + silu-gate elementwise + down-GMM, each read-once/write-once
    nbytes = (x.nbytes * 2 + 2 * E * (D * F * 4 + C * F * 4)   # wi, wg
              + 3 * E * C * F * 4                               # h*silu(g)
              + E * (F * D * 4 + C * F * 4) + E * C * D * 4)    # wo

    def make(knobs):
        return lambda: ops.paged_expert_ffn(table, table, table,
                                            pi, pg, po, x,
                                            impl="kernel", **knobs)

    def quant():
        qi, si = quantize_rows(pi, (-2, -1))
        qg, sg = quantize_rows(pg, (-2, -1))
        qo, so = quantize_rows(po, (-2, -1))
        qb = (x.nbytes * 2 + 2 * E * (D * F + 4 + C * F * 4)
              + 3 * E * C * F * 4
              + E * (F * D + 4 + C * F * 4) + E * C * D * 4)
        return (lambda: ops.quant_paged_expert_ffn(
            table, table, table, qi, qg, qo, si, sg, so, x,
            impl="kernel")), qb

    return _gmm_cands(C, F), make, nbytes, quant


RIGS = {
    "paged_decode_attention": rig_paged_decode,
    "block_paged_decode_attention": rig_block_paged,
    "mixed_block_paged_attention": rig_mixed,
    "paged_gmm": rig_paged_gmm,
    "paged_expert_ffn": rig_paged_ffn,
}


def sweep_kernel(name, a, hbm_bw) -> dict:
    """``hbm_bw`` None (CPU interpreter): roofline shares not measured."""
    cands, make, nbytes, quant = RIGS[name](a)
    t_roof = nbytes / hbm_bw if hbm_bw else None
    rows = []
    for knobs in cands:
        el = _time_call(make(knobs), a.trials)
        rows.append({**knobs, "elapsed_s": el,
                     "achieved_gbps": nbytes / el / 1e9,
                     "frac_of_roofline": t_roof / el if t_roof else None})
    rows.sort(key=lambda r: r["elapsed_s"])
    entry = {"bytes": nbytes, "t_roofline_s": t_roof,
             "candidates": rows, "best": rows[0]}
    if a.quant and quant is not None:
        qfn, qbytes = quant()
        el = _time_call(qfn, a.trials)
        t_qroof = qbytes / hbm_bw if hbm_bw else None
        entry["quant_int8"] = {
            "bytes": qbytes, "t_roofline_s": t_qroof,
            "elapsed_s": el, "achieved_gbps": qbytes / el / 1e9,
            "frac_of_roofline": t_qroof / el if t_qroof else None,
            "bytes_vs_f32": qbytes / nbytes}
    return entry


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trials", type=int, default=5,
                    help="timed repetitions per candidate (best-of)")
    ap.add_argument("--out", type=Path,
                    default=Path(__file__).resolve().parent
                    / "autotune_best.json")
    ap.add_argument("--kernels", nargs="*", default=sorted(RIGS),
                    choices=sorted(RIGS), metavar="KERNEL")
    ap.add_argument("--quant", action="store_true",
                    help="also time the int8 variants at the winning knobs")
    # sweep shapes (defaults sized for a quick interpret-mode dry run)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--q-heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--kv-block-size", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=8,
                    help="mixed kernel prefill-chunk length")
    ap.add_argument("--experts", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=128,
                    help="tokens per local expert (GMM C dim)")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--d-ff", type=int, default=256)
    ap.add_argument("--pool-pages", type=int, default=8)
    a = ap.parse_args(argv)

    dev = jax.devices()[0]
    hbm_bw = None if ops.on_cpu() else peaks_for(dev.device_kind).hbm_bw
    report = {"meta": {"backend": jax.default_backend(),
                       "device_kind": dev.device_kind,
                       "interpret": ops.on_cpu(),
                       "trials": a.trials, "hbm_bw": hbm_bw,
                       "shapes": {k: v for k, v in vars(a).items()
                                  if isinstance(v, int)}},
              "kernels": {}}
    for name in a.kernels:
        print(f"== {name}")
        entry = sweep_kernel(name, a, hbm_bw)
        report["kernels"][name] = entry
        for r in entry["candidates"]:
            knobs = {k: v for k, v in r.items()
                     if k in TUNABLE_KEYS.get(name, ())}
            mark = " *" if r is entry["best"] else ""
            frac = r["frac_of_roofline"]
            roof = (f"{frac * 100:6.2f}% of roofline" if frac is not None
                    else "roofline not measured")
            print(f"   {json.dumps(knobs):24s} {r['elapsed_s'] * 1e3:9.3f} ms"
                  f"  {r['achieved_gbps']:8.3f} GB/s  {roof}{mark}")
        q = entry.get("quant_int8")
        if q:
            print(f"   int8 @ best knobs        {q['elapsed_s'] * 1e3:9.3f} ms"
                  f"  {q['achieved_gbps']:8.3f} GB/s"
                  f"  ({q['bytes_vs_f32'] * 100:.1f}% of f32 bytes)")

    a.out.parent.mkdir(parents=True, exist_ok=True)
    a.out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"\nwrote {a.out}")

    # round-trip through the dispatch-side loader: the persisted table must
    # parse and expose knobs for every tunable kernel that was swept
    table = load_best_configs(a.out, refresh=True)
    tuned = [k for k in a.kernels if TUNABLE_KEYS.get(k)]
    missing = [k for k in tuned if k not in table]
    print(f"dispatch table: {json.dumps(table)}")
    if missing:
        print(f"ERROR: no tunable knobs parsed for {missing}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
