import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture x input shape x mesh)
combination lowers, compiles, and fits — with no real allocation.

For each combination this script:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs sharded ShapeDtypeStructs for params (+opt state) and inputs,
  3. ``jit(step).lower(...).compile()`` — sharding mismatches, unsupported
     collectives or compile-time OOM are hard failures,
  4. records ``memory_analysis()`` / ``cost_analysis()`` + parsed collective
     bytes into experiments/dryrun/*.json for the roofline report.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--skip-existing]
"""
import argparse
import dataclasses
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.analysis.roofline import from_compiled, model_flops_for
from repro.configs import ASSIGNED, INPUT_SHAPES, SHAPES_BY_NAME, get_config
from repro.configs.base import InputShape, ModelConfig
from repro.distributed.sharding import (ParallelCtx, dp_axes, param_specs)
from repro.launch.mesh import make_production_mesh
from repro.models.model import decode_step, forward, init_cache, init_params
from repro.training.optimizer import AdamWConfig, init_state
from repro.training.train_loop import make_train_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# sliding-window length used to run long_500k on full-attention archs
LONG_CONTEXT_WINDOW = 8192

#: the chip the production meshes (launch/mesh.py) model
POD_DEVICE_KIND = "TPU v5 lite"


def plan_for(mcfg: ModelConfig, shape: InputShape):
    """Returns (effective_cfg, kind) or (None, skip_reason)."""
    if shape.kind == "train":
        return mcfg, "encode_train" if mcfg.arch_type == "encoder" else "train"
    if mcfg.arch_type == "encoder":
        if shape.kind == "prefill":
            return mcfg, "encode"
        return None, "encoder-only: no decode step (DESIGN.md §4)"
    if shape.kind == "prefill":
        return mcfg, "prefill"
    # decode shapes
    if shape.name == "long_500k" and not mcfg.supports_long_context:
        if mcfg.arch_type in ("dense", "moe", "vlm"):
            eff = dataclasses.replace(mcfg, attn_window=LONG_CONTEXT_WINDOW)
            return eff, "decode"
        return None, "full attention at 500k skipped (DESIGN.md §4)"
    return mcfg, "decode"


def parallel_for(mesh, opts=None):
    opts = opts or {}
    ep = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return ParallelCtx(mesh=mesh, ep_axes=ep, tp_axis="model", dp_axes=ep,
                       moe_tp=True,
                       moe_dispatch=("packed" if opts.get("moe_packed")
                                     else "expert_slots"))


def _sharded_sds(tree_shapes, specs, mesh):
    return jax.tree.map(
        lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tree_shapes, specs)


def params_sds(mcfg, mesh, opts=None):
    opts = opts or {}
    shapes = jax.eval_shape(
        partial(init_params, mcfg, jax.random.PRNGKey(0),
                jnp.dtype(mcfg.dtype)))
    kv_heads = mcfg.num_kv_heads if opts.get("kv_aligned") else None
    specs = param_specs(shapes, mesh, kv_heads=kv_heads)
    return _sharded_sds(shapes, specs, mesh)


def input_specs(mcfg: ModelConfig, shape: InputShape, mesh, kind: str,
                opts=None):
    """Sharded ShapeDtypeStructs for every model input of this step."""
    dp = dp_axes(mesh)
    B, S = shape.global_batch, shape.seq_len
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    bspec = dp if B % n_dp == 0 and B >= n_dp else None

    def sds(shp, dtype, spec):
        return jax.ShapeDtypeStruct(shp, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    if kind in ("train", "encode_train", "encode"):
        batch = {}
        if mcfg.arch_type == "encoder":
            batch["frames"] = sds((B, S, mcfg.d_model), jnp.dtype(mcfg.dtype),
                                  (bspec, None, None))
        else:
            batch["tokens"] = sds((B, S), jnp.int32, (bspec, None))
        if kind != "encode":
            batch["labels"] = sds((B, S), jnp.int32, (bspec, None))
        if mcfg.arch_type == "vlm":
            batch["image_embeds"] = sds(
                (B, mcfg.num_image_tokens, mcfg.d_model),
                jnp.dtype(mcfg.dtype), (bspec, None, None))
        return batch

    if kind == "prefill":
        batch = {"tokens": sds((B, S), jnp.int32, (bspec, None)),
                 "lengths": sds((B,), jnp.int32, (bspec,))}
        if mcfg.arch_type == "vlm":
            batch["image_embeds"] = sds(
                (B, mcfg.num_image_tokens, mcfg.d_model),
                jnp.dtype(mcfg.dtype), (bspec, None, None))
        return batch

    # decode: one new token against a cache of seq_len
    cache_shapes = jax.eval_shape(
        partial(init_cache, mcfg, B, S, jnp.dtype(mcfg.dtype)))
    from repro.distributed.sharding import cache_specs
    cspecs = cache_specs(mcfg, cache_shapes, mesh,
                         kv_seq_shard=(opts or {}).get("kv_seq_shard", False))
    cache = _sharded_sds(cache_shapes, cspecs, mesh)
    return {
        "tokens": sds((B, 1), jnp.int32, (bspec, None)),
        "cache": cache,
        "lengths": sds((B,), jnp.int32, (bspec,)),
    }


def build_step(mcfg: ModelConfig, kind: str, parallel, max_len: int,
               opts=None):
    opts = opts or {}
    if kind in ("train", "encode_train"):
        opt = AdamWConfig(total_steps=1000)
        step = make_train_step(mcfg, opt, parallel,
                               remat=not opts.get("no_remat"))
        return step, ("params", "opt_state", "batch")
    if kind == "encode":
        def encode(params, batch):
            logits, _ = forward(mcfg, params, batch, parallel=parallel,
                                remat=False)
            return logits
        return encode, ("params", "batch")
    if kind == "prefill":
        def pf(params, batch):
            from repro.models.model import prefill
            return prefill(mcfg, params, batch, max_len=max_len,
                           parallel=parallel)
        return pf, ("params", "batch")
    if kind == "decode":
        def dec(params, tokens, cache, lengths):
            return decode_step(mcfg, params, tokens, cache, lengths,
                               parallel=parallel)
        return dec, ("params", "tokens", "cache", "lengths")
    raise ValueError(kind)


def run_one(arch: str, shape_name: str, multi_pod: bool,
            save: bool = True, opts=None) -> dict:
    opts = opts or {}
    mcfg0 = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mcfg, kind = plan_for(mcfg0, shape)
    mesh_name = "multipod" if multi_pod else "singlepod"
    if opts.pop("_tag_opt", False) or opts:
        mesh_name += "-opt"
    if mcfg is None:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped", "reason": kind}
        if save:
            _save(rec)
        return rec

    t0 = time.perf_counter()
    mesh = make_production_mesh(multi_pod=multi_pod)
    parallel = parallel_for(mesh, opts)
    chips = mesh.size

    with mesh:
        psds = params_sds(mcfg, mesh, opts)
        ins = input_specs(mcfg, shape, mesh, kind, opts)
        step, argnames = build_step(mcfg, kind, parallel,
                                    max_len=shape.seq_len, opts=opts)

        if kind in ("train", "encode_train"):
            opt_shapes = jax.eval_shape(
                partial(init_state, AdamWConfig()), psds)
            # mu/nu shard like params; step counter replicated
            pspecs = param_specs(psds, mesh)
            opt_sds = type(opt_shapes)(
                step=jax.ShapeDtypeStruct((), jnp.int32,
                                          sharding=NamedSharding(mesh, P())),
                mu=_sharded_sds(opt_shapes.mu, pspecs, mesh),
                nu=_sharded_sds(opt_shapes.nu, pspecs, mesh))
            jfn = jax.jit(step, donate_argnums=(0, 1))
            lowered = jfn.lower(psds, opt_sds, ins)
        elif kind == "decode":
            jfn = jax.jit(step, donate_argnums=(2,))
            lowered = jfn.lower(psds, ins["tokens"], ins["cache"],
                                ins["lengths"])
        else:
            jfn = jax.jit(step)
            lowered = jfn.lower(psds, ins)
        t_lower = time.perf_counter() - t0

        t1 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t1

        mem = compiled.memory_analysis()
        mem_rec = {}
        if mem is not None:
            for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                         "temp_size_in_bytes", "generated_code_size_in_bytes",
                         "alias_size_in_bytes"):
                v = getattr(mem, attr, None)
                if v is not None:
                    mem_rec[attr] = int(v)
        hlo = compiled.as_text()
        # placeholder CPU devices stand in for a v5e pod: name its peaks
        rl = from_compiled(compiled, chips, POD_DEVICE_KIND,
                           model_flops_for(mcfg, shape,
                                           "train" if "train" in kind else
                                           ("decode" if kind == "decode"
                                            else "prefill")),
                           hlo_text=hlo)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "kind": kind,
        "opts": opts,
        "status": "ok", "chips": chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem_rec,
        "roofline": rl.as_dict(),
        "attn_window": mcfg.attn_window,
        "hlo_bytes": len(hlo),
    }
    if save:
        _save(rec)
    return rec


def _save(rec):
    os.makedirs(OUT_DIR, exist_ok=True)
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}.json"
    with open(os.path.join(OUT_DIR, name), "w") as f:
        json.dump(rec, f, indent=1)


def optimized_opts(arch: str, shape_name: str) -> dict:
    """Beyond-paper optimization set, applied per step kind (EXPERIMENTS.md
    §Perf): measured wins on decode; packed dispatch *regresses* train and
    prefill (E_local x compute waste) so it stays decode-only, and
    flash-decoding seq sharding is for the full-cache decode_32k case."""
    shape = SHAPES_BY_NAME[shape_name]
    opts = {}
    if shape.name != "long_500k":
        # replicated kv projections regress the windowed batch=1 case
        # (measured 0.85-0.90x) — keep the sharded layout there
        opts["kv_aligned"] = True
    if shape.kind == "decode":
        opts["moe_packed"] = True
        if shape.name == "decode_32k":
            opts["kv_seq_shard"] = True
    return opts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="beyond-paper sharding/dispatch optimizations "
                         "(EXPERIMENTS.md §Perf): head-aligned KV, "
                         "flash-decoding seq-sharded caches, packed MoE "
                         "dispatch")
    args = ap.parse_args()

    archs = sorted(ASSIGNED) if (args.all or not args.arch) else [args.arch]
    shapes = [s.name for s in INPUT_SHAPES] if (args.all or not args.shape) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = ("multipod" if mp else "singlepod") + \
                    ("-opt" if args.optimized else "")
                fname = os.path.join(OUT_DIR,
                                     f"{arch}_{shape}_{mesh_name}.json")
                if args.skip_existing and os.path.exists(fname):
                    print(f"[skip-existing] {arch} {shape} {mesh_name}")
                    continue
                try:
                    rec = run_one(arch, shape, mp,
                                  opts=(optimized_opts(arch, shape)
                                        if args.optimized else {}))
                    if rec["status"] == "ok":
                        r = rec["roofline"]
                        print(f"[ok]   {arch:24s} {shape:12s} {mesh_name:9s} "
                              f"compile={rec['compile_s']:7.1f}s "
                              f"bottleneck={r['bottleneck']:10s} "
                              f"useful={r['useful_flops_ratio']:.2f}")
                    else:
                        print(f"[skip] {arch:24s} {shape:12s} {mesh_name:9s} "
                              f"{rec['reason']}")
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape, mesh_name, str(e)))
                    print(f"[FAIL] {arch} {shape} {mesh_name}: {e}")
    if failures:
        print(f"\n{len(failures)} FAILURES")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall dry-runs passed")


if __name__ == "__main__":
    main()
