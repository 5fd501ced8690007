#!/usr/bin/env python3
"""Chip smoke test: the serving path on a TPU at published widths.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # live 2 -> 4 chip scale-up, compared
                                    # with an unscaled 2-chip run

Serves qwen3-30b-a3b (arXiv:2505.09388) at every published width, cut to
6 of its 48 layers, with random weights drawn from ``--seed``, through the
launcher's own entry points (``repro.launch.serve``): an ``ElasticServer``
with paged KV, chunked prefill, the pooled expert store, overlapped staging
and dropless routing.  Every serving kernel compiles to Mosaic.

One chip: prints the model and its cut, compile seconds per executable,
whether the compiled decode and chunk-prefill executables hold the
``paged_attention`` and ``paged_gmm`` kernels (``tpu_custom_call``), the
requests answered with their token counts, a parity check of each kernel
against its ``kernels/ref.py`` oracle at these widths, a check of every
request's first token against a plain forward pass over the same weights,
and ``peak_bytes_in_use``.  ``--chips 4`` runs only the scaling phase.

The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits non-zero without that line when JAX finds no TPU, when
``REPRO_PAGED_IMPL``/``REPRO_POOLED_IMPL`` ask for the jnp oracles, when a
compiled step lacks a kernel, when a request goes unanswered, or when any
phase raises.  It runs in one process: a chip belongs to one process.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time
from pathlib import Path

MODEL, LAYERS = "qwen3-30b-a3b", 6
REQUESTS, PROMPTS, OUTPUTS = 8, (64, 1024), (32, 128)
# --chips 4: decode slots per replica; the first wave fills both replicas
# of the 2-chip instance with decodes long enough to outlast the staging
# window, so the scale event opens while every one of them is decoding
SCALE_BPR, SCALE_PROMPTS, SCALE_OUTPUTS = 4, (64, 256), (1536, 1792)
KERNELS = ("paged_attention", "paged_gmm")


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def oracle_experts():
    """Trace the reference forward with the jnp expert oracle, so the
    reference shares no kernel with the path under test."""
    old = os.environ.get("REPRO_POOLED_IMPL")
    os.environ["REPRO_POOLED_IMPL"] = "ref"
    try:
        yield
    finally:
        if old is None:
            del os.environ["REPRO_POOLED_IMPL"]
        else:
            os.environ["REPRO_POOLED_IMPL"] = old


def memory_line(jax, label):
    parts = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        parts.append(f"dev{d.id} in_use={st.get('bytes_in_use', 0)} "
                     f"peak={st.get('peak_bytes_in_use', 0)}")
    print(f"memory {label}: " + "; ".join(parts))


def report_compiles(srv, ecfg):
    """Compile seconds per executable, and the kernels each step holds."""
    inst = srv.imm.preinitialize(ecfg)
    for name, sec in inst.compile_times.items():
        print(f"compile {ecfg.describe()} {name}: {sec:.2f} s")
    for name in ("decode", f"chunk_prefill_{srv.prefill_chunk}"):
        text = inst.compiled[name].as_text()
        calls = text.count('custom_call_target="tpu_custom_call"')
        found = [k for k in KERNELS if f"%{k}" in text]
        print(f"kernels {name}: tpu_custom_call x{calls}, {found}")
        check(calls > 0 and found == list(KERNELS),
              f"{name} lacks a compiled kernel: {found} ({calls} calls)")


def check_answers(srv, reqs, vocab):
    gen = srv.engine.generated
    for r in reqs:
        toks = gen.get(r.rid, [])
        print(f"request {r.rid}: prompt {r.prompt_len} tokens, "
              f"answered {len(toks)}/{r.output_len} tokens")
        check(r.finish_s is not None and len(toks) == r.output_len,
              f"request {r.rid} unanswered ({len(toks)}/{r.output_len})")
        check(all(0 <= t < vocab for t in toks),
              f"request {r.rid} produced a token outside the vocabulary")
    print(f"requests answered: {len(reqs)}/{len(reqs)}")


def kernel_parity(jax, seed):
    """Each serving kernel against its jnp oracle at the model's widths."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), bf)

    B, H, KVH, hd, NB, bs, MB = 16, 32, 4, 128, 1024, 16, 64
    kp, vp, q = rand(NB, bs, KVH, hd), rand(NB, bs, KVH, hd), rand(B, H, hd)
    bt = jnp.asarray(rng.permutation(NB)[:B * MB].reshape(B, MB), jnp.int32)
    lens = jnp.asarray(rng.integers(1, MB * bs + 1, B), jnp.int32)
    qc = rand(1, 256, H, hd)
    ctx, qlen = jnp.asarray([700], jnp.int32), jnp.asarray([200], jnp.int32)
    E, C, D, F = 128, 16, 2048, 768
    tbl = jnp.asarray(rng.permutation(E), jnp.int32)
    # weights at the init scale of the model (~1/sqrt(D)), so outputs are O(1)
    pi, pg = rand(E, D, F) * 0.03, rand(E, D, F) * 0.03
    po = rand(E, F, D) * 0.03
    x = rand(E, C, D)
    pairs = {
        "paged_attention decode": (
            ops.block_paged_decode_attention(q, kp, vp, bt, lens,
                                             impl="kernel"),
            ref.block_paged_decode_attention_ref(q, kp, vp, bt, lens)),
        "paged_attention chunk": (
            ops.mixed_block_paged_attention(qc, kp, vp, bt[:1], ctx, qlen,
                                            impl="kernel")[:, :200],
            ref.mixed_block_paged_attention_ref(qc, kp, vp, bt[:1], ctx,
                                                qlen)[:, :200]),
        "paged_gmm expert ffn": (
            ops.paged_expert_ffn(tbl, tbl, tbl, pi, pg, po, x,
                                 impl="kernel"),
            ref.paged_expert_ffn_ref(tbl, tbl, tbl, pi, pg, po, x)),
    }
    for name, (got, want) in pairs.items():
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want)))
        scale = float(np.max(np.abs(want)))
        print(f"parity {name}: shape {got.shape}, max |kernel - oracle| "
              f"{err:.4g} (oracle max |x| {scale:.4g})")
        check(np.all(np.isfinite(got)), f"{name}: non-finite output")
        # both sides read bf16 and accumulate in f32; they differ by the
        # bf16 rounding of outputs and intermediates (2^-8 relative), and a
        # wrong block, head or page moves the result by O(1)
        check(err <= 2e-2 * max(1.0, scale), f"{name}: kernel != oracle")


def reference_first_tokens(jax, srv, cfg, reqs):
    """Every request's first token against a plain forward pass over the
    same weights (dense masked attention, the jnp expert oracle).  bf16
    can reorder near-ties, so the engine's token must score within 1% of
    the reference logit range of the reference's best token."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model as M
    S = max(PROMPTS[1], max(r.prompt_len for r in reqs))
    with oracle_experts():
        fwd = jax.jit(lambda p, t: M.forward(cfg, p, {"tokens": t},
                                             remat=False)[0][0])
        exact = 0
        for r in reqs:
            toks = np.zeros((1, S), np.int32)
            toks[0, :r.prompt_len] = r.prompt
            logits = np.asarray(fwd(srv.engine.params, jnp.asarray(toks))
                                [r.prompt_len - 1], np.float32)
            tok = srv.engine.generated[r.rid][0]
            span = float(logits.max() - logits.min())
            gap = float(logits.max() - logits[tok])
            exact += int(tok == int(logits.argmax()))
            check(np.all(np.isfinite(logits)), "reference logits non-finite")
            check(gap <= 0.01 * span,
                  f"request {r.rid}: first token {tok} scores {gap:.4g} "
                  f"below the reference best (range {span:.4g})")
    print(f"reference first tokens: {exact}/{len(reqs)} equal to the "
          f"reference argmax, all within 1% of its logit range")


def one_chip(jax, args, cfg):
    from repro.launch import serve
    srv = serve.build_server(cfg, seed=args.seed)
    ecfg = serve.device_ladder(1)[0]
    t0 = time.perf_counter()
    srv.boot(ecfg)
    print(f"boot {ecfg.describe()}: {time.perf_counter() - t0:.2f} s "
          "(weights + compile)")
    report_compiles(srv, ecfg)
    memory_line(jax, "after boot")
    reqs = serve.synthetic_requests(REQUESTS, cfg.vocab_size,
                                    seed=args.seed, prompt_range=PROMPTS,
                                    output_range=OUTPUTS)
    t0 = time.perf_counter()
    serve.run_requests(srv, reqs)
    wall = time.perf_counter() - t0
    check_answers(srv, reqs, cfg.vocab_size)
    ntok = sum(r.output_len for r in reqs)
    print(f"served {len(reqs)} requests, {ntok} output tokens in {wall:.2f} s"
          " wall (includes first-call warm-up; not a benchmark)")
    kernel_parity(jax, args.seed)
    reference_first_tokens(jax, srv, cfg, reqs)
    memory_line(jax, "after serving")


def scale_phase(jax, args, cfg):
    """Boot on 2 chips, scale to 4 live, compare with an unscaled run.

    The first wave has long outputs so its sequences decode through the
    staging window and across the commit on the same KV blocks.  The
    second wave arrives after the commit with more requests than 2 chips'
    slots, so it lands on the new chips too."""
    from repro.launch import serve
    ladder = serve.device_ladder(1)
    check(len(ladder) >= 4, f"--chips 4 needs 4 devices; found {len(ladder)}")
    two, four = ladder[1], ladder[3]

    def waves():
        first = serve.synthetic_requests(two.dp * SCALE_BPR, cfg.vocab_size,
                                         seed=args.seed,
                                         prompt_range=SCALE_PROMPTS,
                                         output_range=SCALE_OUTPUTS)
        second = serve.synthetic_requests(4 * SCALE_BPR, cfg.vocab_size,
                                          seed=args.seed + 1,
                                          prompt_range=(64, 512),
                                          output_range=(16, 32),
                                          first_rid=len(first))
        return first, second

    def server():
        return serve.build_server(cfg, batch_per_replica=SCALE_BPR,
                                  seed=args.seed)

    # unscaled reference run on 2 chips
    srv = server()
    srv.boot(two)
    report_compiles(srv, two)
    first, second = waves()
    serve.run_requests(srv, first)
    serve.run_requests(srv, second)
    check_answers(srv, first + second, cfg.vocab_size)
    unscaled = {r.rid: list(srv.engine.generated[r.rid])
                for r in first + second}
    srv.hmm.transfer_engine().shutdown()
    del srv
    gc.collect()

    # the same requests, scaled 2 -> 4 chips while the first wave decodes
    srv = server()
    srv.boot(two)
    memory_line(jax, f"after boot {two.describe()}")
    first, second = waves()
    opened, committed = {}, {}

    def tokens():
        return {r.rid: len(srv.engine.generated.get(r.rid, []))
                for r in first}

    def switchover():
        committed.update(tokens())          # the commit instant
        commit()

    commit, srv.switchover = srv.switchover, switchover

    def scale(tick, now):
        if opened or any(r.first_token_s is None for r in first):
            return None
        opened.update(tokens())
        print(f"scale event opened at tick {tick}: {two.describe()} -> "
              f"{four.describe()}, {sum(opened.values())} tokens so far")
        return four

    t0 = time.perf_counter()
    tasks = serve.run_requests(srv, first, scale=scale)
    check(len(tasks) == 1 and srv.hmm.active_cfg == four,
          f"scale event did not commit (active {srv.hmm.active_cfg})")
    ev = srv.events[-1]
    st = ev.stats
    print(f"scale event {ev.src} -> {ev.dst}: staging {ev.stage_s:.2f} s, "
          f"switchover {ev.switch_s:.2f} s, serve-loop stall "
          f"{ev.stall_s:.2f} s (includes the target's AOT compile), "
          f"zero-copy {st.zero_copy_bytes} B, p2p {st.p2p_bytes} B "
          f"(experts {st.expert_p2p_bytes} B), init {st.init_bytes} B")
    report_compiles(srv, four)
    live = [r.rid for r in first if committed[r.rid] < r.output_len]
    print(f"tokens decoded while the scale event was in flight: "
          f"{sum(committed.values()) - sum(opened.values())}; requests "
          f"decoding across the commit: {live}")
    memory_line(jax, f"after commit {four.describe()}")
    serve.run_requests(srv, second)
    print(f"served {len(first) + len(second)} requests across the scale "
          f"event in {time.perf_counter() - t0:.2f} s wall")
    check_answers(srv, first + second, cfg.vocab_size)
    same, diverged = 0, []
    for r in first + second:
        a, b = unscaled[r.rid], srv.engine.generated[r.rid]
        n = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
        if n is None:
            same += 1
            continue
        diverged.append(f"request {r.rid} first differs at token {n}")
        check(n >= opened.get(r.rid, 0),
              f"request {r.rid} diverged at token {n}, before the scale "
              f"event opened ({opened[r.rid]} tokens)")
    print(f"tokens vs unscaled 2-chip run: {same}/{len(unscaled)} requests "
          "identical" + "".join(f"; {d}" for d in diverged))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro" / "launch" / "serve.py").is_file():
        print("FAIL: the repro package is not beside this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    try:
        from repro.kernels.ops import IMPL_ENV
        forced = {v: os.environ[v] for v in IMPL_ENV
                  if os.environ.get(v, "auto") not in ("auto", "kernel")}
        check(not forced, f"kernel choice overridden: {forced}; the smoke "
              "runs the compiled kernels only")
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch import serve
        print(f"compile cache: {enable_compile_cache()}")
        dev = jax.devices()[0]
        check(dev.platform == "tpu", f"no TPU: JAX runs on {dev.platform}")
        print(f"device: {dev.device_kind} x{len(jax.devices())}")
        cfg = serve.model_config(MODEL, LAYERS)
        print(f"model: {serve.describe_model(cfg, args.seed)}")
        if args.chips == 4:
            scale_phase(jax, args, cfg)
        else:
            one_chip(jax, args, cfg)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
