"""Elastic serving launcher.

  python -m repro.launch.serve --arch qwen3-30b-a3b --layers 6 --requests 8
  JAX_PLATFORMS=cpu python -m repro.launch.serve --arch qwen3-30b-a3b-smoke \
      --tp 2 --batch-per-replica 1 --requests 4 --autoscale

Serves any ``REGISTRY`` model at its published widths; ``--layers`` cuts
only the depth.  Devices and the scaling ladder come from ``jax.devices()``
(``dp = 1 .. n_devices // tp``; ``--tp 1`` allowed).  The ``-smoke`` configs
(d_model 256, 4 experts, f32) are CPU toys: they run only under
``JAX_PLATFORMS=cpu``, where the launcher also asks XLA for
``REPRO_SERVE_DEVICES`` (default 8) virtual host devices.

The serving path is the one the system is built around: paged KV with
chunked prefill where the architecture supports them, the pooled expert
store for MoE models, overlapped staging, and dropless routing
(``capacity_factor = E / top_k``), so a token's output never depends on
the batch it rides in.  Weights are random, drawn from ``--seed``.  With
``--autoscale`` the SLO-aware coordinator scales up the ladder live when
requests queue; one decode slot per replica makes four requests queue.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Callable, List, Optional

import numpy as np

from repro.configs import REGISTRY, get_config
from repro.configs.base import ModelConfig
from repro.core.topology import ElasticConfig
from repro.serving.workload import Request

# the serving shape: decode slots per DP replica, context per sequence (a
# 1024-token prompt plus its answer), prefill chunk, KV block
BATCH_PER_REPLICA, MAX_LEN, PREFILL_CHUNK, KV_BLOCK = 16, 2048, 256, 16
TICK_S = 0.05                       # serve-loop clock per engine tick


def force_cpu_devices() -> None:
    """CPU runs only: ask XLA for ``REPRO_SERVE_DEVICES`` virtual host
    devices.  Takes effect only before the first device query."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        n = int(os.environ.get("REPRO_SERVE_DEVICES", "8"))
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()


def model_config(arch: str, layers: Optional[int] = None) -> ModelConfig:
    """The config to serve: ``<id>`` at published widths (``layers`` cuts
    the depth and nothing else), or ``<id>-smoke``, the reduced CPU toy."""
    import jax
    if arch.endswith("-smoke"):
        if jax.default_backend() != "cpu":
            raise SystemExit(f"{arch} is a CPU toy; serve a published config "
                             "on an accelerator")
    elif arch not in REGISTRY:
        raise SystemExit(f"unknown arch {arch!r}; choose from "
                         f"{sorted(REGISTRY)}")
    cfg = get_config(arch)
    if layers:
        if not cfg.first_k_dense < layers <= cfg.num_layers:
            raise SystemExit(f"--layers must be in ({cfg.first_k_dense}, "
                             f"{cfg.num_layers}]")
        cfg = dataclasses.replace(cfg, num_layers=layers)
    if cfg.is_moe:
        cfg = dataclasses.replace(
            cfg, capacity_factor=cfg.num_experts / cfg.top_k)
    return cfg


def describe_model(cfg: ModelConfig, seed: int) -> str:
    """One line: the model, its source, its widths and what was cut."""
    base = REGISTRY.get(cfg.name)
    parts = [f"{cfg.name}" + (f" [{cfg.source}]" if cfg.source else ""),
             f"d_model {cfg.d_model}",
             f"heads {cfg.num_heads}/{cfg.num_kv_heads} x "
             f"{cfg.resolved_head_dim}"]
    if cfg.is_moe:
        parts.append(f"experts {cfg.num_experts} top-{cfg.top_k} x "
                     f"{cfg.moe_d_ff}")
    parts += [f"vocab {cfg.vocab_size}", cfg.dtype,
              f"layers {cfg.num_layers}" + (
                  f" of {base.num_layers} (depth cut)"
                  if base and base.num_layers != cfg.num_layers else ""),
              f"random weights from seed {seed}"]
    if cfg.omits:
        parts.append(f"not modelled: {cfg.omits}")
    return "; ".join(parts)


def device_ladder(tp: int = 1) -> List[ElasticConfig]:
    """One rung per DP degree the visible devices allow."""
    import jax
    n = len(jax.devices())
    if n < tp:
        raise SystemExit(f"tp={tp} needs {tp} devices; found {n}")
    return [ElasticConfig(dp=d, tp=tp, devices=tuple(range(tp * d)))
            for d in range(1, n // tp + 1)]


def build_server(cfg: ModelConfig, *, tp: int = 1,
                 batch_per_replica: int = BATCH_PER_REPLICA, seed: int = 0,
                 policy=None):
    """An ``ElasticServer`` on the serving path ``cfg`` supports."""
    from repro.core.elastic_engine import ElasticServer
    from repro.models import model as M
    paged = M.paged_cache_supported(cfg)
    chunk = PREFILL_CHUNK if M.chunk_prefill_supported(cfg) else 0
    return ElasticServer(
        cfg, tp=tp, batch_per_replica=batch_per_replica, max_len=MAX_LEN,
        prefill_buckets=(chunk or 64,), policy=policy, seed=seed,
        kv_mode="paged" if paged else "dense", kv_block_size=KV_BLOCK,
        expert_mode="pooled" if cfg.is_moe else "dense",
        staging="overlap", prefill_chunk=chunk)


def synthetic_requests(n: int, vocab: int, *, seed: int = 0,
                       prompt_range=(64, 1024), output_range=(32, 128),
                       spacing_s: float = 0.0,
                       first_rid: int = 0) -> List[Request]:
    """``n`` requests with random prompts; lengths uniform in the inclusive
    ranges, arrivals ``spacing_s`` apart on the serve loop's clock."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        p = int(rng.integers(prompt_range[0], prompt_range[1] + 1))
        o = int(rng.integers(output_range[0], output_range[1] + 1))
        reqs.append(Request(first_rid + i, spacing_s * i, p, o,
                            prompt=rng.integers(0, vocab, p)))
    return reqs


def run_requests(srv, reqs: List[Request], *,
                 scale: Optional[Callable[[int, float],
                                          Optional[ElasticConfig]]] = None,
                 max_ticks: int = 100_000) -> list:
    """Drive ``srv`` until every request has finished; returns the scale
    tasks it ran.  The clock advances ``TICK_S`` per engine tick.
    ``scale(tick, now)`` is asked every tick while no scale event is in
    flight; a returned target opens a live scale event (``start_scale``)
    that advances one poll per tick while serving continues."""
    tasks, task, i, t = [], None, 0, 0.0
    for tick in range(max_ticks):
        while i < len(reqs) and reqs[i].arrival_s <= t:
            srv.submit(reqs[i])
            i += 1
        if task is None and scale is not None:
            target = scale(tick, t)
            if target is not None:
                task = srv.start_scale(target)
                tasks.append(task)
        if task is not None:
            task.advance(t)
            if task.done:
                task = None
        srv.tick(t)
        t += TICK_S
        if i == len(reqs) and task is None \
                and all(r.finish_s is not None for r in reqs):
            return tasks
    raise RuntimeError(f"requests unfinished after {max_ticks} ticks")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="qwen3-30b-a3b",
                    help=f"one of {sorted(REGISTRY)}, or <id>-smoke on CPU")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--batch-per-replica", type=int,
                    default=BATCH_PER_REPLICA, help="decode slots per replica")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--autoscale", action="store_true")
    args = ap.parse_args(argv)

    force_cpu_devices()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.core.coordinator import ScalingPolicy
    from repro.serving.metrics import SLO, summarize

    cfg = model_config(args.arch, args.layers)
    if not cfg.has_decode:
        raise SystemExit(f"{cfg.name} is encoder-only: no serving decode")
    print(describe_model(cfg, args.seed))
    ladder = device_ladder(args.tp)
    slo = SLO(ttft_s=2.0, tpot_s=1.0)
    policy = ScalingPolicy(slo=slo, window=8, cooldown_s=2.0,
                           queue_scale_up=3) if args.autoscale else None
    srv = build_server(cfg, tp=args.tp,
                       batch_per_replica=args.batch_per_replica,
                       seed=args.seed, policy=policy)
    level = 0
    srv.boot(ladder[level])
    reqs = synthetic_requests(args.requests, cfg.vocab_size, seed=args.seed,
                              spacing_s=0.15)

    def autoscale(tick, now):
        nonlocal level
        if srv.autoscale_decision(now) == "up" and level + 1 < len(ladder):
            level += 1
            print(f"[t={now:.2f}] scaling up -> {ladder[level].describe()}")
            return ladder[level]
        return None

    run_requests(srv, reqs, scale=autoscale if args.autoscale else None)
    print(summarize(reqs, slo))
    return srv, reqs


if __name__ == "__main__":
    main()
