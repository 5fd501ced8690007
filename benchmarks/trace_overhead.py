"""Tracing overhead A/B (beyond-paper CI smoke) — the serve loop with the
NULL tracer vs a live ``obs.Tracer`` with routing histograms enabled.

Each arm runs in its own subprocess (8 virtual host devices, cold jit
caches — in-process A/B would let the second arm ride the first arm's
compile cache): boot at 4 devices, decode a live 4-request batch to
completion, and report steady-state tokens/s over the serve loop.  The
``traced`` arm installs a Tracer, samples expert-routing histograms every
other tick, exports the Chrome trace, and validates it; the ``null`` arm
leaves the default ``NULL_TRACER`` installed, exercising the disabled
fast path every instrumented call site takes when tracing is off.

The run asserts the disabled path keeps >= 98%% of the traced arm's
tokens/s — the instrumentation's "free when off" budget (DESIGN.md §9).
The exported trace artifact path is printed so CI can upload it.
"""
import json
import os
import subprocess
import sys
import tempfile

from benchmarks.common import Table, cpu_child_env

CODE = r"""
import json, time, sys
import numpy as np
from repro import obs
from repro.configs.base import ModelConfig
from repro.core.topology import ElasticConfig
from repro.core.elastic_engine import ElasticServer
from repro.serving.workload import Request

MODE = sys.argv[1]                       # "null" | "traced"
TRACE_PATH = sys.argv[2] if len(sys.argv) > 2 else None
MCFG = ModelConfig(name="bench-moe", arch_type="moe", num_layers=4,
                   d_model=128, vocab_size=256, num_heads=8, num_kv_heads=8,
                   head_dim=16, d_ff=256, num_experts=24, top_k=2,
                   moe_d_ff=256, dtype="float32", capacity_factor=100.0)

tr = None
if MODE == "traced":
    tr = obs.install(obs.Tracer(capacity=500_000))

srv = ElasticServer(MCFG, tp=2, batch_per_replica=2, max_len=512,
                    prefill_buckets=(32,), seed=0,
                    routing_sample_every=2 if MODE == "traced" else 0)
srv.boot(ElasticConfig(dp=2, tp=2, devices=(0, 1, 2, 3)))

rng = np.random.default_rng(0)
reqs = [Request(i, 0.0, 16, 200, prompt=rng.integers(0, 256, 16))
        for i in range(4)]
for r in reqs:
    srv.submit(r)

def total_tokens():
    return sum(len(v) for v in srv.engine.generated.values())

t, n = 0.0, 0
for _ in range(10):                      # warmup: admit + compile settle
    srv.tick(t); t += 0.1; n += 1

tok0, t0 = total_tokens(), time.perf_counter()
while any(r.finish_s is None for r in reqs):
    srv.tick(t); t += 0.1; n += 1
    assert n < 20000
wall = time.perf_counter() - t0
toks = total_tokens() - tok0

n_events = 0
if MODE == "traced":
    doc = obs.write_chrome_trace(TRACE_PATH, tr,
                                 extra_metadata={"bench": "trace_overhead"})
    obs.validate_trace(doc)
    n_events = len([r for r in doc["traceEvents"] if r["ph"] != "M"])
    rt = srv.routing_stats()
    assert rt is not None and rt["samples"] >= 1, rt
else:
    assert obs.get_tracer() is obs.NULL_TRACER
    assert obs.NULL_TRACER.events() == []

print("JSON:" + json.dumps(dict(
    mode=MODE, wall_s=wall, tokens=toks, tok_s=toks / wall,
    n_events=n_events)))
"""


def _run_mode(mode: str, trace_path: str | None = None) -> dict:
    env = cpu_child_env()
    argv = [sys.executable, "-c", CODE, mode] + (
        [trace_path] if trace_path else [])
    r = subprocess.run(argv, env=env, capture_output=True, text=True,
                       timeout=900)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-3000:])
    return json.loads([l for l in r.stdout.splitlines()
                       if l.startswith("JSON:")][0][5:])


def _best_of(n: int, mode: str, trace_path: str | None = None) -> dict:
    # host-timing noise only ever slows a run down; best-of-N is the
    # noise-robust estimator of each arm's true throughput
    runs = [_run_mode(mode, trace_path) for _ in range(n)]
    return max(runs, key=lambda r: r["tok_s"])


def run():
    trace_path = os.path.join(tempfile.gettempdir(), "trace_overhead.json")
    traced = _best_of(2, "traced", trace_path)
    null = _best_of(2, "null")
    assert traced["n_events"] > 0
    # the budget: instrumentation must be free when off — the NULL_TRACER
    # arm keeps >= 98% of the traced arm's throughput (it should be the
    # faster arm; the 2% floor absorbs host-timing noise)
    assert null["tok_s"] >= 0.98 * traced["tok_s"], (null["tok_s"],
                                                     traced["tok_s"])
    overhead_pct = 100.0 * (1.0 - traced["tok_s"] / null["tok_s"])

    t = Table("trace_overhead",
              ["tracer", "tokens", "wall_s", "tok_s", "events",
               "overhead_pct"])
    t.add("null", null["tokens"], null["wall_s"], null["tok_s"], 0,
          float("nan"))
    t.add("traced", traced["tokens"], traced["wall_s"], traced["tok_s"],
          traced["n_events"], overhead_pct)
    print(f"trace artifact: {trace_path}")
    return [t]


def main():
    for t in run():
        t.show()
    print("\ntracing A/B: disabled fast path holds >= 98% of traced "
          "throughput (asserted above)")


if __name__ == "__main__":
    main()
