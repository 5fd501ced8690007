"""Fig. 12 — scale-down latency across methods (appendix A.2), plus the
beyond-paper MEASURED drain-vs-migrate comparison on the real engine.

Two entry points (benchmarks/run.py registers both):

* ``run()`` (``--only fig12``) — the paper projection: cost-model
  scale-down latency per strategy and transition.
* ``run_measured()`` (``--only scaledown_migrate``, CI smoke) — each
  scale-down policy runs in its own subprocess on the real JAX engine
  (8 virtual host devices): boot at 6 devices with paged KV, park two
  LONG-output sequences in the doomed partition (plus short fillers that
  free survivor slots), then scale 6->4 mid-decode.

  - ``drain``  — the devices release only after the doomed sequences run
    to completion: scale-down wall is bounded by the longest in-flight
    output (the coarse release ElasticMoE §5.2 argues against).
  - ``migrate``— live KV blocks device-copy onto survivors through the
    background TransferEngine (MIGRATING phase) and the devices release
    in a handful of ticks.

  The run asserts the acceptance criteria end-to-end: migrate-mode wall
  ≥5x lower than drain under the long-output workload, tokens of the
  migrated sequences bit-identical to an unscaled run, zero preemptions
  in migrate mode, and a clean pool (``check_invariants``) after commit.
"""
import json
import subprocess
import sys

from benchmarks.common import (PAPER_MODELS, STRATEGY_LABELS, Table,
                               cpu_child_env, feasible, scale_cost)

TRANSITIONS = {
    "deepseek-v2-lite-16b": [(8, 6), (6, 4), (4, 2)],
    "qwen3-30b-a3b": [(10, 8), (8, 6), (6, 4)],
    "deepseek-v3": [(32, 16), (24, 16), (20, 16), (16, 2)],
}


def run() -> Table:
    strategies = {k: v for k, v in STRATEGY_LABELS.items()
                  if k != "horizontal"}
    t = Table("fig12_scaledown_latency_s",
              ["model", "transition"] + list(strategies))
    for model in PAPER_MODELS:
        for n0, n1 in TRANSITIONS[model]:
            row = [model, f"{n0}->{n1}"]
            for strat in strategies:
                if not feasible(strat, n0, n1):
                    row.append("n/a")
                    continue
                _, cost = scale_cost(model, n0, n1, strat)
                row.append(cost.scale_time_s)
            t.add(*row)
    return t


# ------------------------------------------- measured drain vs migrate

CODE = r"""
import json, time, sys
import numpy as np
from repro.configs.base import ModelConfig
from repro.core.topology import ElasticConfig
from repro.core.elastic_engine import ElasticServer
from repro.serving.workload import Request

MODE = sys.argv[1]
MCFG = ModelConfig(name="bench-moe", arch_type="moe", num_layers=4,
                   d_model=128, vocab_size=256, num_heads=8, num_kv_heads=8,
                   head_dim=16, d_ff=256, num_experts=24, top_k=2,
                   moe_d_ff=256, dtype="float32", capacity_factor=100.0)
c6 = ElasticConfig(dp=3, tp=2, devices=(0, 1, 2, 3, 4, 5))
c4 = ElasticConfig(dp=2, tp=2, devices=(0, 1, 2, 3))
LONG = 300                      # doomed sequences' output length (ticks the
                                # drain must wait out; migrate does not)

def build(cfg):
    srv = ElasticServer(MCFG, tp=2, batch_per_replica=2, max_len=512,
                        prefill_buckets=(32,), seed=0, kv_mode="paged",
                        kv_block_size=32, scaledown=MODE)
    srv.boot(cfg)
    return srv

def reqs():
    rng = np.random.default_rng(0)
    # rids 0-3: short fillers occupying the survivor slots, freeing them
    # before the scale command; rids 4-5: long outputs in the doomed slots
    outs = [8, 8, 12, 12, LONG, LONG]
    return [Request(i, 0.0, 24, o, prompt=rng.integers(0, 256, 24))
            for i, o in enumerate(outs)]

srv, rs = build(c6), reqs()
for r in rs:
    srv.submit(r)
t, n = 0.0, 0
while any(srv.requests[i].finish_s is None for i in range(4)):
    srv.tick(t); t += 0.1; n += 1      # fillers finish; 4,5 keep decoding
    assert n < 2000
assert all(srv.engine.slots[s].active for s in (4, 5))

t0 = time.perf_counter()
task = srv.start_scale(c4)
while not task.done:
    srv.tick(t); t += 0.1; n += 1
    task.advance(t)
    assert n < 20000
scale_wall = time.perf_counter() - t0

while any(r.finish_s is None for r in rs):
    srv.tick(t); t += 0.1; n += 1
    assert n < 20000
assert srv.hmm.active_cfg.ndev == 4
assert srv.hmm.kv_blocks.num_partitions == 2
srv.hmm.kv_blocks.check_invariants()
assert srv.engine.kv_stats()["used_blocks"] == 0

# unscaled reference at the TARGET config: bit-identical tokens expected
ref, rs2 = build(c4), reqs()
for r in rs2:
    ref.submit(r)
t2, n2 = 0.0, 0
while any(r.finish_s is None for r in rs2):
    ref.tick(t2); t2 += 0.1; n2 += 1
    assert n2 < 20000
for r in rs2:
    assert srv.engine.generated[r.rid] == ref.engine.generated[r.rid], r.rid

ev = srv.events[-1]
print("JSON:" + json.dumps(dict(
    mode=MODE, scale_wall_s=scale_wall,
    migrated_blocks=ev.migrated_blocks, migration_bytes=ev.migration_bytes,
    preemptions=srv.engine.preemptions,
    tokens={str(r.rid): srv.engine.generated[r.rid] for r in rs})))
"""


def _run_mode(mode: str) -> dict:
    env = cpu_child_env()
    r = subprocess.run([sys.executable, "-c", CODE, mode], env=env,
                       capture_output=True, text=True, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-3000:])
    return json.loads([l for l in r.stdout.splitlines()
                       if l.startswith("JSON:")][0][5:])


def run_measured() -> Table:
    drain = _run_mode("drain")
    migrate = _run_mode("migrate")
    # acceptance: ≥5x faster release, identical tokens, real migration,
    # no recompute fallback needed
    assert migrate["scale_wall_s"] * 5 <= drain["scale_wall_s"], \
        (migrate["scale_wall_s"], drain["scale_wall_s"])
    assert migrate["tokens"] == drain["tokens"]
    assert migrate["migrated_blocks"] > 0 and drain["migrated_blocks"] == 0
    assert migrate["preemptions"] == 0

    t = Table("scaledown_measured",
              ["scaledown", "scale_wall_s", "migrated_blocks",
               "migration_bytes", "preemptions"])
    for row in (drain, migrate):
        t.add(row["mode"], row["scale_wall_s"], row["migrated_blocks"],
              row["migration_bytes"], row["preemptions"])
    return t


def main():
    t = run()
    t.show()
    for r in t.rows:
        ours = r[2]
        base = min(v for v in r[3:] if isinstance(v, float))
        print(f"  {r[0]} {r[1]}: {ours:.2f}s vs {base:.2f}s "
              f"({ours / base:.2f}x of fastest baseline)")
    m = run_measured()
    m.show()
    d, g = m.rows[0][1], m.rows[1][1]
    print(f"\nmeasured drain {d:.2f}s vs migrate {g:.2f}s "
          f"({d / g:.1f}x lower scale-down wall, bit-identical tokens)")


if __name__ == "__main__":
    main()
