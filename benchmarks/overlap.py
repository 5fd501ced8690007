"""Overlapped staging (beyond-paper CI smoke) — serial vs background
TransferEngine scale-up on the REAL engine, plus the cost-model projection
on the paper models.

Two tables:

* ``overlap_measured`` — each staging mode runs in its own subprocess
  (8 virtual host devices, cold jit caches — in-process A/B timing would
  let the second run ride the first run's compile cache): boot at 4
  devices, pre-initialize the target, then scale 4->6 while decoding a
  live batch.  Every transfer op is padded by a fixed 40 ms in BOTH modes
  so the tiny host model's staging window emulates paper-scale transfer
  durations (serial pays the pad inline on the serve loop, overlap on the
  background workers; bytes and tokens are unaffected).  Reported per mode: scale-up wall-clock (``start_scale`` ->
  task DONE), decode ticks that ran while transfer ops were in flight,
  tokens/s during the scaling window, serve-loop stall, and overlap
  efficiency (Σ per-op transfer time / staging wall-clock).  The run
  asserts the paper's decoupling claim end-to-end: overlap wall-clock
  strictly below serial, byte-identical ``TransferStats``, and
  bit-identical tokens between the two modes.
* ``overlap_projected`` — ``costmodel.plan_cost(staging=...)`` on the
  paper models: overlapped scale-up latency (warmup hidden under the
  transfer window, transfers slowed by the HBM/link contention factor)
  and modelled decode-stall seconds vs the serial sum (DESIGN.md §3).
"""
import json
import subprocess
import sys

from benchmarks.common import PAPER_MODELS, Table, cpu_child_env, scale_cost

CODE = r"""
import json, time, sys
import numpy as np
from repro.configs.base import ModelConfig
from repro.core.topology import ElasticConfig
from repro.core.elastic_engine import ElasticServer
from repro.core.hmm import TransferStats
from repro.serving.driver import ScalePhase
from repro.serving.workload import Request

MODE = sys.argv[1]
MCFG = ModelConfig(name="bench-moe", arch_type="moe", num_layers=4,
                   d_model=128, vocab_size=256, num_heads=8, num_kv_heads=8,
                   head_dim=16, d_ff=256, num_experts=24, top_k=2,
                   moe_d_ff=256, dtype="float32", capacity_factor=100.0)
c4 = ElasticConfig(dp=2, tp=2, devices=(0, 1, 2, 3))
c6 = ElasticConfig(dp=3, tp=2, devices=(0, 1, 2, 3, 4, 5))

srv = ElasticServer(MCFG, tp=2, batch_per_replica=2, max_len=512,
                    prefill_buckets=(32,), seed=0, staging=MODE)
srv.boot(c4)
srv.preinitialize(c6)          # warm compile, as the driver's prewarm does

# pad every transfer op by a fixed 40 ms — IDENTICALLY in both modes — so
# the tiny host model's staging window emulates paper-scale transfer
# durations and tokens/s during the window is measurable.  Byte accounting
# and tokens are unaffected; the pad cancels out in the serial-vs-overlap
# comparison (serial pays it inline on the serve loop, overlap on the
# background workers).
OP_PAD_S = 0.04
_orig_unit = srv.hmm._stage_unit
def _padded_unit(*a, **k):
    time.sleep(OP_PAD_S)
    return _orig_unit(*a, **k)
srv.hmm._stage_unit = _padded_unit

rng = np.random.default_rng(0)
reqs = [Request(i, 0.0, 16, 300, prompt=rng.integers(0, 256, 16))
        for i in range(4)]
for r in reqs:
    srv.submit(r)

def total_tokens():
    return sum(len(v) for v in srv.engine.generated.values())

t, n = 0.0, 0
for _ in range(5):             # settle the batch before the scale command
    srv.tick(t); t += 0.1; n += 1

t0 = time.perf_counter()
task = srv.start_scale(c6)
tok0, in_flight_ticks, stage_wall = total_tokens(), 0, None
while not task.done:
    srv.tick(t); t += 0.1; n += 1
    if task.phase is ScalePhase.STAGING and srv.hmm.staging_in_flight:
        in_flight_ticks += 1
    task.advance(t)
    if stage_wall is None and task.event is not None:
        stage_wall = time.perf_counter() - t0   # STAGING (∥ COMPILING) done
    assert n < 20000
scale_wall = time.perf_counter() - t0
window_toks = total_tokens() - tok0

while any(r.finish_s is None for r in reqs):
    srv.tick(t); t += 0.1; n += 1
    assert n < 20000

st = task.stage_stats
print("JSON:" + json.dumps(dict(
    mode=MODE, scale_wall_s=scale_wall, stage_wall_s=stage_wall,
    in_flight_ticks=in_flight_ticks,
    window_toks=window_toks, window_tok_s=window_toks / scale_wall,
    stall_s=task.stall_s, overlap_eff=task.overlap_efficiency,
    stats={f: getattr(st, f) for f in TransferStats.BYTE_FIELDS},
    tokens={str(r.rid): srv.engine.generated[r.rid] for r in reqs})))
"""

TRANSITIONS = [(4, 6), (6, 8)]


def _run_mode(mode: str) -> dict:
    env = cpu_child_env()
    r = subprocess.run([sys.executable, "-c", CODE, mode], env=env,
                       capture_output=True, text=True, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-3000:])
    return json.loads([l for l in r.stdout.splitlines()
                       if l.startswith("JSON:")][0][5:])


def run():
    serial = _run_mode("serial")
    overlap = _run_mode("overlap")
    # the acceptance triplet: less wall-clock, same bytes, same tokens
    assert overlap["scale_wall_s"] < serial["scale_wall_s"], \
        (overlap["scale_wall_s"], serial["scale_wall_s"])
    assert overlap["stats"] == serial["stats"], (overlap["stats"],
                                                 serial["stats"])
    assert overlap["tokens"] == serial["tokens"]

    meas = Table("overlap_measured",
                 ["staging", "scale_wall_s", "stage_wall_s",
                  "in_flight_ticks", "window_tok_s", "stall_s",
                  "overlap_eff"])
    for row in (serial, overlap):
        meas.add(row["mode"], row["scale_wall_s"], row["stage_wall_s"],
                 row["in_flight_ticks"], row["window_tok_s"],
                 row["stall_s"],
                 row["overlap_eff"] if row["overlap_eff"] is not None
                 else float("nan"))

    proj = Table("overlap_projected",
                 ["model", "transition", "serial_s", "overlap_s",
                  "serial_stall_s", "overlap_stall_s"])
    for name in PAPER_MODELS:
        for n_old, n_new in TRANSITIONS:
            _, cs = scale_cost(name, n_old, n_new, "elastic",
                               staging="serial")
            _, co = scale_cost(name, n_old, n_new, "elastic",
                               staging="overlap")
            assert co.scale_time_s <= cs.scale_time_s, (name, n_old, n_new)
            assert co.decode_stall_s < cs.decode_stall_s, (name, n_old,
                                                           n_new)
            proj.add(name, f"{n_old}->{n_new}", cs.scale_time_s,
                     co.scale_time_s, cs.decode_stall_s, co.decode_stall_s)
    return [meas, proj]


def main():
    for t in run():
        t.show()
    print("\noverlapped staging: same bytes, bit-identical tokens, "
          "strictly lower scale-up wall-clock (asserted above)")


if __name__ == "__main__":
    main()
