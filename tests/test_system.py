"""End-to-end behaviour test: elastic serving under autoscaling policy —
boots small, load spikes, SLO-aware estimator triggers scale-up, service
continues uninterrupted (subprocess, 8 host devices)."""
import pytest

from helpers import TEST_MOE, run_with_devices

pytestmark = pytest.mark.slow


def test_autoscaled_serving_end_to_end():
    out = run_with_devices(TEST_MOE + """
import numpy as np
from repro.core.coordinator import ScalingPolicy
from repro.core.elastic_engine import ElasticServer
from repro.core.topology import ElasticConfig
from repro.serving.metrics import SLO, summarize
from repro.serving.workload import Request

policy = ScalingPolicy(slo=SLO(ttft_s=1.0, tpot_s=1.0), window=8,
                       cooldown_s=0.0, queue_scale_up=3)
srv = ElasticServer(MCFG, tp=2, batch_per_replica=2, max_len=128,
                    prefill_buckets=(32,), policy=policy, seed=0)
c4 = ElasticConfig(dp=2, tp=2, devices=(0,1,2,3))
c6 = ElasticConfig(dp=3, tp=2, devices=(0,1,2,3,4,5))
srv.boot(c4)
srv.preinitialize(c6)

rng = np.random.default_rng(0)
reqs = [Request(i, 0.05*i, 16, 12, prompt=rng.integers(0,128,16))
        for i in range(12)]
t, n, scaled = 0.0, 0, False
pending = list(reqs)
served_during_scale = 0
while any(r.finish_s is None for r in reqs):
    while pending and pending[0].arrival_s <= t:
        srv.submit(pending.pop(0))
    if not scaled and srv.autoscale_decision(t) == "up":
        srv.stage_scale(c6)
        served_during_scale += len(srv.tick(t)); t += 0.05
        srv.switchover()
        scaled = True
        continue
    srv.tick(t); t += 0.05; n += 1
    assert n < 2000
assert scaled, "autoscaler never triggered"
assert srv.engine.num_slots == 6
s = summarize(reqs)
assert s["finished"] == 12
print("E2E-OK", s)
""")
    assert "E2E-OK" in out


def test_launcher_autoscales_on_cpu():
    """The documented CPU check of ``repro.launch.serve``: every request
    finishes, and the queue behind one decode slot scales the instance up
    the ladder live."""
    out = run_with_devices("""
from repro.launch import serve
srv, reqs = serve.main(["--arch", "qwen3-30b-a3b-smoke", "--tp", "2",
                        "--batch-per-replica", "1", "--requests", "4",
                        "--autoscale"])
assert all(r.finish_s is not None for r in reqs)
assert srv.events and srv.hmm.active_cfg.dp > 1, srv.events
print("LAUNCHER-OK", srv.hmm.active_cfg.describe())
""")
    assert "scaling up -> DP2-TP2" in out and "LAUNCHER-OK" in out
