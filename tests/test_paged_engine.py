"""Paged-KV engine tests (real JAX on host devices, subprocess):

* token parity with the dense engine, including shared-prefix CoW runs,
* bit-identical tokens across a scale-up event with paged KV (the block
  tables survive the pool growth verbatim — the zero-copy remap claim),
* preemption under pool pressure: an over-committed burst completes,
* pool conservation at the end of every run (check_invariants).
"""
import pytest

from helpers import TEST_MOE, run_with_devices

PAGED_COMMON = TEST_MOE + """
import numpy as np
from repro.core.topology import ElasticConfig
from repro.core.elastic_engine import ElasticServer
from repro.serving.workload import Request, shared_prefix_workload

c4 = ElasticConfig(dp=2, tp=2, devices=(0,1,2,3))
c6 = ElasticConfig(dp=3, tp=2, devices=(0,1,2,3,4,5))

def drive(srv, reqs, tmax=3000):
    for r in reqs: srv.submit(r)
    t, n = 0.0, 0
    while any(r.finish_s is None for r in reqs):
        srv.tick(t); t += .1; n += 1
        assert n < tmax, [r.finish_s for r in reqs]
    return srv
"""


@pytest.mark.slow
def test_paged_engine_matches_dense_and_shares_blocks():
    """Same workload, dense vs paged: identical tokens per request, and the
    shared-prefix workload actually shares blocks + triggers CoW forks."""
    out = run_with_devices(PAGED_COMMON + """
def build(kv_mode):
    srv = ElasticServer(MCFG, tp=2, batch_per_replica=4, max_len=128,
                        prefill_buckets=(32, 64), seed=0, kv_mode=kv_mode,
                        kv_block_size=16)
    srv.boot(c4)
    return srv

reqs = lambda: shared_prefix_workload(
    [(0.0, 3), (0.5, 5)], prefix_len=40, suffix_range=(0, 6),
    vocab_size=128, seed=2, output_range=(10, 20))

paged = drive(build("paged"), reqs())
dense = drive(build("dense"), reqs())
st = paged.engine.kv_stats()
assert st["shared_block_hits"] > 0, st
assert st["cow_copies"] > 0, st
assert st["used_blocks"] == 0, st
paged.hmm.kv_blocks.check_invariants()
for rid in dense.engine.generated:
    assert dense.engine.generated[rid] == paged.engine.generated[rid], rid

# a request whose prefill token is its ONLY token must still be reported
# finished (it never reaches decode_tick) — regression for the
# finished-at-admission path, both layouts
for srv in (paged, dense):
    r1 = Request(900, 0.0, 16, 1, prompt=np.arange(16) % 128)
    srv.submit(r1)
    srv.tick(99.0)
    assert r1.finish_s == 99.0, r1
    assert len(srv.engine.generated[900]) == 1
paged.hmm.kv_blocks.check_invariants()
print("PAGED-DENSE-PARITY-OK", st["shared_block_hits"], st["cow_copies"])
""", ndev=4)
    assert "PAGED-DENSE-PARITY-OK" in out


@pytest.mark.slow
def test_paged_tokens_identical_across_scaleup():
    """Scale 4->6 devices mid-decode with paged KV: surviving block tables
    are reused verbatim on the grown pool, so every token matches the
    unscaled run bit for bit."""
    out = run_with_devices(PAGED_COMMON + """
def run(scale):
    srv = ElasticServer(MCFG, tp=2, batch_per_replica=2, max_len=128,
                        prefill_buckets=(32,), seed=0, kv_mode="paged",
                        kv_block_size=16)
    srv.boot(c4 if scale else c6)
    rng = np.random.default_rng(0)
    reqs = [Request(i, 0.0, 16, 40, prompt=rng.integers(0, 128, 16))
            for i in range(4)]
    for r in reqs: srv.submit(r)
    t, n, task = 0.0, 0, None
    while any(r.finish_s is None for r in reqs):
        if scale and n == 5 and task is None:
            task = srv.start_scale(c6)
        srv.tick(t); t += .1; n += 1
        if task is not None and not task.done:
            task.advance(t)
        assert n < 500
    return {r.rid: srv.engine.generated[r.rid] for r in reqs}, srv

ref_toks, _ = run(False)
got_toks, srv = run(True)
assert srv.hmm.kv_blocks.num_partitions == 3
srv.hmm.kv_blocks.check_invariants()
assert srv.engine.preemptions == 0
for rid in ref_toks:
    assert ref_toks[rid] == got_toks[rid], (rid, ref_toks[rid], got_toks[rid])
print("PAGED-SCALEUP-DETERMINISM-OK")
""")
    assert "PAGED-SCALEUP-DETERMINISM-OK" in out


@pytest.mark.slow
def test_closed_loop_driver_paged_up_down_shrinks_partitions():
    """The unchanged ClusterDriver loop over a PAGED real engine: burst ->
    scale up (pool grows a partition), idle -> drain + scale down (doomed
    partition verified empty, then dropped), CoW sharing active throughout,
    pool conserves."""
    out = run_with_devices(PAGED_COMMON + """
from repro.core.coordinator import ScalingPolicy
from repro.serving.driver import ClusterDriver, DriverConfig
from repro.serving.metrics import SLO
from repro.serving.workload import scripted_burst

policy = ScalingPolicy(slo=SLO(ttft_s=5.0, tpot_s=5.0), window=8,
                       cooldown_s=1.0, queue_scale_up=3)
srv = ElasticServer(MCFG, tp=2, batch_per_replica=2, max_len=128,
                    prefill_buckets=(32,), seed=0, kv_mode="paged",
                    kv_block_size=16, kv_blocks_per_replica=10)
srv.boot(c4)
srv.preinitialize(c6)
driver = ClusterDriver(srv, policy, mcfg=MCFG, tp=2, device_pool=range(6),
                       config=DriverConfig(dt=0.05, settle_s=2.0,
                                           prewarm_next=False))
reqs = scripted_burst([(0.0, 2), (0.5, 7), (6.0, 1)], vocab_size=128, seed=1,
                      output_range=(30, 50))
reqs += shared_prefix_workload([(0.3, 3)], prefix_len=40, suffix_range=(0, 6),
                               vocab_size=128, seed=4, output_range=(10, 20),
                               rid0=100)
reqs.sort(key=lambda r: r.arrival_s)
until = 0.0
while any(r.finish_s is None for r in reqs) or \
        "down" not in [e.direction for e in driver.events]:
    until += 10.0
    driver.run(reqs if until == 10.0 else [], until=until)
    assert until < 300.0, "stalled"
dirs = [e.direction for e in driver.events]
assert "up" in dirs and "down" in dirs, dirs
assert srv.hmm.active_cfg.ndev == 4
assert srv.hmm.kv_blocks.num_partitions == srv.hmm.active_cfg.dp == 2
assert srv.engine.kv_stats()["shared_block_hits"] > 0
assert srv.engine.kv_stats()["used_blocks"] == 0
srv.hmm.kv_blocks.check_invariants()
print("PAGED-CLOSED-LOOP-OK", dirs)
""")
    assert "PAGED-CLOSED-LOOP-OK" in out


@pytest.mark.slow
def test_paged_engine_preempts_under_pressure_and_completes():
    """Pool sized well below the admitted sequences' eventual footprint: the
    engine preempts (recompute-on-resume) instead of deadlocking, every
    request still finishes, and the pool drains clean."""
    out = run_with_devices(PAGED_COMMON + """
srv = ElasticServer(MCFG, tp=2, batch_per_replica=4, max_len=128,
                    prefill_buckets=(32,), seed=0, kv_mode="paged",
                    kv_block_size=16, kv_blocks_per_replica=8)
srv.boot(c4)
rng = np.random.default_rng(1)
reqs = [Request(i, 0.0, 16, 60, prompt=rng.integers(0, 128, 16))
        for i in range(8)]
drive(srv, reqs)
st = srv.engine.kv_stats()
assert srv.engine.preemptions > 0, st
assert st["used_blocks"] == 0, st
srv.hmm.kv_blocks.check_invariants()
# preempted requests were recomputed and still produced full outputs
for r in reqs:
    assert len(srv.engine.generated[r.rid]) == r.output_len, r.rid
# a request that could NEVER fit a partition fails fast at submit instead
# of head-of-line-blocking the queue forever
try:
    srv.submit(Request(999, 0.0, 16, 1000, prompt=rng.integers(0, 128, 16)))
    raise SystemExit("oversized request was accepted")
except ValueError:
    pass
print("PAGED-PREEMPT-OK", srv.engine.preemptions)
""", ndev=4)
    assert "PAGED-PREEMPT-OK" in out


@pytest.mark.slow
def test_lazy_prefill_bucket_cache_is_bounded():
    """Regression: paged mode lazily compiles a prefill executable per
    unseen padded length, and resumed-after-preemption prompts keep growing,
    so the cache must be LRU-bounded.  AOT-precompiled buckets are pinned;
    a recently-hit lazy bucket outlives an older one; an evicted bucket is
    transparently recompiled when a prompt needs it again."""
    out = run_with_devices(PAGED_COMMON + """
srv = ElasticServer(MCFG, tp=2, batch_per_replica=4, max_len=512,
                    prefill_buckets=(32,), seed=0, kv_mode="paged",
                    kv_block_size=16)
srv.boot(c4)
eng = srv.engine
cap = eng.MAX_LAZY_PREFILL
assert cap == 8
# fill the lazy cache exactly to capacity: buckets 64, 96, ..., 288
for i in range(2, 2 + cap):
    eng._prefill(32 * i)
    assert len(eng._lazy_prefill) <= cap
assert len(eng._lazy_prefill) == cap
before = set(eng.compiled)
eng._prefill(64)                      # cache hit — refreshes 64's recency
assert set(eng.compiled) == before    # a hit never compiles or evicts
eng._prefill(320)                     # one past capacity -> one eviction
assert len(eng._lazy_prefill) == cap
assert "prefill_96" not in eng.compiled     # oldest unrefreshed: evicted
assert "prefill_64" in eng.compiled         # refreshed: survived (LRU)
assert "prefill_32" in eng.compiled         # AOT bucket: never evictable
assert "prefill_32" not in eng._lazy_prefill
# the evicted bucket is recompiled on demand: a 70-token prompt pads to 96
rng = np.random.default_rng(3)
reqs = [Request(0, 0.0, 70, 8, prompt=rng.integers(0, 128, 70))]
drive(srv, reqs)
assert "prefill_96" in eng.compiled
assert len(eng._lazy_prefill) <= cap
assert len(eng.generated[0]) == 8
assert eng.kv_stats()["used_blocks"] == 0
srv.hmm.kv_blocks.check_invariants()
print("LAZY-PREFILL-LRU-OK", sorted(eng._lazy_prefill))
""", ndev=4)
    assert "LAZY-PREFILL-LRU-OK" in out


@pytest.mark.slow
def test_paged_dense_prefix_layers_match_forward_with_and_without_routing():
    """A MoE config whose first layer is dense (``first_k_dense=1``) on two
    replicas, paged KV with chunked prefill: the dense-prefix layer and the
    scanned MoE layers each write and read their own layer of the one
    stacked pool.  Every generated token equals the greedy choice of the
    full-sequence forward pass (teacher-forced on the engine's own tokens),
    and the routing-telemetry decode, run on every tick, changes no token
    and counts one row per MoE layer."""
    out = run_with_devices(PAGED_COMMON + """
import dataclasses
import jax, jax.numpy as jnp
from repro.models import model as M

cfg = dataclasses.replace(MCFG, name="test-moe-dense-prefix", num_layers=3,
                          first_k_dense=1)
c2 = ElasticConfig(dp=2, tp=1, devices=(0, 1))

def serve(routing):
    srv = ElasticServer(cfg, tp=1, batch_per_replica=2, max_len=128,
                        prefill_buckets=(32,), seed=0, kv_mode="paged",
                        kv_block_size=16, prefill_chunk=32,
                        routing_sample_every=routing)
    srv.boot(c2)
    rng = np.random.default_rng(5)
    reqs = [Request(i, 0.1 * i, L, o, prompt=rng.integers(0, 128, L))
            for i, (L, o) in enumerate([(20, 12), (45, 9), (70, 14),
                                        (33, 10)])]
    drive(srv, reqs)
    srv.hmm.kv_blocks.check_invariants()
    return srv, {r.rid: srv.engine.generated[r.rid] for r in reqs}, reqs

srv, toks, reqs = serve(0)
fwd = jax.jit(lambda p, t: M.forward(cfg, p, {"tokens": t}, remat=False)[0])
for r in reqs:
    assert len(toks[r.rid]) == r.output_len, r.rid
    seq = np.concatenate([np.asarray(r.prompt), toks[r.rid][:-1]])
    logits = np.asarray(fwd(srv.hmm.params,
                            jnp.asarray(seq[None], jnp.int32)))[0]
    logits = logits[r.prompt_len - 1:]
    top2 = np.sort(logits, -1)[:, -2:]
    # a tie within float noise may go either way; none is that close here
    assert (top2[:, 1] - top2[:, 0]).min() > 1e-4, r.rid
    assert (logits.argmax(-1) == np.asarray(toks[r.rid])).all(), r.rid

routed, routed_toks, _ = serve(1)
assert routed_toks == toks
stats = routed.engine.routing_stats()
assert stats["samples"] > 0
assert stats["counts"].shape == (cfg.num_layers - cfg.first_k_dense,
                                 cfg.num_experts)
print("PAGED-DENSE-PREFIX-OK", stats["samples"])
""", ndev=2)
    assert "PAGED-DENSE-PREFIX-OK" in out
