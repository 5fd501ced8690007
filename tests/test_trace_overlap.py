"""Acceptance tests for the tracing layer over the real concurrency surface
(DESIGN.md §9): a closed-loop ``ClusterDriver`` run over an overlapped
``ElasticServer`` exports a Chrome-trace JSON in which a per-``TransferOp``
span demonstrably overlaps a ``srv.step`` span — the visual proof of
STAGING ∥ serving — and ``tools/trace_report.py`` summarizes it.  The
simulator emits the same schema in sim-time.
"""
import json
import sys
from pathlib import Path

import pytest

from helpers import TEST_MOE, run_with_devices

REPO = Path(__file__).resolve().parent.parent


@pytest.mark.slow
def test_driver_closed_loop_trace_transfer_overlaps_decode(tmp_path):
    """The ISSUE's acceptance criterion: closed-loop driver, real engine,
    staging="overlap", exported trace shows a transfer-op span intersecting
    a decode-tick span; trace_report prints the overlap count; routing
    histograms ride along in the same trace."""
    trace_path = tmp_path / "trace.json"
    out = run_with_devices(TEST_MOE + f"""
import sys, time
from repro import obs
from repro.core.coordinator import ScalingPolicy
from repro.core.elastic_engine import ElasticServer
from repro.core.topology import ElasticConfig
from repro.serving.driver import ClusterDriver, DriverConfig
from repro.serving.metrics import SLO, summarize
from repro.serving.workload import scripted_burst

tr = obs.install(obs.Tracer(capacity=200_000))

policy = ScalingPolicy(slo=SLO(ttft_s=1.0, tpot_s=1.0), window=8,
                       cooldown_s=1.0, queue_scale_up=3)
srv = ElasticServer(MCFG, tp=2, batch_per_replica=2, max_len=128,
                    prefill_buckets=(32,), seed=0, staging="overlap",
                    transfer_workers=1, routing_sample_every=4)
srv.boot(ElasticConfig(dp=2, tp=2, devices=(0,1,2,3)))

# throttle each transfer op so the staging window deterministically spans
# several driver ticks (same trick as test_overlap_staging.py)
orig = srv.hmm._stage_unit
def slow_unit(*a, **k):
    time.sleep(0.05)
    return orig(*a, **k)
srv.hmm._stage_unit = slow_unit

driver = ClusterDriver(srv, policy, mcfg=MCFG, tp=2, device_pool=range(6),
                       config=DriverConfig(dt=0.05, settle_s=2.0,
                                           prewarm_next=False))
reqs = scripted_burst([(0.0, 2), (0.5, 7), (6.0, 1)], vocab_size=128, seed=1)
until = 0.0
while any(r.finish_s is None for r in reqs):
    until += 10.0
    driver.run(reqs if until == 10.0 else [], until=until)
    assert until < 400.0, "stalled"
assert any(e.direction == "up" for e in driver.events)

doc = obs.write_chrome_trace({str(trace_path)!r}, tr,
                             extra_metadata={{"run": "acceptance"}})
obs.validate_trace(doc)

cats = {{r.get("cat") for r in doc["traceEvents"] if r["ph"] != "M"}}
for want in ("scale", "hmm", "transfer", "serve", "req", "routing"):
    assert want in cats, (want, cats)

# the acceptance predicate: >= 1 transfer-op span intersects a decode tick
sys.path.insert(0, {str(REPO / "tools")!r})
import trace_report
n_transfer, n_overlap, n_ticks = trace_report.overlap_report(doc)
assert n_transfer >= 1 and n_ticks >= 1, (n_transfer, n_ticks)
assert n_overlap >= 1, "no TransferOp span overlapped a srv.step span"

# routing histograms were sampled during the run and reach summarize()
rt = srv.routing_stats()
assert rt is not None and rt["samples"] >= 1
assert rt["counts"].shape == (MCFG.num_layers, MCFG.num_experts)
summ = summarize(driver.finished, backend=srv)
assert summ["routing_samples"] == rt["samples"]

# driver events carry the routing telemetry columns
done = [e for e in driver.events if e.routing_samples is not None]
assert done, [e.routing_samples for e in driver.events]

# the CLI consumes the exported file end to end
assert trace_report.main([{str(trace_path)!r}]) == 0
print("TRACE-OVERLAP-OK", n_transfer, n_overlap, n_ticks)
""")
    assert "TRACE-OVERLAP-OK" in out
    # the artifact written by the subprocess is a loadable Chrome trace
    doc = json.loads(trace_path.read_text())
    assert doc["traceEvents"] and doc["metadata"] == {"run": "acceptance"}


def test_sim_backend_emits_same_schema_in_sim_time():
    """The simulator emits the same event schema with explicit sim-time
    stamps: a scale.STAGING span on the sim-scale lane covering
    [t_command, t_ready], decode ticks at the modelled step duration, and
    per-request lifecycle instants — no wall-clock values leak in."""
    from repro import obs
    from repro.configs import get_config
    from repro.core.topology import ElasticConfig
    from repro.serving.simulator import ServingSimulator
    from repro.serving.workload import Request

    tr = obs.install(obs.Tracer())
    try:
        mcfg = get_config("deepseek-v2-lite-16b")
        sim = ServingSimulator(mcfg, tp=2, ndev=4, strategy="elastic")
        reqs = [Request(i, 0.0, 512, 20) for i in range(4)]
        for r in reqs:
            sim.submit(r)
        task = sim.start_scale(ElasticConfig(4, 2, tuple(range(8))))
        t, horizon = 0.0, 600.0
        while t < horizon and (any(r.finish_s is None for r in reqs)
                               or not task.done):
            sim.step(t)
            if not task.done:
                task.advance(t)
            t += 0.05
        assert all(r.finish_s is not None for r in reqs)
        assert task.done

        evs = tr.events()
        staging = [e for e in evs if e.name == "scale.STAGING"]
        assert len(staging) == 1 and staging[0].tid == "sim-scale"
        assert staging[0].t0 == task.event.t_command
        assert staging[0].t1 == task.event.t_ready
        commits = [e for e in evs if e.name == "scale.commit"]
        assert len(commits) == 1 and commits[0].ph == "i"

        ticks = [e for e in evs if e.name == "srv.step"]
        assert ticks and all(e.tid == "sim" for e in ticks)
        # sim clock domain: every timestamp sits inside the sim horizon,
        # nowhere near time.perf_counter()'s wall-clock origin
        assert all(0.0 <= e.t0 <= horizon and e.t1 <= 2 * horizon
                   for e in evs if e.ph == "X")
        # span duration is the modelled decode step, not quantum dt
        b, nd = ticks[0].args["batch"], ticks[0].args["ndev"]
        assert ticks[0].dur == pytest.approx(sim.perf.decode_step_s(b, nd))

        admits = {e.args["rid"] for e in evs if e.name == "req.admit"}
        firsts = {e.args["rid"] for e in evs if e.name == "req.first_token"}
        finishes = {e.args["rid"] for e in evs if e.name == "req.finish"}
        assert admits == firsts == finishes == {0, 1, 2, 3}

        # the same exporter consumes a sim-time trace unchanged
        doc = obs.chrome_trace(tr)
        obs.validate_trace(doc)
        spans = [r for r in doc["traceEvents"] if r["ph"] == "X"]
        assert min(r["ts"] for r in spans) == 0.0     # normalized
    finally:
        obs.install(None)


def test_null_tracer_keeps_sim_and_scheduler_silent():
    """With no tracer installed the instrumented paths stay no-ops — the
    guard every hot loop relies on for the <=2%% overhead budget."""
    from repro import obs
    from repro.serving.scheduler import PrefillJob, TokenBudgetScheduler

    assert obs.get_tracer() is obs.NULL_TRACER
    sched = TokenBudgetScheduler(chunk=8)
    plans = sched.plan([PrefillJob(slot=0, rid=0, pos=0, total=16)])
    assert [p.take for p in plans] == [8]
    assert obs.NULL_TRACER.events() == []
