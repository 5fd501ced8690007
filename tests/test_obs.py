"""Unit tests for the tracing/telemetry layer (repro.obs, DESIGN.md §9):
ring-buffer bounds, thread lanes, the disabled fast path, the injectable
clock, Chrome-trace export schema, tools/trace_report.py, and the profiler
sink: a tick's ``srv.*`` span tree in a JAX profiler trace of a small
chunked, paged server, and the step executables' names."""
from __future__ import annotations

import json
import sys
import threading
from pathlib import Path

import pytest

from repro import obs
from repro.obs import tracer as tracer_mod

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _no_global_tracer():
    """Tests install their own tracer; always restore the null default."""
    yield
    obs.install(None)


# ---------------------------------------------------------------- recording

def test_span_complete_instant_counter():
    t = iter(range(100))
    tr = obs.Tracer(clock=lambda: float(next(t)))
    with tr.span("work", cat="serve", args={"k": 1}):
        pass
    tr.complete("staged", 10.0, 12.5, cat="transfer")
    tr.instant("admit", cat="req", args={"rid": 7})
    tr.counter("depth", 3.0, cat="serve")
    evs = tr.events()
    assert [e.ph for e in evs] == ["X", "X", "i", "C"]
    span = evs[0]
    assert span.name == "work" and span.cat == "serve"
    assert (span.t0, span.t1) == (0.0, 1.0) and span.dur == 1.0
    assert evs[1].dur == 2.5
    assert evs[3].args == {"value": 3.0}


def test_ring_buffer_bounded():
    tr = obs.Tracer(capacity=16)
    for i in range(100):
        tr.instant(f"e{i}")
    evs = tr.events()
    assert len(evs) == 16
    assert evs[0].name == "e84" and evs[-1].name == "e99"  # oldest evicted
    tr.clear()
    assert tr.events() == []


def test_explicit_time_span_out_of_order_ok():
    tr = obs.Tracer()
    tr.complete("later", 5.0, 6.0)
    tr.complete("earlier", 1.0, 2.0)   # explicit timestamps need no ordering
    assert [e.t0 for e in tr.events()] == [5.0, 1.0]


def test_thread_lanes_and_names():
    tr = obs.Tracer()
    tr.instant("main-ev")

    def worker():
        tr.complete("op", 0.0, 1.0, cat="transfer")

    th = threading.Thread(target=worker, name="hmm-transfer-test")
    th.start()
    th.join()
    main_ev, op = tr.events()
    assert main_ev.tid == threading.get_ident()
    assert op.tid != main_ev.tid
    assert tr.thread_names()[op.tid] == "hmm-transfer-test"


def test_string_lane_passthrough():
    tr = obs.Tracer()
    tr.complete("scale.STAGING", 0.0, 1.0, cat="scale", tid="scale")
    assert tr.events()[0].tid == "scale"


# ------------------------------------------------------------ null fast path

def test_null_tracer_is_default_and_noop():
    assert obs.get_tracer() is obs.NULL_TRACER
    nt = obs.NULL_TRACER
    assert nt.enabled is False
    nt.complete("x", 0, 1)
    nt.instant("x")
    nt.counter("x", 1.0)
    with nt.span("x"):
        pass
    assert nt.events() == [] and nt.thread_names() == {}
    assert nt.now() > 0  # still a usable clock for unconditional call sites


def test_install_and_reset():
    tr = obs.Tracer()
    assert obs.install(tr) is tr
    assert obs.get_tracer() is tr
    assert obs.install(None) is obs.NULL_TRACER
    assert obs.get_tracer() is obs.NULL_TRACER


def test_traced_decorator_short_circuits_when_disabled(monkeypatch):
    calls = []

    @obs.traced("unit.fn", cat="test")
    def fn(x):
        calls.append(x)
        return x * 2

    # disabled: no span machinery, result passes through
    assert fn(3) == 6
    tr = obs.Tracer()
    obs.install(tr)
    assert fn(4) == 8
    assert calls == [3, 4]
    evs = tr.events()
    assert len(evs) == 1 and evs[0].name == "unit.fn" and evs[0].cat == "test"

    # sabotage the real span path: the disabled branch must never touch it
    obs.install(None)
    monkeypatch.setattr(obs.Tracer, "span",
                        lambda *a, **k: pytest.fail("span on disabled path"))
    assert fn(5) == 10


# ------------------------------------------------------------------- export

def _sample_tracer():
    tr = obs.Tracer(clock=lambda: 0.0)
    tr.complete("scale.STAGING", 100.0, 101.0, cat="scale", tid="scale")
    tr.complete("srv.step", 100.2, 100.3, cat="serve")
    tr.instant("req.admit", cat="req", t=100.1, args={"rid": 1})
    tr.counter("routing.top_expert_share", 0.25, cat="routing", t=100.4)
    return tr


def test_chrome_trace_schema_and_normalization():
    tr = _sample_tracer()
    doc = obs.chrome_trace(tr, extra_metadata={"run": "unit"})
    obs.validate_trace(doc)
    assert doc["displayTimeUnit"] == "ms"
    assert doc["metadata"] == {"run": "unit"}
    evs = [r for r in doc["traceEvents"] if r["ph"] != "M"]
    # ts normalized to µs relative to the earliest event
    assert min(r["ts"] for r in evs) == 0.0
    span = next(r for r in evs if r["name"] == "scale.STAGING")
    assert span["ph"] == "X" and span["dur"] == pytest.approx(1e6)
    assert span["tid"] < 0  # synthetic string lane
    names = [r for r in doc["traceEvents"]
             if r["ph"] == "M" and r["name"] == "thread_name"]
    assert any(r["args"]["name"] == "scale" and r["tid"] == span["tid"]
               for r in names)
    inst = next(r for r in evs if r["name"] == "req.admit")
    assert inst["s"] == "t" and inst["args"] == {"rid": 1}
    ctr = next(r for r in evs if r["ph"] == "C")
    assert ctr["args"] == {"value": 0.25}


def test_write_and_load_roundtrip(tmp_path):
    tr = _sample_tracer()
    path = tmp_path / "trace.json"
    written = obs.write_chrome_trace(str(path), tr)
    loaded = obs.load_trace(str(path))
    assert loaded == json.loads(json.dumps(written))


def test_validate_trace_rejects_malformed():
    with pytest.raises(AssertionError):
        obs.validate_trace({"events": []})
    with pytest.raises(AssertionError):
        obs.validate_trace({"traceEvents": [{"ph": "X", "pid": 1, "tid": 0,
                                             "ts": 0, "name": "x"}]})  # no dur


def test_sim_clock_domain():
    sim_t = [0.0]
    tr = obs.Tracer(clock=lambda: sim_t[0])
    with tr.span("tick"):
        sim_t[0] = 2.5
    ev = tr.events()[0]
    assert (ev.t0, ev.t1) == (0.0, 2.5)


# ------------------------------------------------------------- trace_report

def _report_mod():
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    return trace_report


def test_trace_report_summary_and_overlap(tmp_path, capsys):
    rep = _report_mod()
    tr = obs.Tracer()
    tr.complete("w0", 0.0, 1.0, cat="transfer", tid="a")
    tr.complete("w0", 2.0, 3.0, cat="transfer", tid="a")
    tr.complete("srv.step", 0.5, 0.6, cat="serve")          # overlaps w0 #1
    tr.complete("scale.STAGING", 0.0, 3.0, cat="scale", tid="scale")
    doc = obs.chrome_trace(tr)

    rows = rep.summary_rows(doc)
    by_name = {r[1]: r for r in rows}
    assert by_name["w0"][2] == 2                      # count
    assert by_name["w0"][3] == pytest.approx(2000.0)  # total_ms
    assert rows[0][1] == "scale.STAGING"              # sorted by total desc
    only = rep.summary_rows(doc, cat="transfer")
    assert {r[1] for r in only} == {"w0"}

    n_tr, n_ov, n_ticks = rep.overlap_report(doc)
    assert (n_tr, n_ov, n_ticks) == (2, 1, 1)

    path = tmp_path / "t.json"
    path.write_text(json.dumps(doc))
    assert rep.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "span summary" in out and "phase timeline" in out
    assert "transfer spans overlapping a decode tick: 1" in out


# ------------------------------------------------------- the profiler sink

def _host_events(trace_dir):
    """``[(name, start_ns, end_ns, stats)]`` of every host-plane event in
    the profiler trace written under ``trace_dir``."""
    import jax
    path = next(Path(trace_dir).rglob("*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _profiled(trace_dir, body):
    import jax
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("test.outer"):
            body()
    finally:
        jax.profiler.stop_trace()
    return _host_events(trace_dir)


def test_live_spans_reach_the_profiler_and_after_the_fact_spans_do_not(
        tmp_path):
    """``span`` and ``@traced`` annotate their body for the profiler, with
    or without a ring-buffer tracer, args and late metadata as stats;
    ``complete`` (explicit, possibly sim-clock, timestamps) never does."""
    @obs.traced("unit.traced", cat="test")
    def fn():
        return 1

    tr = obs.Tracer(clock=lambda: 5.0)

    def body():
        with obs.NULL_TRACER.span("unit.null", args={"rows": 3}) as sp:
            sp.set_metadata(late=1)
        fn()
        obs.install(tr)
        with tr.span("unit.ring", args={"take": 256}) as sp:
            sp.set_metadata(late=2)
        fn()
        tr.complete("unit.after", 1.0, 2.0)
        obs.install(None)

    evs = _profiled(tmp_path, body)
    by = {}
    for n, _, _, stats in evs:
        by.setdefault(n, []).append(stats)
    assert by["unit.null"] == [{"rows": 3, "late": 1}]
    assert by["unit.ring"] == [{"take": 256, "late": 2}]
    assert len(by["unit.traced"]) == 2
    assert "unit.after" not in by
    ring = {e.name: e.args for e in tr.events()}
    assert ring == {"unit.ring": {"take": 256, "late": 2},
                    "unit.traced": None, "unit.after": None}


def test_scale_phases_and_transfer_ops_annotate_live(tmp_path):
    """A phase annotation opens at the phase's entry and closes at its
    exit (none for a terminal phase); a transfer op's annotation wraps its
    work on the worker thread, inside the phase it staged in."""
    import time

    from repro.core.elastic_engine import EngineScalingTask
    from repro.core.topology import ElasticConfig
    from repro.core.transfer import TransferEngine, TransferOp
    from repro.serving.driver import ScalePhase
    task = EngineScalingTask.__new__(EngineScalingTask)
    task.target = ElasticConfig(dp=1, tp=1, devices=(0,))
    eng = TransferEngine(max_workers=1)

    def body():
        task.phase = ScalePhase.STAGING
        eng.submit([TransferOp(7, "w0", lambda: time.sleep(0.01))]).join(10)
        task.phase = ScalePhase.COMMITTING
        task.phase = ScalePhase.DONE

    try:
        evs = _profiled(tmp_path, body)
    finally:
        eng.shutdown()
    by = {n: (s, e, st) for n, s, e, st in evs}
    staging, commit, op = (by["scale.STAGING"], by["scale.COMMITTING"],
                           by["w0"])
    assert staging[0] <= op[0] and op[1] <= staging[1] <= commit[0]
    assert op[1] - op[0] >= 10e6 and op[2] == {"index": 7}
    assert not any(n.startswith(("scale.DONE", "scale.ABORTED"))
                   for n in by)


TINY_MOE = dict(name="test-moe", arch_type="moe", num_layers=2, d_model=64,
                vocab_size=128, num_heads=4, num_kv_heads=4, head_dim=16,
                d_ff=128, num_experts=8, top_k=2, moe_d_ff=32,
                dtype="float32", capacity_factor=100.0)
CHUNK = 16


@pytest.fixture(scope="module")
def chunked_server():
    """One CPU device: paged KV, 16-token prefill chunks, the routed decode
    twin every second step."""
    from repro.configs.base import ModelConfig
    from repro.core.elastic_engine import ElasticServer
    from repro.core.topology import ElasticConfig
    srv = ElasticServer(ModelConfig(**TINY_MOE), tp=1, batch_per_replica=2,
                        max_len=64, prefill_buckets=(32,), seed=0,
                        kv_mode="paged", kv_block_size=16,
                        prefill_chunk=CHUNK, routing_sample_every=2)
    srv.boot(ElasticConfig(dp=1, tp=1, devices=(0,)))
    return srv


def _serve(srv, rid0):
    """Two requests (prompts of 3 and 1 chunks) served to completion."""
    import numpy as np

    from repro.serving.workload import Request
    rng = np.random.default_rng(rid0)
    reqs = [Request(rid0 + i, 0.0, L, 4, prompt=rng.integers(0, 128, L))
            for i, L in enumerate((40, 12))]
    for r in reqs:
        srv.submit(r)
    for n in range(100):
        srv.tick(float(n))
        if all(r.finish_s is not None for r in reqs):
            return reqs
    raise AssertionError("requests did not finish")


def _holds(parent, evs, name):
    return any(n == name and parent[1] <= s and e <= parent[2]
               for n, s, e, _ in evs)


def test_tick_span_tree_in_the_profiler_trace(chunked_server, tmp_path):
    """Each tick's ``srv.*`` tree lands on the host plane inside the
    caller's annotation: every chunk holds its prep and dispatch, a final
    chunk its read, every decode step its prep, dispatch, read and
    commit; counts ride along as stats."""
    reqs = []
    evs = _profiled(tmp_path,
                    lambda: reqs.extend(_serve(chunked_server, 100)))
    outer = next(e for e in evs if e[0] == "test.outer")
    srv_evs = [e for e in evs if e[0].startswith("srv.")]
    assert all(outer[1] <= s and e <= outer[2] for _, s, e, _ in srv_evs)
    names = {e[0] for e in srv_evs}
    assert {"srv.admit", "srv.step", "srv.prefill", "srv.prefill.chunk",
            "srv.decode"} <= names
    assert "srv.tick" not in names and "srv.rebalance" not in names
    chunks = [e for e in srv_evs if e[0] == "srv.prefill.chunk"]
    assert len(chunks) == 3 + 1
    prompt = {r.rid: r.prompt_len for r in reqs}
    finals = 0
    for c in chunks:
        st = c[3]
        assert set(st) == {"rid", "start", "take"}
        for child in ("srv.prefill.prep", "srv.prefill.dispatch",
                      "srv.prefill.register"):
            assert _holds(c, srv_evs, child), (c, child)
        if st["start"] + st["take"] == prompt[st["rid"]]:
            finals += 1
            assert _holds(c, srv_evs, "srv.prefill.read")
    assert finals == 2
    decodes = [e for e in srv_evs if e[0] == "srv.decode"]
    assert decodes and all(1 <= d[3]["rows"] <= 2 for d in decodes)
    for d in decodes:
        for child in ("prep", "dispatch", "read", "commit"):
            assert _holds(d, srv_evs, f"srv.decode.{child}"), (d, child)
    admits = [e for e in srv_evs if e[0] == "srv.admit"]
    assert sum(a[3]["admitted"] for a in admits) == 2
    steps = [e for e in srv_evs if e[0] == "srv.step"]
    assert len(steps) == len(admits)
    assert all(any(_holds(s, srv_evs, n) for s in steps)
               for n in ("srv.prefill", "srv.decode"))


def test_tick_spans_reach_the_ring_buffer_only_when_installed(
        chunked_server):
    tr = obs.install(obs.Tracer())
    _serve(chunked_server, 200)
    obs.install(None)
    evs = tr.events()
    spans = {e.name for e in evs if e.ph == "X"}
    assert {"srv.admit", "srv.step", "srv.prefill", "srv.prefill.chunk",
            "srv.prefill.prep", "srv.prefill.dispatch",
            "srv.prefill.register", "srv.prefill.read", "srv.decode",
            "srv.decode.prep", "srv.decode.dispatch", "srv.decode.read",
            "srv.decode.commit"} <= spans
    chunk = next(e for e in evs if e.name == "srv.prefill.chunk")
    assert chunk.args == {"rid": 200, "start": 0, "take": CHUNK}
    assert all(e.args["rows"] >= 1 for e in evs if e.name == "srv.decode")
    assert sum(e.args["admitted"] for e in evs
               if e.name == "srv.admit") == 2
    n = len(tr.events())
    _serve(chunked_server, 300)          # NULL_TRACER: nothing buffered
    assert len(tr.events()) == n and obs.NULL_TRACER.events() == []


@pytest.mark.parametrize("key,module", [
    ("decode", "jit_decode_step"),
    ("decode_routed", "jit_decode_step_routed"),
    (f"chunk_prefill_{CHUNK}", "jit_chunk_prefill"),
    ("prefill_48", "jit_prefill_48"),
])
def test_step_executables_carry_stable_names(chunked_server, key, module):
    """The module name a profiler trace shows for each step executable
    (a ``functools.partial`` would jit as ``jit__unknown``); ``prefill_48``
    is compiled lazily, as a resumed prompt's bucket is."""
    eng = chunked_server.engine
    exe = eng._prefill(48) if key == "prefill_48" else eng.compiled[key]
    assert exe.as_text().startswith(f"HloModule {module},")
