"""The reduction from a trace to busy time, kernel time and named idle
gaps: on hand-made events, and on a short recorded trace of the
``qwen3_30b_a3b.chat`` cell on one TPU v5e (``testdata/``)."""
import json
from pathlib import Path

import pytest

from harness import trace as tm

DATA = Path(__file__).resolve().parents[1] / "testdata"


def _tr(ops, host, modules=()):
    return {"devices": {"/device:TPU:0": {"ops": [list(o) for o in ops],
                                          "modules": [list(m)
                                                      for m in modules]}},
            "host": [list(h) for h in host]}


def test_busy_is_the_union_clipped_to_the_window():
    tr = _tr([("a", 0.0, 2.0), ("b", 1.0, 2.0), ("c", 5.0, 1.0),
              ("d", 9.5, 2.0)],
             [("bench.window", 0.5, 10.0)])
    w = tm.window(tr)
    assert w == (0.5, 10.5)
    # [0.5, 3] + [5, 6] + [9.5, 10.5]
    assert tm.busy_seconds(tr, w) == pytest.approx(2.5 + 1.0 + 1.0)
    assert tm.gaps(tr, w) == [(3.0, 5.0), (6.0, 9.5)]


def test_self_time_leaves_nested_ops_to_themselves():
    ops = [("while.3", 0.0, 10.0), ("paged_gmm.1", 1.0, 2.0),
           ("fusion.7", 4.0, 1.0), ("paged_gmm.1", 6.0, 3.0)]
    st = {(n, s): d for n, s, d in tm.self_times(ops)}
    assert st[("while.3", 0.0)] == pytest.approx(4.0)
    assert st[("paged_gmm.1", 6.0)] == pytest.approx(3.0)
    tr = _tr(ops, [("bench.window", 0.0, 10.0)])
    secs, n = tm.kernel_seconds(tr, "paged_gmm", (0.0, 10.0))
    assert (secs, n) == (pytest.approx(5.0), 2)
    # a longer name that starts alike is another kernel
    tr2 = _tr(ops + [("paged_gmm_int8.1", 9.5, 0.2)],
              [("bench.window", 0.0, 10.0)])
    assert tm.kernel_seconds(tr2, "paged_gmm", (0.0, 10.0))[1] == 2
    top = dict(tm.top_ops(tr, (0.0, 10.0)))
    assert top["paged_gmm"] == pytest.approx(5.0)
    assert top["while"] == pytest.approx(4.0)


def test_gaps_are_named_by_the_innermost_annotation():
    tr = _tr([("a", 0.0, 1.0), ("b", 2.0, 1.0), ("c", 5.0, 1.0)],
             [("bench.window", 0.0, 6.0), ("srv.tick", 0.0, 3.5),
              ("bench.collect", 3.5, 0.2), ("bench.idle", 3.7, 1.3)])
    gaps = tm.idle_gaps(tr, (0.0, 6.0))
    assert gaps[0] == ["bench.idle", pytest.approx(2.0)]
    assert gaps[1] == ["srv.tick", pytest.approx(1.0)]


def test_module_runs_and_host_spans_in_the_window():
    tr = _tr([], [("bench.window", 0.0, 10.0), ("srv.tick", 0.5, 2.0),
                  ("srv.tick", 9.5, 1.0)],
             modules=[("jit__unknown(2)", 2.0, 0.02),
                      ("jit__unknown(1)", 1.0, 0.01),
                      ("jit__unknown(1)", 11.0, 0.01)])
    assert [m[1] for m in tm.modules_in(tr, (0.0, 10.0))] == [1.0, 2.0]
    assert tm.host_spans(tr, "srv.tick", (0.0, 10.0)) == [(0.5, 2.5)]


def test_a_recorded_v5e_trace_of_two_ticks():
    """Two ticks of ``qwen3_30b_a3b.chat`` on one TPU v5e, under overload
    (a 256-token chunk and a 64-slot decode step in each): 6 layers, three
    ``paged_gmm`` calls (up, gate, down) and one ``paged_attention`` call
    per layer and executable."""
    tr = json.loads((DATA / "qwen3_chat_v5e_two_ticks.json").read_text())
    w = tm.window(tr)
    assert w[1] - w[0] == pytest.approx(0.2465, abs=1e-4)
    gmm, n_gmm = tm.kernel_seconds(tr, "paged_gmm", w)
    attn, n_attn = tm.kernel_seconds(tr, "paged_attention", w)
    assert (n_gmm, n_attn) == (2 * 2 * 6 * 3, 2 * 2 * 6)
    busy = tm.busy_seconds(tr, w)
    assert gmm + attn < busy < w[1] - w[0]
    # the decode step is the last executable run in each tick
    ticks = tm.host_spans(tr, "srv.tick", w)
    assert len(ticks) == 2
    last = [tm.modules_in(tr, t)[-1] for t in ticks]
    assert [round(m[2] * 1e3, 1) for m in last] == [45.5, 45.6]
    # the op name is the HLO instruction's, and every idle gap falls in a
    # harness annotation
    assert all(" " not in n for n, _, _ in
               tr["devices"]["/device:TPU:0"]["ops"])
    assert {n for n, _ in tm.idle_gaps(tr, w)} <= {
        "srv.tick", "bench.collect", "bench.submit"}
    assert sum(g for _, g in tm.idle_gaps(tr, w, n=10**6)) == pytest.approx(
        (w[1] - w[0]) - busy)


def test_decode_step_read_from_the_recorded_trace():
    """The decode executable is the module whose run is last among the
    step-sized runs of most ``srv.tick`` spans (prefill first, then
    decode, in each tick); every run of it in the window counts."""
    from harness.cell import RunView
    from harness.spec import plugin
    tr = json.loads((DATA / "qwen3_chat_v5e_two_ticks.json").read_text())
    view = RunView(trace=tr, trace_window=tm.window(tr))
    read = plugin("metrics", "decode_step_ms").read
    assert read(view) == pytest.approx((45.486 + 45.559) / 2, abs=0.01)
    # no tick spans: nothing to read
    view = RunView(trace=dict(tr, host=[h for h in tr["host"]
                                        if h[0] != "srv.tick"]),
                   trace_window=tm.window(tr))
    assert read(view) is None
