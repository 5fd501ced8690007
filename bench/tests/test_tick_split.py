"""The readers of the program's own spans inside a tick: the chunk-prefill
executable's device time, and the device-idle time inside the tick's
preparation and read-back spans, on hand-made events and on a short
recorded trace of ``qwen3_30b_a3b.chat`` on one TPU v5e (``testdata/``)."""
import json
from pathlib import Path

import pytest

from harness import tick_split
from harness import trace as tm
from harness.cell import RunView
from harness.spec import plugin

DATA = Path(__file__).resolve().parents[1] / "testdata"
SPANS = DATA / "qwen3_chat_v5e_spans.json"


def _view(ops, host, modules=()):
    tr = {"devices": {"/device:TPU:0": {"ops": [list(o) for o in ops],
                                        "modules": [list(m)
                                                    for m in modules]}},
          "host": [list(h) for h in host]}
    return RunView(trace=tr, trace_window=tm.window(tr))


def _read(name, view):
    return plugin("metrics", name).read(view)


def test_busy_cover_by_bisection():
    cover = tick_split.busy_cover([(1.0, 2.0), (3.0, 5.0), (6.0, 7.0)])
    assert cover(0.0, 10.0) == pytest.approx(4.0)
    assert cover(1.5, 3.5) == pytest.approx(1.0)
    assert cover(3.5, 4.0) == pytest.approx(0.5)
    assert cover(5.0, 6.0) == 0.0
    assert cover(8.0, 9.0) == 0.0


# two ticks; the device is busy [1.5, 3.0] and [6.0, 8.5]
OPS = [("fusion.1", 1.5, 1.5), ("fusion.2", 6.0, 2.5)]
HOST = [("bench.window", 0.0, 10.0),
        ("srv.tick", 1.0, 4.0), ("srv.tick", 5.0, 4.0),
        # tick 1: admission idle 0.5, decode prep half busy, read and
        # commit after the step
        ("srv.admit", 1.0, 0.5), ("srv.decode.prep", 2.5, 1.0),
        ("srv.decode.read", 3.5, 1.0), ("srv.decode.commit", 4.5, 0.5),
        # tick 2: a chunk's prep, nested spans counted once, its
        # register while the device runs, then the decode read tail
        ("srv.prefill.prep", 5.0, 1.0), ("srv.prefill.prep", 5.2, 0.3),
        ("srv.prefill.register", 6.5, 1.0), ("srv.decode.read", 8.0, 1.0),
        # outside any tick: not counted
        ("srv.admit", 9.5, 0.2)]


def test_prep_and_readback_are_device_idle_per_tick():
    view = _view(OPS, HOST)
    # prep: 0.5 (admit) + 0.5 (decode prep) + 1.0 (chunk prep) over 2 ticks
    assert _read("tick_prep_ms", view) == pytest.approx(1e3 * 2.0 / 2)
    # read-back: 1.0 + 0.5 (tick 1) + 0 + 0.5 (tick 2) over 2 ticks
    assert _read("tick_readback_ms", view) == pytest.approx(1e3 * 2.0 / 2)
    # both are parts of the host's time in the tick
    host = _read("tick_host_ms", view)
    assert host == pytest.approx(1e3 * (2.5 + 1.5) / 2)
    assert (_read("tick_prep_ms", view) + _read("tick_readback_ms", view)
            <= host)


def test_a_program_without_the_spans_reads_nothing():
    view = _view(OPS, [h for h in HOST if not h[0].startswith("srv.")
                       or h[0] == "srv.tick"],
                 modules=[("jit__unknown(1)", 6.0, 2.5)])
    for name in ("tick_prep_ms", "tick_readback_ms", "prefill_chunk_ms"):
        assert _read(name, view) is None
    assert _read("tick_prep_ms", RunView(trace=None,
                                         trace_window=None)) is None


def test_chunk_prefill_is_found_by_its_name():
    mods = [("jit_chunk_prefill(7)", 5.5, 0.070),
            ("jit_decode_step(9)", 6.0, 0.045),
            ("jit_chunk_prefill(7)", 7.0, 0.066),
            ("jit_chunk_prefill(7)", 10.5, 0.070)]       # after the window
    view = _view(OPS, HOST, modules=mods)
    assert _read("prefill_chunk_ms", view) == pytest.approx(68.0)


def test_a_recorded_v5e_trace_with_the_program_spans():
    """Three ticks of ``qwen3_30b_a3b.chat`` on one TPU v5e, each with a
    256-token chunk (the third a prompt's final one) and a decode step,
    recorded with ``bench/record_trace.py``: the chunk executable is found
    by name, runs once per ``srv.prefill.chunk`` span, and the split of
    the host's time fits inside ``tick_host_ms``."""
    tr = json.loads(SPANS.read_text())
    w = tm.window(tr)
    view = RunView(trace=tr, trace_window=w)
    chunks = tm.host_spans(tr, "srv.prefill.chunk", w)
    runs = [m for m in tm.modules_in(tr, w)
            if m[0].startswith("jit_chunk_prefill(")]
    assert len(chunks) == len(runs) == 3
    assert len(tm.host_spans(tr, "srv.prefill.read", w)) == 1
    assert not any("_unknown" in m[0] for m in tm.modules_in(tr, w))
    assert _read("prefill_chunk_ms", view) == pytest.approx(
        (71.926 + 73.608 + 75.273) / 3, abs=0.01)
    assert _read("decode_step_ms", view) == pytest.approx(43.30, abs=0.01)
    prep = _read("tick_prep_ms", view)
    back = _read("tick_readback_ms", view)
    host = _read("tick_host_ms", view)
    assert prep == pytest.approx(0.316, abs=0.001)
    assert back == pytest.approx(1.990, abs=0.001)
    assert host == pytest.approx(5.894, abs=0.001)
    assert prep + back < host
    # every idle gap is named by a program span below the harness's tick
    names = {n for n, _ in tm.idle_gaps(tr, w, n=10**6)}
    assert names and all(n.startswith("srv.") and n != "srv.tick"
                         for n in names)
