"""A whole run on the CPU at a small size, the look for a chip skipped.

The last line keeps the contract's schema and prints no device metric; a
run whose timed path alters tokens where they are produced comes out not
correct; and the control (the reference in float8, ``bench/control.py``)
comes out not correct where the served bfloat16 tokens pass, at this size
with this size's limit (the cells' limits are set on the chip, PERF.md).
A scale cell's run (boot on two of four host devices, a live scale to
four at the window's start) is driven in a child process with four
virtual devices.
"""
import json
import os
import time

import pytest

from conftest import small_cell
from harness import cell as cellmod

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def _run(conf, trace=False, **kw):
    return cellmod.run(small_cell(conf, **kw), seed=2**31 + 11, seconds=3,
                       trace=trace, require_chip=False,
                       log=lambda *a: None)


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_last_line_schema(conf, trace, capsys):
    res = _run(conf, trace)
    cellmod.emit(res)
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[:5] == KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 12
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] == 1
    assert "busy_s" not in dev and "breakdown" not in line
    names = set(line["metrics"])
    cell = small_cell(conf)
    if trace:
        # on the CPU no device metric is read, and mfu needs a chip's peak
        assert names == set()
    else:
        assert names == set(cell.end_to_end)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert out.err.strip().splitlines()[-1].startswith("check ")
    assert line["checks"]["mean_gap"][1] == 0.05


def test_a_traced_run_serves_the_sample_before_reading_its_trace(
        conf, monkeypatch):
    """Stopping the profiler and reading its trace take no time from the
    minute the sample has to finish in: the drain's deadline is set after
    the profiler has stopped, and the trace is read after the drain."""
    import jax

    from harness import serve
    from harness import trace as tracemod
    seen = {}
    stop, drain, load = (jax.profiler.stop_trace, serve.Driver.drain,
                         tracemod.load)

    def timed_stop():
        stop()
        seen["stopped"] = time.perf_counter()

    def timed_drain(self, rids, until):
        seen["until"] = until
        drain(self, rids, until)
        seen["drained"] = time.perf_counter()

    def timed_load(path):
        seen["read"] = time.perf_counter()
        return load(path)

    monkeypatch.setattr(jax.profiler, "stop_trace", timed_stop)
    monkeypatch.setattr(serve.Driver, "drain", timed_drain)
    monkeypatch.setattr(tracemod, "load", timed_load)
    res = _run(conf, trace=True)
    assert res["correct"] is True
    assert seen["until"] - seen["stopped"] >= cellmod.DRAIN_S
    assert seen["read"] >= seen["drained"]


def test_altered_tokens_are_not_correct(monkeypatch):
    """Tokens altered where the decode step produces them: every 5th tick,
    each token it served moves to the next id."""
    import repro.core.elastic_engine  # noqa: F401  (imports engine in order)
    from repro.serving.engine import InferenceEngine
    orig = InferenceEngine.decode_tick
    calls = {"n": 0}

    def broken(self):
        out = orig(self)
        calls["n"] += 1
        if calls["n"] % 5 == 0:
            for i, (rid, tok, fin) in enumerate(out):
                bad = (tok + 1) % self.mcfg.vocab_size
                self.generated[rid][-1] = bad
                out[i] = (rid, bad, fin)
        return out

    monkeypatch.setattr(InferenceEngine, "decode_tick", broken)
    from conftest import GQA
    res = _run(GQA)
    assert res["correct"] is False
    assert res["checks"]["mean_gap"][0] > 0.05


@pytest.mark.parametrize("arch", ["gqa", "mla"])
def test_the_control_is_not_correct(arch):
    """At 256 wide (seed 4) the served bfloat16 tokens pass and the
    float8 reference in their place fails the same limit."""
    from conftest import GQA, MLA
    conf = dict({"gqa": GQA, "mla": MLA}[arch], hidden_size=256)
    read = {}
    for control in (None, "fp8"):
        res = cellmod.run(small_cell(conf), seed=4, seconds=4, trace=False,
                          control=control, require_chip=False,
                          log=lambda *a: None)
        read[control] = (res["correct"], res["checks"]["mean_gap"][0])
    assert read[None][0] is True and read["fp8"][0] is False
    assert read["fp8"][1] >= 3 * read[None][1]


def test_without_a_chip_the_command_prints_no_result():
    import subprocess
    import sys
    from pathlib import Path
    run = Path(__file__).resolve().parents[1] / "run.py"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(run), "--workload",
                        "qwen3_30b_a3b.chat", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], capture_output=True, text=True,
                       env=env, timeout=300)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
    assert "no TPU" in p.stderr


SCALE_CHILD = r"""
import json, sys
sys.path[:0] = sys.argv[1:3]
from conftest import GQA, MIX
from harness import cell as cellmod, spec
names = ("output_tok_s", "itl_p95_s", "setup_s", "scale_up_s",
         "scale_stall_s", "staging_gbps")
units = {"output_tok_s": "tokens/s", "itl_p95_s": "s", "setup_s": "s",
         "scale_up_s": "s", "scale_stall_s": "s", "staging_gbps": "GB/s"}
cell = spec.Cell(name="small.scale", chips=4, config=GQA,
                 arch=spec.arch(GQA), traffic=MIX,
                 params={"rate_rps": 6.0, "sample_requests": 4,
                         "limits": {"mean_gap": 0.05},
                         "scale": {"from_chips": 2, "to_chips": 4}},
                 per_layer=(), end_to_end=names, units=units)
res = cellmod.run(cell, seed=2**31 + 5, seconds=4, trace=False,
                  require_chip=False, log=lambda *a: print(*a, file=sys.stderr))
print(json.dumps(res))
"""


def test_a_scale_cell_scales_live_and_checks():
    """Boot on two of four virtual devices, scale to four at the window's
    start while serving: the event commits, its readers read, and what was
    served across the commit passes the check."""
    import subprocess
    import sys
    from pathlib import Path
    tests = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCALE_CHILD, str(tests),
                        str(tests.parent)], capture_output=True, text=True,
                       env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["scale_not_committed"] == [0, 0]
    assert res["device"]["count"] == 4
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert {"scale_up_s", "scale_stall_s", "staging_gbps"} <= set(m)
    assert 0 < m["scale_stall_s"] <= m["scale_up_s"]
    assert m["staging_gbps"] > 0
