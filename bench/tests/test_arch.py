"""Architecture files: the softmax-MoE file gives, leaf for leaf and digit
for digit, the weights, reference readings and FLOP counts recorded
before the architecture moved out of the harness
(``testdata/arch_golden.json``); and a configuration that names an
architecture file the harness has never seen is loaded, laid out, served
and checked through that file, with no edit to any existing file."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from conftest import GQA, MIX, MLA
from harness import spec, weights

BENCH = Path(__file__).resolve().parents[1]
GOLDEN = json.loads((BENCH / "testdata" / "arch_golden.json").read_text())
CONFS = {"gqa": GQA, "mla": MLA}


def _batch(conf):
    """The fixed token batch the golden readings were taken on."""
    rng = np.random.default_rng(0)
    toks = rng.integers(0, conf["vocab_size"],
                        GOLDEN["tokens_shape"]).astype(np.int32)
    tgt = np.full_like(toks, -1)
    tgt[:, 3:-1] = toks[:, 4:]
    return toks, tgt


@pytest.mark.parametrize("name", ["gqa", "mla"])
def test_weights_match_the_golden_leaves(name):
    conf = CONFS[name]
    tree = jax.jit(lambda k: spec.arch(conf).params(conf, k))(
        weights.seed_key(GOLDEN["seed"]))
    leaves = {jax.tree_util.keystr(p): hashlib.sha256(
        np.asarray(x).tobytes()).hexdigest()
        for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert leaves == GOLDEN[name]["leaves"]


@pytest.mark.parametrize("precision", ["f32", "fp8"])
@pytest.mark.parametrize("name", ["gqa", "mla"])
def test_reference_matches_the_golden_readings(name, precision):
    conf = CONFS[name]
    arch, key = spec.arch(conf), weights.seed_key(GOLDEN["seed"])
    toks, tgt = _batch(conf)
    x = arch.hidden(conf, key, toks, precision)
    gap, best = arch.head(conf, key, x, tgt, precision)
    want = GOLDEN[name]["reference"][precision]
    assert np.array_equal(np.asarray(gap),
                          np.asarray(want["gap"], np.float32))
    assert np.array_equal(np.asarray(best), np.asarray(want["best"]))


@pytest.mark.parametrize("name", ["gqa", "mla"])
def test_token_flops_match_the_golden_counts(name):
    conf = CONFS[name]
    got = {f"{c},{int(lg)}": spec.arch(conf).token(conf, c, lg)
           for c in (1, 1000, 4096) for lg in (False, True)}
    assert got == GOLDEN[name]["token_flops"]


PROBE = '''"""An architecture the harness has never seen: the softmax-MoE
arithmetic under another name, recording each entry the harness calls."""
from harness import spec

_base = spec.plugin("arch", "softmax_moe")
REACHED = []


def _recorded(name):
    def call(*args, **kw):
        REACHED.append(name)
        return getattr(_base, name)(*args, **kw)
    return call


model_config, params, hidden, head, token = map(
    _recorded, ("model_config", "params", "hidden", "head", "token"))
'''

CHILD = r"""
import json, sys
from pathlib import Path
bench = Path(sys.argv[1])
sys.path[:0] = [str(bench), sys.argv[2]]
from harness import cell as cellmod, serve, spec, weights
from repro.models import model as M
out = {}
cell = spec.load_cell("probe.chat")
out["arch_file"] = cell.arch.__file__
reached = cell.arch.REACHED
weights.check_layout(cell.arch, cell.config, M.init_params,
                     cell.arch.model_config(cell.config))
out["check_layout"] = sorted(set(reached))
del reached[:]
res = cellmod.run(cell, seed=2**31 + 13, seconds=3, trace=False,
                  require_chip=False, log=lambda *a: None)
out["run"] = sorted(set(reached))
out["correct"] = res["correct"]
del reached[:]
spec.plugin("work", "model_step").tick(
    cell.config, serve.Tick(0.0, 0.01, decode_ctx=[10], prefill=[]))
out["model_step"] = sorted(set(reached))
try:
    spec.load_cell("noarch.chat")
    out["noarch"] = None
except SystemExit as e:
    out["noarch"] = str(e)
print(json.dumps(out))
"""


def test_an_unseen_architecture_file_is_reached_by_name(tmp_path):
    """In a copy of the benchmark tree, only new files and appended
    entries: a configuration naming ``arch/probe.py``, its cell and mix;
    and a configuration with no ``"arch"``, which fails naming its file."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "arch" / "probe.py").write_text(PROBE)
    probe = dict(GQA, arch="probe")
    noarch = {k: v for k, v in GQA.items() if k != "arch"}
    for name, conf in (("probe", probe), ("noarch", noarch)):
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(conf))
        (root / "bench" / "cells" / f"{name}.chat.json").write_text(
            json.dumps({"rate_rps": 4.0, "sample_requests": 4,
                        "limits": {"mean_gap": 0.05}}))
    (root / "bench" / "traffic" / "small.json").write_text(json.dumps(MIX))
    bm = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for name in ("probe", "noarch"):
        bm["configs"].append({"name": name, "source": "test",
                              "file": f"bench/configs/{name}.json",
                              "reduced": [], "why": "test"})
        bm["workloads"].append({"name": f"{name}.chat", "config": name,
                                "traffic": "small", "chips": 1,
                                "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    p = subprocess.run([sys.executable, "-c", CHILD, str(root / "bench"),
                        str(BENCH.parent / "src")], capture_output=True,
                       text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert Path(out["arch_file"]) == root / "bench" / "arch" / "probe.py"
    assert out["check_layout"] == ["model_config", "params"]
    assert {"model_config", "params", "hidden", "head"} <= set(out["run"])
    assert out["correct"] is True
    assert out["model_step"] == ["token"]
    assert out["noarch"] is not None
    assert "bench/configs/noarch.json" in out["noarch"]
