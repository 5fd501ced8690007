#!/usr/bin/env python3
"""Record ``arch_golden.json`` from the harness as it was before the
architecture files: the one built-in family of ``harness/weights.py``,
``harness/reference.py`` and ``work/model_step.py``.

  c=$(git log --format=%H --diff-filter=A -- bench/arch/softmax_moe.py | tail -1)
  git archive "$c^" | tar -x -C <dir>
  python3 bench/testdata/record_arch_golden.py <dir> bench/testdata/arch_golden.json

``<dir>`` is a checkout of the commit before the move (its ``bench/`` has
no ``arch/``).  On the CPU, at the GQA and MLA fixtures of that
checkout's ``bench/tests/conftest.py`` and seed ``2**31 + 5``, it writes
the sha256 of every weight leaf, the reference's ``(gap, best)`` in float32
and float8 on a fixed token batch, and the FLOPs of one token at three
contexts.  ``bench/tests/test_arch.py`` holds the moved code to them.
"""
import hashlib
import json
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
OLD = Path(sys.argv[1]).resolve() / "bench"
sys.path[:0] = [str(OLD), str(OLD / "tests"), str(OLD.parent / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from conftest import GQA, MLA  # noqa: E402
from harness import reference, weights  # noqa: E402
from harness.cell import plugin  # noqa: E402

step = plugin("work", "model_step")
SEED = 2**31 + 5
CTX = (1, 1000, 4096)


def batch(conf):
    rng = np.random.default_rng(0)
    toks = rng.integers(0, conf["vocab_size"], (2, 16)).astype(np.int32)
    tgt = np.full_like(toks, -1)
    tgt[:, 3:-1] = toks[:, 4:]
    return toks, tgt


def record(conf):
    key = weights.seed_key(SEED)
    tree = jax.jit(lambda k: weights.params(conf, k))(key)
    leaves = {jax.tree_util.keystr(p): hashlib.sha256(
        np.asarray(x).tobytes()).hexdigest()
        for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    toks, tgt = batch(conf)
    ref = {}
    for prec in ("f32", "fp8"):
        gap, best = reference.gaps(conf, key, toks, tgt, prec)
        ref[prec] = {"gap": np.asarray(gap).tolist(),
                     "best": np.asarray(best).tolist()}
    flops = {f"{c},{int(lg)}": step.token(conf, c, lg)
             for c in CTX for lg in (False, True)}
    return {"leaves": leaves, "reference": ref, "token_flops": flops}


out = {"recorded": "by bench/testdata/record_arch_golden.py from a checkout "
                   "of the harness before the architecture moved into "
                   "bench/arch/",
       "seed": SEED, "tokens_shape": [2, 16],
       "gqa": record(GQA), "mla": record(MLA)}
Path(sys.argv[2]).write_text(json.dumps(out, indent=1) + "\n")
print("written", sys.argv[2])
