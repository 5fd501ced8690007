"""Self-test of the dry-run machinery (subprocess: it needs 512 placeholder
devices, which must never leak into the main test session)."""
import json
import os
import subprocess
import sys

import pytest

from helpers import REPO

pytestmark = pytest.mark.slow


def test_dryrun_single_combination(tmp_path):
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json
from repro.launch.dryrun import run_one
rec = run_one("qwen1.5-0.5b", "decode_32k", False, save=False)
assert rec["status"] == "ok", rec
rl = rec["roofline"]
assert rl["chips"] == 256
assert rl["flops"] > 0 and rl["hbm_bytes"] > 0
assert rl["bottleneck"] in ("compute", "memory", "collective")
assert 0 < rl["useful_flops_ratio"] < 2
rec2 = run_one("hubert-xlarge", "decode_32k", False, save=False)
assert rec2["status"] == "skipped"
rec3 = run_one("qwen1.5-0.5b", "decode_32k", True, save=False)
assert rec3["status"] == "ok" and rec3["roofline"]["chips"] == 512
print("DRYRUN-OK")
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DRYRUN-OK" in r.stdout


def test_main_session_has_one_device():
    """The 512-device flag must not leak (per the brief)."""
    import jax
    assert len(jax.devices()) == 1
