"""Test helpers: multi-device subprocess runner.

The main pytest session keeps the default 1 CPU device (per the brief);
elastic/distributed tests spawn a subprocess with
``--xla_force_host_platform_device_count=N``.
"""
from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, ndev: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    # CPU experiments: on a machine with a TPU the child never takes the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={r.returncode})\n--- stdout:\n"
            f"{r.stdout}\n--- stderr:\n{r.stderr}")
    return r.stdout


# Shared tiny MoE model used by the elastic integration tests: 24 experts so
# EP degrees 4, 6 and 8 all divide evenly.
TEST_MOE = """
from repro.configs.base import ModelConfig
MCFG = ModelConfig(name="test-moe", arch_type="moe", num_layers=2, d_model=64,
                   vocab_size=128, num_heads=4, num_kv_heads=4, head_dim=16,
                   d_ff=128, num_experts=24, top_k=2, moe_d_ff=32,
                   dtype="float32", capacity_factor=100.0)
"""
