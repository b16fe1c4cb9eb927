"""Every examples/ script must run end-to-end (the switching-user
contract: each major workflow has a runnable recipe)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MESH_EXAMPLES = ["fleet_hybrid.py", "pipeline_1f1b.py",
                 "auto_parallel_engine.py", "degree_planner.py",
                 "long_context_ring.py", "moe_capacity.py"]
PLAIN_EXAMPLES = ["train_gpt2.py", "inference_predictor.py",
                  "parameter_server.py"]


def _run(name, extra_env):
    env = dict(os.environ)
    env.update(extra_env)
    env["JAX_PLATFORMS"] = "cpu"
    # ROOT only: the examples must import THIS checkout, not whatever
    # the ambient PYTHONPATH holds
    env["PYTHONPATH"] = ROOT
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", name)],
        capture_output=True, text=True, timeout=420, env=env, cwd=ROOT)
    assert r.returncode == 0, f"{name} failed:\n{r.stderr[-2000:]}"
    return r.stdout


@pytest.mark.parametrize("name", PLAIN_EXAMPLES)
def test_plain_example(name):
    out = _run(name, {})
    assert "loss" in out or "matches" in out


@pytest.mark.parametrize("name", MESH_EXAMPLES)
def test_mesh_example(name):
    out = _run(
        name, {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert "loss" in out
