"""Test env: force an 8-virtual-device CPU platform BEFORE jax initializes,
so distributed/sharding tests run without TPU hardware (the 'Gloo analog' —
SURVEY.md §4: all distributed tests run on one host)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# exact f32 matmuls for numeric checks (the default 'fastest' uses bf16-class
# accumulation — the TPU-speed setting; tests want reference numerics)
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    # tier-1 runs with -m 'not slow' inside an 870 s budget; anything
    # sleep/loop-heavy (>5 s) must carry this marker
    # (tools/check_slow_markers.py lints for unmarked offenders)
    config.addinivalue_line(
        "markers", "slow: takes >5s; excluded from the tier-1 budget run")
