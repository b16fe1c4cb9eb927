"""Runtime flag registry.

Capability parity with the reference's exported-flag system
(reference: paddle/phi/core/flags.cc PHI_DEFINE_EXPORTED_* macros and
python/paddle/base/framework.py set_flags/get_flags). Flags initialize from
FLAGS_* environment variables and are mutable at runtime.
"""
from __future__ import annotations

import os
from typing import Any, Dict

_REGISTRY: Dict[str, Any] = {}


def _env_cast(raw: str, default):
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


def define_flag(name: str, default, help_str: str = "") -> None:
    env = os.environ.get("FLAGS_" + name)
    value = _env_cast(env, default) if env is not None else default
    _REGISTRY[name] = value


def set_flags(flags: Dict[str, Any]) -> None:
    for k, v in flags.items():
        k = k[6:] if k.startswith("FLAGS_") else k
        if k not in _REGISTRY:
            raise KeyError(f"flag {k!r} is not defined")
        _REGISTRY[k] = v


def get_flags(flags) -> Dict[str, Any]:
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for k in flags:
        kk = k[6:] if k.startswith("FLAGS_") else k
        if kk not in _REGISTRY:
            raise KeyError(f"flag {kk!r} is not defined")
        out[k] = _REGISTRY[kk]
    return out


def get_flag(name: str):
    return _REGISTRY[name]


# Core flags (parity with the reference's most commonly used FLAGS_*).
define_flag("check_nan_inf", False, "check every op output for NaN/Inf")
define_flag("check_index_bounds", False,
            "eager range-check of gather/embedding indices (host sync)")
define_flag("use_pallas_kernels", True, "prefer Pallas fused kernels over XLA lowering")
define_flag("use_spmd_rules", True,
            "apply explicit per-op SPMD rules (sharding constraints + "
            "dist_attr propagation) where registered")
define_flag("eager_vjp", False,
            "linearize ops at forward time instead of deferring jax.vjp "
            "to backward (slow; debugging aid)")
define_flag("spmd_strict", False,
            "raise instead of falling back to GSPMD when a registered "
            "SPMD rule rejects a call or a sharding constraint fails "
            "(fallbacks are always counted in dispatch.spmd_rule_stats)")
define_flag("planner_strict", False,
            "raise instead of falling back to pure data-parallel when "
            "every planner candidate is pruned (fallbacks are always "
            "counted in planner.planner_stats)")
define_flag("use_fused_optimizer", True,
            "eager optimizer.step as one jitted multi-tensor XLA program")
define_flag("pallas_prefer_ce", False,
            "prefer the pallas fused softmax-CE over XLA's on TPU")
define_flag("pallas_ce_bwd", "auto",
            "backward impl for the pallas softmax-CE kernel: auto "
            "(= xla: softmax-minus-onehot from the saved lse, fusable by "
            "XLA — the measured fwd+bwd winner), xla, or pallas")
define_flag("pallas_prefer_norms", False,
            "ship the pallas rms/layer-norm kernels on TPU even under "
            "differentiation (default ships XLA there: its fused fwd+bwd "
            "measured faster on v5e; fwd-dominant inference can opt in)")
define_flag("pallas_force_interpret", False,
            "run Pallas kernels in interpret mode on non-TPU backends "
            "(kernel tests); default falls back to the XLA impl off-TPU")
define_flag("embedding_deterministic", False, "deterministic embedding grad accumulation")
define_flag("dataloader_start_method", "forkserver",
            "multiprocessing start method for DataLoader workers; fork is "
            "unsafe once the JAX runtime threads exist")
define_flag("cudnn_deterministic", False, "accepted for API parity; no-op on TPU")
define_flag("low_precision_op_list", 0, "collect amp op stats level")
