"""Seed the measured-defaults table from the on-chip autotune cache.

VERDICT r4 #6 (cold-cache cliff): jitted train steps consult the autotune
cache but cannot measure, so a session without an eager pre-tune of the
exact shapes fell back to hand heuristics. This tool folds every measured
exact-shape winner in ``artifacts/autotune_tpu.json`` into shape-CLASS
entries (power-of-two seq/row buckets — the same classifier the call
sites in ops/pallas/{flash_attention,cross_entropy,norms}.py compute) and
writes ``artifacts/measured_defaults.json``; ``use_artifacts_cache``
loads it, and a traced cold-cache call takes the class winner before the
heuristic. Run after bench_kernels.py has tuned on the chip (both files
are run-time products, git-ignored).

Reference discipline: paddle/phi/kernels/autotune/ caches with serialized
defaults so later processes skip measurement.
"""
from __future__ import annotations

import ast
import json
import os
import re
import sys
from collections import Counter, defaultdict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# the ONE class-key format, shared with the consult path — a private
# f-string here would silently desynchronize from the call sites
from paddle_tpu.core.autotune import (  # noqa: E402
    ce_class_key, flash_class_key, norm_class_key)


def _parse_arrays(parts):
    """['(8, 1024, 16, 128):bfloat16', ...] -> [(shape tuple, dtype)]."""
    out = []
    for p in parts:
        m = re.match(r"^(\(.*?\)):(\w+)$", p)
        if not m:
            return None
        out.append((ast.literal_eval(m.group(1)), m.group(2)))
    return out


def classify(key: str):
    """Exact cache key -> shape-class key (None when unclassifiable)."""
    if key.endswith("__meta"):
        return None
    parts = key.split("|")
    tag, arrays = parts[0], _parse_arrays(parts[1:])
    if not arrays:
        return None
    if tag.startswith("flash_attention_blocks_v2"):
        if len(arrays) < 2 or len(arrays[0][0]) != 4:
            return None
        (qs, qd), (ks, _) = arrays[0], arrays[1]
        _, sq, hq, d = qs
        sk, hk = ks[1], ks[2]
        return flash_class_key(tag, sq, sk, hq != hk, d, qd)
    if tag == "softmax_xent_dir":
        shape, dt = arrays[0]
        if len(shape) < 2:
            return None
        return ce_class_key(shape[0], shape[-1], dt)
    if tag in ("rms_norm_dir", "layer_norm_dir"):
        shape, dt = arrays[0]
        if not shape:
            return None
        rows = 1
        for s in shape[:-1]:
            rows *= s
        return norm_class_key(tag, rows, shape[-1], dt)
    return None


def build_defaults(cache: dict) -> dict:
    """{exact key: winner} -> {class key: majority winner}."""
    votes = defaultdict(Counter)
    for key, winner in sorted(cache.items()):
        ck = classify(key)
        if ck is not None and isinstance(winner, str):
            votes[ck][winner] += 1
    return {ck: c.most_common(1)[0][0] for ck, c in votes.items()}


def main() -> int:
    cache_p = os.path.join(REPO, "artifacts", "autotune_tpu.json")
    out_p = os.path.join(REPO, "artifacts", "measured_defaults.json")
    if not os.path.exists(cache_p):
        print(f"no autotune cache at {cache_p}; nothing to seed")
        return 0
    with open(cache_p) as f:
        cache = json.load(f)
    defaults = build_defaults(cache)
    payload = {
        "_note": "shape-class measured winners derived from "
                 "artifacts/autotune_tpu.json by tools/seed_defaults.py; "
                 "consulted by traced cold-cache calls "
                 "(core/autotune.py class_default)",
        "defaults": defaults,
    }
    with open(out_p, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    print(f"seeded {len(defaults)} class defaults -> {out_p}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
