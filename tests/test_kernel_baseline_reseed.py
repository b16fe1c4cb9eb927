"""Baseline re-seed + stale-evidence refusal (VERDICT r4 next-round #7).

tools/kernel_baseline.py re-seeds artifacts/kernel_baseline.json from
post-selection shipped ratios after the first clean capture, ratchets
keep-best afterwards, and lets the gate FAIL (not skip) on a capture older
than the seed.
"""
from __future__ import annotations

import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "kernel_baseline", os.path.join(REPO, "tools", "kernel_baseline.py"))
kb = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(kb)


def _capture(ts, shipped, errors=()):
    results = {}
    for key, val in shipped.items():
        name, tag = key.rsplit(".", 1)
        results.setdefault(name, {})[tag] = {
            "ratio": val * 1.1, "shipped_ratio": val}
    for key in errors:
        name, tag = key.rsplit(".", 1)
        results.setdefault(name, {}).setdefault(tag, {})[
            "pallas_error"] = "boom"
    return {"metric": "pallas_vs_xla_kernel_ratios", "platform": "tpu",
            "captured_at_unix": ts, "results": results}


def test_reseed_noop_without_clean_shipped_ratios(tmp_path):
    bp = str(tmp_path / "baseline.json")
    assert not kb.reseed(_capture(100.0, {}), bp)
    # a row whose own measurement errored is excluded
    cap = _capture(100.0, {"fa.fwd": 1.2})
    cap["results"]["fa"]["fwd"]["shipped_error"] = "boom"
    assert not kb.reseed(cap, bp)
    assert not os.path.exists(bp)


def test_reseed_filters_errored_cases_not_whole_capture(tmp_path):
    # one case of a pass may fail to compile or fit: the clean cases
    # must still retire the grandfathered raw floor (review finding r5)
    bp = str(tmp_path / "baseline.json")
    with open(bp, "w") as f:
        json.dump({"ratios": {"fa.fwd_bwd": 0.837}}, f)
    cap = _capture(200.0, {"fa.fwd": 1.3, "ce.fwd": 2.0},
                   errors=("rms.fwd",))
    assert kb.reseed(cap, bp)
    with open(bp) as f:
        base = json.load(f)
    assert base["kind"] == "shipped"
    assert base["ratios"] == {"fa.fwd": 1.3, "ce.fwd": 2.0}


def test_first_seed_replaces_raw_baseline(tmp_path):
    bp = str(tmp_path / "baseline.json")
    with open(bp, "w") as f:
        json.dump({"ratios": {"fa.fwd_bwd": 0.837}}, f)  # r3 raw floor
    assert kb.reseed(_capture(200.0, {"fa.fwd": 1.3, "fa.fwd_bwd": 1.05}),
                     bp)
    with open(bp) as f:
        base = json.load(f)
    assert base["kind"] == "shipped"
    assert base["seeded_at_unix"] == 200.0
    # the grandfathered 0.837 raw floor is gone; the shipped floor rules
    assert base["ratios"] == {"fa.fwd": 1.3, "fa.fwd_bwd": 1.05}


def test_later_seed_ratchets_up_and_decays_down(tmp_path):
    bp = str(tmp_path / "baseline.json")
    kb.reseed(_capture(200.0, {"fa.fwd": 1.3, "ce.fwd": 2.0}), bp)
    kb.reseed(_capture(300.0, {"fa.fwd": 1.1, "rms.fwd": 1.02}), bp)
    with open(bp) as f:
        base = json.load(f)
    assert base["seeded_at_unix"] == 300.0
    # lower remeasure decays the floor geometrically (one noisy high
    # measurement must not fail every honest capture after it)...
    assert abs(base["ratios"]["fa.fwd"] - (1.3 * 1.1) ** 0.5) < 5e-3
    assert base["ratios"]["ce.fwd"] == 2.0   # un-rerun case keeps floor
    assert base["ratios"]["rms.fwd"] == 1.02
    # ...and converges toward the honest value across captures
    for ts in (400.0, 500.0, 600.0, 700.0):
        kb.reseed(_capture(ts, {"fa.fwd": 1.1}), bp)
    with open(bp) as f:
        assert json.load(f)["ratios"]["fa.fwd"] < 1.12
    # a higher remeasure ratchets up immediately
    kb.reseed(_capture(800.0, {"fa.fwd": 1.4}), bp)
    with open(bp) as f:
        assert json.load(f)["ratios"]["fa.fwd"] == 1.4


def test_stale_capture_detected_after_seed(tmp_path):
    bp = str(tmp_path / "baseline.json")
    kb.reseed(_capture(1000.0, {"fa.fwd": 1.3}), bp)
    with open(bp) as f:
        base = json.load(f)
    assert kb.is_stale(_capture(500.0, {"fa.fwd": 1.2}), base)
    assert not kb.is_stale(_capture(1000.0, {"fa.fwd": 1.2}), base)
    assert not kb.is_stale(_capture(2000.0, {"fa.fwd": 1.2}), base)
    # raw (pre-seed) baseline never declares staleness
    assert not kb.is_stale(_capture(500.0, {}), {"ratios": {}})
    # once seeded, a capture with NO embedded timestamp is stale: mtime is
    # forgeable by cp/git-checkout, and post-r5 captures always embed one
    no_ts = _capture(None, {"fa.fwd": 1.2})
    del no_ts["captured_at_unix"]
    assert kb.is_stale(no_ts, base)


def test_capture_time_falls_back_to_mtime(tmp_path):
    p = str(tmp_path / "cap.json")
    cap = {"results": {}}
    with open(p, "w") as f:
        json.dump(cap, f)
    os.utime(p, (12345.0, 12345.0))
    assert kb.capture_time(cap, p) == 12345.0
    assert kb.capture_time({"captured_at_unix": 7.0}, p) == 7.0


def test_gate_module_fails_not_skips_on_stale(tmp_path, monkeypatch):
    """End-to-end: point the gate at a seeded baseline + older capture and
    assert it raises Failed, not Skipped."""
    import pytest
    from _pytest.outcomes import Failed
    spec = importlib.util.spec_from_file_location(
        "test_kernel_gate_mod",
        os.path.join(REPO, "tests", "test_kernel_gate.py"))
    gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate)
    cap_p = tmp_path / "bench_kernels.json"
    base_p = tmp_path / "baseline.json"
    with open(cap_p, "w") as f:
        json.dump(_capture(500.0, {"fa.fwd": 1.2}), f)
    kb.reseed(_capture(1000.0, {"fa.fwd": 1.3}), str(base_p))
    monkeypatch.setattr(gate, "CAPTURE", str(cap_p))
    monkeypatch.setattr(gate, "BASELINE", str(base_p))
    with pytest.raises(Failed, match="stale"):
        gate._load_capture()
    # a fresh capture with shipped ratios loads fine
    with open(cap_p, "w") as f:
        json.dump(_capture(2000.0, {"fa.fwd": 1.31}), f)
    assert gate._load_capture()["captured_at_unix"] == 2000.0
