"""tools/check_bench_ratios.py — per-kernel bench-ratio ratchet gate.

Runs entirely over synthetic report/bests artifacts in tmp_path; no
accelerator, no real bench run. Fast (tier-2) coverage for: clean-row
extraction, error-row and unmeasured-key skipping, the tolerance floor,
--update ratcheting (up only), and CLI exit codes.
"""
import json

import pytest

from tools.check_bench_ratios import (check, load_best, main,
                                      report_ratios, save_best)


def _report(results):
    return {"extra": {"kernels_vs_xla": {"results": results}}}


def _write(path, doc):
    path.write_text(json.dumps(doc))
    return str(path)


GOOD = {
    "fa": {"fwd": {"pallas_ms": 1.0, "xla_ms": 1.5, "ratio": 1.5},
           "fwd_bwd": {"pallas_ms": 2.0, "xla_ms": 2.4, "ratio": 1.2}},
    "ce": {"fwd": {"pallas_ms": 1.0, "xla_ms": 2.0, "ratio": 2.0}},
}


class TestExtraction:
    def test_clean_rows_extracted(self):
        assert report_ratios(_report(GOOD)) == {
            "fa.fwd": 1.5, "fa.fwd_bwd": 1.2, "ce.fwd": 2.0}

    def test_error_rows_skipped(self):
        results = dict(GOOD)
        results["drop"] = {
            "fwd": {"pallas_error": "boom", "xla_ms": 3.0},
            "fwd_bwd": {"pallas_ms": 1.0, "xla_ms": 1.1, "ratio": 1.1,
                        "xla_error": "also boom"}}
        got = report_ratios(_report(results))
        assert "drop.fwd" not in got and "drop.fwd_bwd" not in got
        assert got["fa.fwd"] == 1.5

    def test_missing_ratio_and_shape_tolerated(self):
        got = report_ratios(_report({
            "a": {"fwd": {"pallas_ms": 1.0}},    # no ratio computed
            "b": "not-a-dict",
            "c": {"fwd": 3.0}}))
        assert got == {}
        assert report_ratios({}) == {}


class TestCheck:
    def test_drop_beyond_tolerance_is_regression(self):
        best = {"fa.fwd": 2.0}
        regs, _, _ = check({"fa.fwd": 1.6}, best, tolerance=0.15)
        assert [r[0] for r in regs] == ["fa.fwd"]
        # floor = 2.0 * 0.85 = 1.7
        assert regs[0][3] == pytest.approx(1.7)

    def test_drop_within_tolerance_passes(self):
        regs, _, _ = check({"fa.fwd": 1.75}, {"fa.fwd": 2.0}, 0.15)
        assert regs == []

    def test_improvement_and_new_key_classified(self):
        regs, imps, new = check({"fa.fwd": 2.5, "rms.fwd": 1.0},
                                {"fa.fwd": 2.0}, 0.15)
        assert regs == [] and new == ["rms.fwd"]
        assert imps == [("fa.fwd", 2.5, 2.0)]

    def test_unmeasured_best_key_skipped(self):
        regs, imps, new = check({}, {"fa.fwd": 2.0}, 0.15)
        assert (regs, imps, new) == ([], [], [])


class TestCli:
    def test_green_run_exits_zero(self, tmp_path, capsys):
        rep = _write(tmp_path / "r.json", _report(GOOD))
        best = tmp_path / "best.json"
        save_best(str(best), {"fa.fwd": 1.5, "fa.fwd_bwd": 1.2,
                              "ce.fwd": 2.0})
        assert main([rep, "--best", str(best)]) == 0
        assert "OK" in capsys.readouterr().out

    def test_regression_exits_one(self, tmp_path, capsys):
        rep = _write(tmp_path / "r.json", _report(GOOD))
        best = tmp_path / "best.json"
        save_best(str(best), {"fa.fwd": 5.0})
        assert main([rep, "--best", str(best)]) == 1
        assert "REGRESSION fa.fwd" in capsys.readouterr().out

    def test_update_ratchets_up_only(self, tmp_path):
        rep = _write(tmp_path / "r.json", _report(GOOD))
        best = tmp_path / "best.json"
        # ce.fwd best above measured (2.5 > 2.0, within 15%? floor
        # 2.125 > 2.0 would regress — use tolerance 0.3 to stay green)
        save_best(str(best), {"fa.fwd": 1.0, "ce.fwd": 2.5})
        assert main([rep, "--best", str(best), "--tolerance", "0.3",
                     "--update"]) == 0
        got = load_best(str(best))
        assert got["fa.fwd"] == 1.5       # ratcheted up
        assert got["ce.fwd"] == 2.5       # never decays
        assert got["fa.fwd_bwd"] == 1.2   # first-seen recorded

    def test_update_on_regression_still_fails(self, tmp_path):
        rep = _write(tmp_path / "r.json", _report(GOOD))
        best = tmp_path / "best.json"
        save_best(str(best), {"fa.fwd": 5.0})
        assert main([rep, "--best", str(best), "--update"]) == 1
        assert load_best(str(best))["fa.fwd"] == 5.0  # best kept

    def test_missing_best_file_is_all_new(self, tmp_path, capsys):
        rep = _write(tmp_path / "r.json", _report(GOOD))
        assert main([rep, "--best", str(tmp_path / "nope.json")]) == 0
        assert "3 new" in capsys.readouterr().out

    def test_unreadable_report_exits_two(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert main([str(bad)]) == 2
        assert main([str(tmp_path / "absent.json")]) == 2

    def test_empty_report_exits_two(self, tmp_path):
        rep = _write(tmp_path / "r.json", _report({}))
        assert main([rep]) == 2
