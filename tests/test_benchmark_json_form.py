"""BENCHMARK.json against the form the driver refuses a file for before any
run: names, units, lengths, ASCII, the keys an entry may have, file paths
under ``paths``, and what a new cell has to report. PR 32 was refused once for
a ``why`` of 204 characters; this holds every entry, old and new, to the
form."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json"), "rb") as f:
    RAW = f.read()
BENCH = json.loads(RAW)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_the_file_is_small_and_ascii():
    assert len(RAW) <= 64 * 1024
    RAW.decode("ascii")
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert all(_line(w) for w in BENCH["command"])


@pytest.mark.parametrize("config", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_a_configuration_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert _line(config["source"]) and _line(config["why"])
    assert len(config["reduced"]) <= 16
    assert all(NAME.match(k) for k in config["reduced"])
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert re.match(r"^[A-Za-z0-9_.\-/]+$", config["file"])
    with open(os.path.join(ROOT, config["file"])) as f:
        values = json.load(f)
    # every key the entry lists as reduced is explained in the file
    assert set(config["reduced"]) <= set(values.get("reduced",
                                                    config["reduced"]))


@pytest.mark.parametrize("cell", BENCH["workloads"],
                         ids=[w["name"] for w in BENCH["workloads"]])
def test_a_workload_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    assert cell["chips"] in (1, 4) and _line(cell["why"])
    assert os.path.exists(os.path.join(
        ROOT, BENCH["paths"][0], "workloads", cell["name"] + ".json"))
    # it reports setup_s, one more end-to-end metric and a per-layer one
    def reported(rows):
        return [m["name"] for m in rows
                if "workloads" not in m or cell["name"] in m["workloads"]]
    e2e = reported(BENCH["end_to_end"])
    assert "setup_s" in e2e and len(e2e) >= 2
    assert reported(BENCH["per_layer"])


@pytest.mark.parametrize(
    "metric", BENCH["end_to_end"] + BENCH["per_layer"],
    ids=[m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
def test_a_metric_entry(metric):
    per_layer = metric in BENCH["per_layer"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(metric) <= allowed
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", [])) <= cells
    if per_layer:
        assert _line(metric["layer"])
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert os.path.exists(os.path.join(
            ROOT, BENCH["paths"][0], "metrics", metric["name"] + ".py"))
        if "roofline" in metric["name"]:
            assert metric["unit"] == "%" and metric["better"] == "higher"
    else:
        assert metric["source"] in ("host_clock", "device_trace")


def test_names_are_unique_and_a_pair_appears_once():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[key]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    # a full check fits its budget
    runs = 2 + 14 * len(BENCH["workloads"])
    assert runs * (BENCH["run_seconds"] + 60) \
        + 2 * 90 * len(BENCH["workloads"]) + 1200 <= 43200
