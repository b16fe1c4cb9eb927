"""What ``BENCHMARK.json`` names, found as files by name.

A cell, a configuration, a runner, a model family, a plain reference and a
per-layer metric are each a file of their own under one of the benchmark's
``paths``; the harness finds them by the name in ``BENCHMARK.json`` (or, for a
runner and a family, by the name the cell's or the configuration's file gives):

    <path>/workloads/<cell>.json      the cell's parameters (data)
    <file of the configuration>       the sizes as run (data)
    <path>/runners/<runner>.py        drives one kind of cell
    <path>/families/<family>.py       builds the program's model of a family
    <path>/reference/<family>.py      the family's plain reference
    <path>/metrics/<metric>.py        reads one per-layer metric
    <path>/peaks.json                 published peaks by device_kind

A later PR adds files and entries and edits none that are there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys


@dataclasses.dataclass
class Config:
    name: str
    source: str
    file: str
    reduced: list
    values: dict


@dataclasses.dataclass
class Cell:
    name: str
    config: Config
    traffic: str
    chips: int
    why: str
    params: dict


@dataclasses.dataclass
class Benchmark:
    root: str
    paths: list
    run_seconds: int
    configs: dict
    cells: dict
    end_to_end: list
    per_layer: list

    def find(self, *parts) -> str:
        for p in self.paths:
            cand = os.path.join(self.root, p, *parts)
            if os.path.exists(cand):
                return cand
        raise FileNotFoundError(
            f"{os.path.join(*parts)} is under none of {self.paths}")

    def cell(self, name: str) -> Cell:
        try:
            return self.cells[name]
        except KeyError:
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json "
                             f"has {sorted(self.cells)}") from None

    def module(self, kind: str, name: str):
        """The Python file ``<path>/<kind>/<name>.py`` as a module."""
        path = self.find(kind, name + ".py")
        modname = "_bench_%s_%s" % (kind, "".join(
            c if c.isalnum() else "_" for c in name))
        if modname in sys.modules and \
                getattr(sys.modules[modname], "__file__", None) == path:
            return sys.modules[modname]
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
        return mod

    def peaks(self, device_kind: str) -> dict:
        with open(self.find("peaks.json")) as f:
            table = json.load(f)["device_kinds"]
        if device_kind not in table:
            raise SystemExit(
                f"no published peaks for device_kind {device_kind!r}; "
                f"peaks.json has {sorted(table)} (add a row with its source)")
        return table[device_kind]

    def metrics_for(self, cell_name: str, which: str) -> list:
        rows = self.end_to_end if which == "end_to_end" else self.per_layer
        return [m for m in rows
                if "workloads" not in m or cell_name in m["workloads"]]


def load_benchmark(path: str, root: str | None = None) -> Benchmark:
    with open(path) as f:
        raw = json.load(f)
    root = root or os.path.dirname(os.path.abspath(path))
    bench = Benchmark(root=root, paths=list(raw["paths"]),
                      run_seconds=raw["run_seconds"], configs={}, cells={},
                      end_to_end=raw["end_to_end"],
                      per_layer=raw["per_layer"])
    for c in raw["configs"]:
        with open(os.path.join(root, c["file"])) as f:
            values = json.load(f)
        bench.configs[c["name"]] = Config(
            name=c["name"], source=c["source"], file=c["file"],
            reduced=list(c["reduced"]), values=values)
    for w in raw["workloads"]:
        with open(bench.find("workloads", w["name"] + ".json")) as f:
            params = json.load(f)
        bench.cells[w["name"]] = Cell(
            name=w["name"], config=bench.configs[w["config"]],
            traffic=w["traffic"], chips=w["chips"], why=w["why"],
            params=params)
    return bench
