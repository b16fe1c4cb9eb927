"""The trainer loop's records against a profile of the same steps —
``python3 tools/loop_clock_probe.py [--cell C] [--seed N] [--steps K]``
from the root of a checkout, in the process that holds the chip.

``run_steps`` stamps each whole iteration with ``time.time_ns()``; a
profile's events count ``start_ns`` from ``profile_start_time``, on the same
clock (``tools/trace_gaps.py::origin_ns``). This builds a training cell's
program as the benchmark's runner does, drives ``K`` steps with the runner's
profile start (4th fetched step) and stop (14th) in ``on_log``, keeps every
record the loop makes (``PipelineMetrics.add_iteration`` is wrapped for the
length of the run), and prints one JSON object: for each record inside the
profile, ``host_off_us`` (``origin + start_ns`` of its ``train::dispatch``
span less its dispatch stamp), ``module_start_us`` and ``prev_module_end_us``
(the device's step program that ran it, and the one before, against the same
stamp: the previous one ending before the stamp means the device ran dry),
and the steps found dry by the trace beside those the records call starved.
On the CPU (``--bench benchmarks/tests/preset/BENCHMARK.json --cell
gpt2-tiny-train``) there is no device plane: the host offsets alone.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

STEP_PROGRAM = r"jit_train_step"


def _planes(data):
    """``{step: dispatch span start_ns}`` and ``[(start_ns, duration_ns)]``
    of the step program on device 0."""
    spans, modules = {}, []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "train::dispatch":
                        spans[dict(e.stats)["step"]] = float(e.start_ns)
        elif plane.name.startswith("/device:") and plane.name.endswith(":0"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(float(e.start_ns), float(e.duration_ns))
                                for e in line.events
                                if re.search(STEP_PROGRAM, e.name)]
    return spans, sorted(modules)


def rows(records, origin, spans, modules):
    out = []
    for r in records:
        t = r["t_ns"]["dispatch"]
        row = {"step": r["step"], "starved": r["starved"],
               "loop_ms": round(r["loop_ms"], 3),
               "ms": {k: round(v, 3) for k, v in r["ms"].items()}}
        if r["step"] in spans:
            row["host_off_us"] = (origin + spans[r["step"]] - t) / 1e3
            after = [m for m in modules if origin + m[0] > t]
            before = [m for m in modules if origin + m[0] <= t]
            if after and before:
                row["module_start_us"] = (origin + after[0][0] - t) / 1e3
                row["prev_module_end_us"] = (origin + sum(before[-1])
                                             - t) / 1e3
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--cell", default="gpt2s-train-s1024")
    ap.add_argument("--seed", type=int, default=2147503677)
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--trace-dir", default=os.path.join(ROOT, "_chip",
                                                         "clock_trace"))
    args = ap.parse_args(argv)

    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax

    from benchmarks.harness import spec
    from paddle_tpu.io.prefetch import PipelineMetrics
    from tools import trace_gaps

    bench = spec.load_benchmark(args.bench, root=ROOT)
    cell = bench.cell(args.cell)
    runner = bench.module("runners", cell.params["runner"])
    prog = runner.build_program(bench, cell, args.seed)
    batches = runner.batch_fn(bench, cell, args.seed)
    depth = cell.params["prefetch_depth"]
    runner.drive(prog, batches, depth, steps=4)          # compile and warm

    every, fetched = [], [0]
    kept = PipelineMetrics.add_iteration

    def keep(self, record, starved_s, gc):
        every.append(record)
        return kept(self, record, starved_s, gc)

    def on_log(i, loss):
        fetched[0] += 1
        if fetched[0] == 4:
            jax.profiler.start_trace(args.trace_dir)
        elif fetched[0] == 14:
            jax.profiler.stop_trace()

    PipelineMetrics.add_iteration = keep
    try:
        _, snap = runner.drive(prog, batches, depth, steps=args.steps,
                               on_log=on_log)
    finally:
        PipelineMetrics.add_iteration = kept
    data = jax.profiler.ProfileData.from_file(
        trace_gaps.find_xplane(args.trace_dir))
    origin = trace_gaps.origin_ns(data)
    got = rows(every, origin, *_planes(data))
    traced = [x for x in got if "module_start_us" in x]
    print(json.dumps({
        "origin_ns": origin, "records": got,
        "host_offsets_us": sorted(x["host_off_us"] for x in got
                                  if "host_off_us" in x),
        "dry_by_trace": [x["step"] for x in traced
                         if x["prev_module_end_us"] < 0],
        "starved_by_record": [x["step"] for x in traced if x["starved"]],
        "snapshot": {k: snap[k] for k in (
            "loop_ms", "starved_steps", "starved_by", "starved_s",
            "gc_pause_s", "gc_collections", "gc_gen2", "callback_s",
            "dispatch_s", "device_blocked_s")}}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
