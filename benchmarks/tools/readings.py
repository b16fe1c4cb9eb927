"""The readings a cell's limits are set from, in one process on the chip.

    python3 benchmarks/tools/readings.py <cell> --program 1,2,3 --control 1,2,3 --fault 1,2,3

For each seed the plain reference follows the cell's first steps once. Then,
by the lists the seed is in: the program's own first steps are read against
it (the lower reading: sound runs), the control (the same reference with
float8 operands, ``reference/numerics.py::Fp8``) is read against it (the
upper reading), and the fault "half of the batch left out, the mean taken
over the rest", planted in the reference put in the program's place. Training's
readings need no measured window. One JSON line a reading, also appended to
``chiprun_out/readings_<cell>.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

def seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None, *, benchmark_json=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--program", type=seeds, default=[])
    ap.add_argument("--control", type=seeds, default=[])
    ap.add_argument("--fault", type=seeds, default=[])
    args = ap.parse_args(argv)

    import jax

    from benchmarks.harness import compare, spec
    bench = spec.load_benchmark(
        benchmark_json or os.path.join(ROOT, "BENCHMARK.json"), root=ROOT)
    cell = bench.cell(args.cell)
    runner = bench.module("runners", cell.params["runner"])
    numerics = bench.module("reference", "numerics")
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"readings_{cell.name}.jsonl")

    def emit(kind, seed, got, want, t0):
        rows = compare.training(got, want, {})
        rec = {"cell": cell.name, "kind": kind, "seed": seed,
               "platform": jax.devices()[0].platform,
               "seconds": round(time.perf_counter() - t0, 2),
               "loss": got["loss"], "ref_loss": want["loss"],
               "numbers": {r["name"]: r["value"] for r in rows},
               "leaves": {r["name"]: r.get("leaf") for r in rows
                          if r.get("leaf")}}
        line = json.dumps(rec)
        print("READING " + line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")

    every = sorted(set(args.program) | set(args.control) | set(args.fault))
    for seed in every:
        batches = runner.batch_fn(bench, cell, seed)
        got, t_prog = None, time.perf_counter()
        if seed in args.program:
            prog = runner.build_program(bench, cell, seed)
            got = runner.first_steps(bench, cell, prog, batches, seed)
            prog.params = prog.opt_state = prog.step = None
            del prog
        t0 = time.perf_counter()
        want = runner.follow_reference(bench, cell, seed, batches)
        print(f"reference seed {seed}: {time.perf_counter() - t0:.1f} s",
              flush=True)
        if got is not None:
            emit("program", seed, got, want, t_prog)
        if seed in args.control:
            t0 = time.perf_counter()
            emit("control_fp8", seed, runner.follow_reference(
                bench, cell, seed, batches, math=numerics.Fp8()), want, t0)
        if seed in args.fault:
            t0 = time.perf_counter()
            emit("fault_half_batch", seed, runner.follow_reference(
                bench, cell, seed, batches,
                leave_out_rows=cell.params["batch"] // 2), want, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
