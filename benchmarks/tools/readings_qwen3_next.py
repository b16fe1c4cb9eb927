"""The readings the Qwen3-Next cell's limits are set from, in one process on
the chip: the program, the float8 control, and six faults, each planted in
the reference put in the program's place (``tools/readings_sala.py`` is the
pattern):

- ``fault_no_carry``: no state carried across chunk boundaries (the state
  zeroed at the start of every block of ``reference.BLOCK`` = 128 tokens,
  the kernels' chunk);
- ``fault_beta_one``: the write strength beta set to 1;
- ``fault_neighbour_decay``: every DeltaNet head decays as its neighbour
  (head h takes head h - 1's ``A_log``, ``dt_bias`` and offsets, head 0 the
  last's);
- ``fault_no_output_gate``: the ``silu(z)`` output gate left out;
- ``fault_no_shared_gate``: the shared expert's sigmoid gate left out;
- ``fault_sigmoid_router``: sigmoid scores in place of the softmax.

    python3 benchmarks/tools/readings_qwen3_next.py <cell> --program 1,2,3 --control 1,2,3 --fault 1,2,3 [--fault-steps 1]

For each seed the plain reference follows the cell's first steps once; the
program's first steps, the control and the faults are each read against it.
``--fault-steps n`` follows each fault over its first n steps only: the
losses of those steps and ``grad_norm_gap`` (read at step 1) are compared,
``change_norm_gap`` (after the cell's last step) is not read. Beside each
reading stand the limits of the cell's file that it fails. One JSON line a
reading, also appended to ``chiprun_out/readings_<cell>.jsonl``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.tools.readings_moe import seeds  # noqa: E402
from benchmarks.tools.readings_sala import swapped  # noqa: E402


def faults(reference) -> dict:
    """name -> a context manager under which the reference computes the
    fault."""
    import jax
    import jax.numpy as jnp
    return {
        "fault_no_carry": lambda: swapped(
            reference, "carry", lambda carry: jnp.zeros_like),
        "fault_beta_one": lambda: swapped(
            reference, "write_strength", lambda strength: jnp.ones_like),
        "fault_neighbour_decay": lambda: swapped(
            reference, "head_decay",
            lambda decay: lambda a, d, v: tuple(
                jnp.roll(x, 1) for x in decay(a, d, v))),
        "fault_no_output_gate": lambda: swapped(
            reference, "output_gate", lambda gate: jnp.ones_like),
        "fault_no_shared_gate": lambda: swapped(
            reference, "shared_gate",
            lambda gate: lambda t, w, math: 1.0),
        "fault_sigmoid_router": lambda: swapped(
            reference, "router_probs", lambda probs: jax.nn.sigmoid),
    }


def main(argv=None, *, benchmark_json=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--program", type=seeds, default=[])
    ap.add_argument("--control", type=seeds, default=[])
    ap.add_argument("--fault", type=seeds, default=[])
    ap.add_argument("--kinds", default="", help="the --fault seeds read "
                    "these alone (default: all six)")
    ap.add_argument("--fault-steps", type=int, default=0, help="follow the "
                    "faults over this many first steps (default: the "
                    "cell's)")
    args = ap.parse_args(argv)

    import jax

    from benchmarks.harness import compare, spec
    bench = spec.load_benchmark(
        benchmark_json or os.path.join(ROOT, "BENCHMARK.json"), root=ROOT)
    cell = bench.cell(args.cell)
    runner = bench.module("runners", cell.params["runner"])
    numerics = bench.module("reference", "numerics")
    reference = bench.module("reference", cell.config.values["family"])
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"readings_{cell.name}.jsonl")

    steps = cell.params["check"]["steps"]
    short = args.fault_steps and args.fault_steps < steps
    fault_cell = dataclasses.replace(cell, params=dict(
        cell.params, check=dict(cell.params["check"],
                                steps=args.fault_steps))) if short else cell

    def emit(kind, seed, got, want, t0):
        rows = compare.training(got, want, cell.params["check"]["limits"])
        if len(got["loss"]) < steps:
            rows = [r for r in rows if r["name"] != "change_norm_gap"]
        line = json.dumps({
            "cell": cell.name, "kind": kind, "seed": seed,
            "steps": len(got["loss"]),
            "platform": jax.devices()[0].platform,
            "seconds": round(time.perf_counter() - t0, 2),
            "loss": got["loss"], "ref_loss": want["loss"],
            "numbers": {r["name"]: r["value"] for r in rows},
            "fails": [r["name"] for r in rows if r["limit"] is not None
                      and r["value"] > r["limit"]],
            "leaves": {r["name"]: r.get("leaf") for r in rows
                       if r.get("leaf")}})
        print("READING " + line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")

    every = sorted(set(args.program) | set(args.control) | set(args.fault))
    kinds = set(args.kinds.split(",")) if args.kinds else None
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
            for kind, plant in faults(reference).items():
                if kinds is not None and kind not in kinds:
                    continue
                t0 = time.perf_counter()
                with plant():
                    emit(kind, seed, runner.follow_reference(
                        bench, fault_cell, seed, batches), want, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
