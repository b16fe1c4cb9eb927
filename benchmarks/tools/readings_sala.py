"""The readings the MiniCPM-SALA cell's limits are set from, in one process on
the chip: the program, the float8 control, and four faults, each planted in
the reference put in the program's place (``tools/readings_glm.py`` is the
pattern):

- ``fault_top32``: half of ``sparse_config.topk`` blocks are chosen;
- ``fault_no_local``: the forced local blocks left out (``window_size`` =
  one block: a query's own block only, beside block 0);
- ``fault_neighbour_decay``: every lightning head decays at its neighbour's
  rate (head h takes head h - 1's, head 0 the last's);
- ``fault_no_gate``: the mixers' output gate left out (a gate of 1).

    python3 benchmarks/tools/readings_sala.py <cell> --program 1,2,3 --control 1,2,3 --fault 1,2,3 --choice 1,2,3,4 --choice-faults 1,2

``--choice`` seeds read ``choice_gap`` alone (``runners/train_lean.py``: the
(query, block) pairs of the first batch chosen by one side and not by the
float32 reference, over the pairs the reference chose) for the program, and
those also under ``--choice-faults`` for the control and the two faults that
touch the choice; nothing is trained for it. Beside each reading stand the
limits of the cell's file that it fails. One JSON line a reading, also
appended to ``chiprun_out/readings_<cell>.jsonl``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.tools.readings_glm import changed  # noqa: E402
from benchmarks.tools.readings_moe import seeds  # noqa: E402


@contextlib.contextmanager
def swapped(module, name: str, make):
    """``module.name`` replaced by ``make(the original)``, for one reading."""
    before = getattr(module, name)
    setattr(module, name, make(before))
    try:
        yield
    finally:
        setattr(module, name, before)


def faults(reference, values: dict) -> dict:
    """name -> a context manager under which the reference computes the
    fault."""
    import numpy as np
    sc = values["sparse_config"]
    return {
        "fault_top32": lambda: changed(
            values, sparse_config=dict(sc, topk=sc["topk"] // 2)),
        "fault_no_local": lambda: changed(
            values, sparse_config=dict(sc, window_size=sc["block_size"])),
        "fault_neighbour_decay": lambda: swapped(
            reference, "decay_rates",
            lambda rates: lambda v, layer: np.roll(rates(v, layer), 1)),
        "fault_no_gate": lambda: swapped(
            reference, "output_gate", lambda gate: lambda y, w, math: 1.0),
    }


CHOICE_FAULTS = ("fault_top32", "fault_no_local")


def read_choices(bench, cell, runner, reference, seed, batches, log,
                 spoiled: bool):
    """``choice_gap`` of the first batch, as the cell's runner compares it,
    for the program and, ``spoiled``, for the float8 control and the two
    faults that touch the choice, each against the float32 reference's
    choices. No step is trained."""
    numerics = bench.module("reference", "numerics")
    values = cell.config.values
    ids = batches(0)[0]
    t0 = time.perf_counter()
    want = runner.reference_choices(bench, cell, seed, ids)
    numbers = {"program": runner.choice_gap(
        runner.program_choices(bench, cell, seed, ids), want)}
    if spoiled:
        numbers["control_fp8"] = runner.choice_gap(runner.reference_choices(
            bench, cell, seed, ids, math=numerics.Fp8()), want)
        for kind in CHOICE_FAULTS:
            with faults(reference, values)[kind]():
                numbers[kind] = runner.choice_gap(
                    runner.reference_choices(bench, cell, seed, ids), want)
    log({"cell": cell.name, "kind": "choice", "seed": seed,
         "seconds": round(time.perf_counter() - t0, 2),
         "choices": int(sum(a.sum() for a in want.values())),
         "limit": cell.params["check"]["limits"].get("choice_gap"),
         "numbers": numbers})


def main(argv=None, *, benchmark_json=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--program", type=seeds, default=[])
    ap.add_argument("--control", type=seeds, default=[])
    ap.add_argument("--fault", type=seeds, default=[])
    ap.add_argument("--choice", type=seeds, default=[])
    ap.add_argument("--choice-faults", type=seeds, default=[])
    ap.add_argument("--kinds", default="", help="the --fault seeds read "
                    "these alone (default: all four)")
    args = ap.parse_args(argv)

    import jax

    from benchmarks.harness import compare, spec
    bench = spec.load_benchmark(
        benchmark_json or os.path.join(ROOT, "BENCHMARK.json"), root=ROOT)
    cell = bench.cell(args.cell)
    values = cell.config.values
    runner = bench.module("runners", cell.params["runner"])
    numerics = bench.module("reference", "numerics")
    reference = bench.module("reference", values["family"])
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"readings_{cell.name}.jsonl")

    def log(rec):
        line = json.dumps(rec)
        print("READING " + line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")

    def emit(kind, seed, got, want, t0):
        rows = compare.training(got, want, cell.params["check"]["limits"])
        log({"cell": cell.name, "kind": kind, "seed": seed,
             "platform": jax.devices()[0].platform,
             "seconds": round(time.perf_counter() - t0, 2),
             "loss": got["loss"], "ref_loss": want["loss"],
             "numbers": {r["name"]: r["value"] for r in rows},
             "fails": [r["name"] for r in rows if r["limit"] is not None
                       and r["value"] > r["limit"]],
             "leaves": {r["name"]: r.get("leaf") for r in rows
                        if r.get("leaf")}})

    every = sorted(set(args.program) | set(args.control) | set(args.fault)
                   | set(args.choice))
    kinds = set(args.kinds.split(",")) if args.kinds else None
    for seed in every:
        batches = runner.batch_fn(bench, cell, seed)
        got, t_prog = None, time.perf_counter()
        if seed in args.program:
            prog = runner.build_program(bench, cell, seed)
            got = runner.first_steps(bench, cell, prog, batches, seed)
            prog.params = prog.opt_state = prog.step = None
            del prog
        if seed in args.choice:
            read_choices(bench, cell, runner, reference, seed, batches, log,
                         spoiled=seed in args.choice_faults)
        if not (got or seed in args.control or seed in args.fault):
            continue
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
            for kind, plant in faults(reference, values).items():
                if kinds is not None and kind not in kinds:
                    continue
                t0 = time.perf_counter()
                with plant():
                    emit(kind, seed, runner.follow_reference(
                        bench, cell, seed, batches), want, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
