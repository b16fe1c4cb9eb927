"""The readings the GLM MoE lite cell's limits are set from, in one process on
the chip: the program, the float8 control, and four faults and one more
control, each planted in the reference put in the program's place
(``tools/readings_moe.py`` is the pattern; its ``route`` takes no bias, so
this family has a file of its own):

- ``fault_half_batch``: half of the batch's rows left out;
- ``fault_top3``: one expert fewer than the configuration's top-k is chosen
  (and the weights renormalised over those);
- ``fault_no_mtp``: the multi-token-prediction term left out of the loss
  (``mtp_loss_weight`` 0): the module's leaves then get no gradient;
- ``control_no_bias``: the experts chosen by their scores alone, as a router
  without the correction bias would (``score_bias.scale`` 0).

    python3 benchmarks/tools/readings_glm.py <cell> --program 1,2,3 --control 1,2,3 --fault 1,2,3 --choice 1
    python3 benchmarks/tools/readings_glm.py <cell> --drift 1 --steps 36

``--choice`` seeds count, on the first batch, the tokens whose chosen experts
differ between the program and the float32 reference, layer by layer, and
print the program's ``routing_stats``. ``--drift`` seeds print the program's
``routing_stats`` of the FIRST batch before training and after ``--steps``
steps of the cell's own training: do the held experts keep their rows? One
JSON line a reading, also appended to ``chiprun_out/readings_<cell>.jsonl``.
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

from benchmarks.tools.readings_moe import (count_choice_gaps,  # noqa: E402
                                           planted, seeds)


def top_fewer(route):
    """``route`` with one expert fewer chosen."""
    return lambda scores, k, bias: route(scores, k - 1, bias)


@contextlib.contextmanager
def changed(values: dict, **keys):
    """The configuration's values with ``keys`` changed, for one reading."""
    before = {k: values[k] for k in keys}
    values.update(keys)
    try:
        yield
    finally:
        values.update(before)


def faults(reference, values: dict) -> dict:
    """name -> a context manager under which the reference computes the
    fault (or the control)."""
    return {
        "fault_top3": lambda: planted(reference, top_fewer),
        "fault_no_mtp": lambda: changed(values, mtp_loss_weight=0.0),
        "control_no_bias": lambda: changed(
            values, score_bias=dict(values["score_bias"], scale=0.0)),
    }


def follow_drift(bench, cell, runner, seed, batches, steps, log):
    values = cell.config.values
    family = bench.module("families", values["family"])
    import jax.numpy as jnp
    model = family.build_model(values)
    if jnp.dtype(values["dtype"]) == jnp.bfloat16:
        model = model.bfloat16()
    prog = runner.build_program(bench, cell, seed)
    probe = batches(0)[0]
    for at in (0, steps):
        if at:
            runner.drive(prog, batches, cell.params["prefetch_depth"],
                         steps=steps)
        log({"cell": cell.name, "kind": "drift", "seed": seed, "step": at,
             "tokens": int(probe.size),
             "routing_stats": model.routing_stats(probe,
                                                  params=prog.params)})


def main(argv=None, *, benchmark_json=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--program", type=seeds, default=[])
    ap.add_argument("--control", type=seeds, default=[])
    ap.add_argument("--fault", type=seeds, default=[])
    ap.add_argument("--choice", type=seeds, default=[])
    ap.add_argument("--drift", type=seeds, default=[])
    ap.add_argument("--kinds", default="", help="of fault_half_batch,"
                    "fault_top3,fault_no_mtp,control_no_bias: the --fault "
                    "seeds read these alone (default: all four)")
    ap.add_argument("--steps", type=int, default=36)
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
        rows = compare.training(got, want, {})
        log({"cell": cell.name, "kind": kind, "seed": seed,
             "platform": jax.devices()[0].platform,
             "seconds": round(time.perf_counter() - t0, 2),
             "loss": got["loss"], "ref_loss": want["loss"],
             "numbers": {r["name"]: r["value"] for r in rows},
             "leaves": {r["name"]: r.get("leaf") for r in rows
                        if r.get("leaf")}})

    for seed in args.drift:
        follow_drift(bench, cell, runner, seed,
                     runner.batch_fn(bench, cell, seed), args.steps, log)
    every = sorted(set(args.program) | set(args.control) | set(args.fault)
                   | set(args.choice))
    for seed in every:
        batches = runner.batch_fn(bench, cell, seed)
        got, t_prog = None, time.perf_counter()
        if seed in args.program:
            prog = runner.build_program(bench, cell, seed)
            got = runner.first_steps(bench, cell, prog, batches, seed)
            prog.params = prog.opt_state = prog.step = None
            del prog
        if seed in args.choice:
            count_choice_gaps(bench, cell, runner, reference, seed, batches,
                              log)
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
            kinds = set(args.kinds.split(",")) if args.kinds else None
            if kinds is None or "fault_half_batch" in kinds:
                t0 = time.perf_counter()
                emit("fault_half_batch", seed, runner.follow_reference(
                    bench, cell, seed, batches,
                    leave_out_rows=cell.params["batch"] // 2), want, t0)
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
