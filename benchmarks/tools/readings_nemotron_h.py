"""The readings the Nemotron-3 Nano cell's limits are set from, in one process
on the chip: the program, the float8 control, the control whose scan keeps
its state in bfloat16, and six faults, each planted in the reference put in
the program's place (``tools/readings_qwen3_next.py`` is the pattern):

- ``control_bf16_state``: the scan's state rounded to bfloat16 after every
  token (straight through in the backward pass), everything else float32;
- ``fault_no_carry``: no state carried across chunk boundaries (the state
  zeroed at the start of every block of ``chunk_size`` = 128 tokens, the
  kernels' chunk);
- ``fault_neighbour_group``: every head reads the neighbouring group's B
  and C (group g + 1 for group g, the last the first);
- ``fault_neighbour_decay``: every Mamba-2 head decays as its neighbour
  (head h takes head h - 1's ``A_log``, ``dt_bias`` and offsets);
- ``fault_no_skip``: the skip ``D x`` left out;
- ``fault_gate_after_norm``: ``w RMSNorm(y) silu(z)`` in place of ``w
  RMSNorm(y silu(z))``;
- ``fault_relu``: the experts' relu^2 replaced by relu.

    python3 benchmarks/tools/readings_nemotron_h.py <cell> --program 1,2,3 --control 1,2,3 --fault 1,2,3 [--fault-steps 1] [--choice 1]

``--control`` reads both controls. ``--kinds control_bf16`` reads, beside
them or alone, the reference with every matrix product's operands rounded to
bfloat16: the storage the configuration states, so not a control that has to
fail, but what the program's own precision reads in the reference.
``--choice`` seeds count, on the first batch, the tokens whose chosen experts
differ between the program and the float32 reference, by expert layer. For each seed the plain reference follows
the cell's first steps once; the program's first steps, the controls and the
faults are each read against it. ``--fault-steps n`` follows each fault (and
the bfloat16-state control) over its first n steps only: the losses of those
steps and ``grad_norm_gap`` (read at step 1) are compared,
``change_norm_gap`` is not read. Beside each reading stand the limits of the
cell's file that it fails. One JSON line a reading, also appended to
``chiprun_out/readings_<cell>.jsonl``.
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


def bf16_state(reference):
    """The control: the scan's state in bfloat16."""
    import jax

    def rounded(state_precision):
        def state(st):
            q = jax.lax.reduce_precision(st, 8, 7)
            return st + jax.lax.stop_gradient(q - st)
        return state
    return swapped(reference, "state_precision", rounded)


def faults(reference) -> dict:
    """name -> a context manager under which the reference computes the
    fault."""
    import jax
    import jax.numpy as jnp

    def gate_after_norm(y, z, w, groups, eps):
        v = y.reshape(y.shape[:-1] + (groups, -1))
        v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                              + eps)
        return v.reshape(y.shape) * w * jax.nn.silu(z)
    return {
        "fault_no_carry": lambda: swapped(
            reference, "carry", lambda carry: jnp.zeros_like),
        "fault_neighbour_group": lambda: swapped(
            reference, "group_of",
            lambda group_of: lambda h, g: (group_of(h, g) + 1) % g),
        "fault_neighbour_decay": lambda: swapped(
            reference, "head_decay",
            lambda decay: lambda a, d, v: tuple(
                jnp.roll(x, 1) for x in decay(a, d, v))),
        "fault_no_skip": lambda: swapped(
            reference, "skip", lambda skip: lambda x, d: 0.0),
        "fault_gate_after_norm": lambda: swapped(
            reference, "gated_norm", lambda norm: gate_after_norm),
        "fault_relu": lambda: swapped(
            reference, "expert_act", lambda act: jax.nn.relu),
    }


def worst_leaves(got: dict, want: dict, n: int = 6) -> list:
    """The n leaves farthest from the reference in the norm of their step-1
    gradient, by ``harness/compare.py``'s measure: [leaf, gap, reference
    norm]."""
    import statistics
    floor = statistics.median(want.values())
    gaps = sorted(((abs(got[k] - w) / max(w, floor, 1e-30), k, w)
                   for k, w in want.items()), reverse=True)
    return [[k, g, w] for g, k, w in gaps[:n]]


def count_choice_gaps(bench, cell, runner, reference, seed, batches):
    """Tokens of the first batch whose chosen experts differ between the
    program (bfloat16 storage, float32 router) and the float32 reference,
    by expert layer; and how many of those differ in an expert this chip
    holds."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    ref_train = bench.module("reference", "train")
    numerics = bench.module("reference", "numerics")
    values = cell.config.values
    family = bench.module("families", values["family"])
    model = family.build_model(values)
    if jnp.dtype(values["dtype"]) == jnp.bfloat16:
        model = model.bfloat16()
    made = ref_train.make_params(reference.param_shapes(values), seed,
                                 jnp.dtype(values["dtype"]),
                                 values["initializer_range"])
    ids = jnp.asarray(batches(0)[0])
    got = model.chosen_experts(ids, params={
        family.program_name(k): a for k, a in made.items()})
    del model
    with jax.default_matmul_precision("highest"):
        want = jax.device_get(jax.jit(
            lambda p, x: reference.chosen_experts(
                p, x, values, numerics.Exact()))(made, ids))
    first, held, _ = reference._share(values)
    out, here = {}, {}
    for layer, w in want.items():
        mine = np.sort(np.asarray(got[layer]), axis=-1)
        w = np.asarray(w)
        out[layer] = int((mine != w).any(-1).sum())
        hm = ((mine >= first) & (mine < first + held))
        hw = ((w >= first) & (w < first + held))
        here[layer] = int(((mine != w) & (hm | hw)).any(-1).sum())
    return {"cell": cell.name, "kind": "choice", "seed": seed,
            "tokens": int(ids.size), "tokens_choosing_otherwise": out,
            "of_those_a_held_expert": here}


def main(argv=None, *, benchmark_json=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--program", type=seeds, default=[])
    ap.add_argument("--control", type=seeds, default=[])
    ap.add_argument("--fault", type=seeds, default=[])
    ap.add_argument("--choice", type=seeds, default=[])
    ap.add_argument("--kinds", default="", help="the --fault and --control "
                    "seeds read these alone (default: all)")
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
                       if r.get("leaf")},
            "worst_grad_leaves": worst_leaves(got["grad_norm"],
                                              want["grad_norm"])})
        print("READING " + line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")

    every = sorted(set(args.program) | set(args.control) | set(args.fault)
                   | set(args.choice))
    kinds = set(args.kinds.split(",")) if args.kinds else None

    def wanted(kind):
        return kinds is None or kind in kinds
    for seed in every:
        batches = runner.batch_fn(bench, cell, seed)
        got, t_prog = None, time.perf_counter()
        if seed in args.program:
            prog = runner.build_program(bench, cell, seed)
            got = runner.first_steps(bench, cell, prog, batches, seed)
            prog.params = prog.opt_state = prog.step = None
            del prog
        if seed in args.choice:
            line = json.dumps(count_choice_gaps(
                bench, cell, runner, reference, seed, batches))
            print("READING " + line, flush=True)
            with open(out_path, "a") as f:
                f.write(line + "\n")
        if not (got or seed in args.control or seed in args.fault):
            continue
        t0 = time.perf_counter()
        want = runner.follow_reference(bench, cell, seed, batches)
        print(f"reference seed {seed}: {time.perf_counter() - t0:.1f} s",
              flush=True)
        if got is not None:
            emit("program", seed, got, want, t_prog)
        if seed in args.control:
            if wanted("control_fp8"):
                t0 = time.perf_counter()
                emit("control_fp8", seed, runner.follow_reference(
                    bench, cell, seed, batches, math=numerics.Fp8()), want,
                    t0)
            if kinds and "control_bf16" in kinds:
                t0 = time.perf_counter()
                emit("control_bf16", seed, runner.follow_reference(
                    bench, cell, seed, batches, math=numerics.Bf16()), want,
                    t0)
            if wanted("control_bf16_state"):
                t0 = time.perf_counter()
                with bf16_state(reference):
                    emit("control_bf16_state", seed, runner.follow_reference(
                        bench, fault_cell, seed, batches), want, t0)
        if seed in args.fault:
            for kind, plant in faults(reference).items():
                if not wanted(kind):
                    continue
                t0 = time.perf_counter()
                with plant():
                    emit(kind, seed, runner.follow_reference(
                        bench, fault_cell, seed, batches), want, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
