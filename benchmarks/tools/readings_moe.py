"""The readings a mixture-of-experts cell's limits are set from, in one process
on the chip: ``tools/readings.py``'s program, float8 control and "half of the
batch left out", and two faults of the expert layer, each planted in the
reference put in the program's place by swapping its ``route``:

- ``top7``: one expert fewer than the configuration's top-k is chosen (and the
  weights renormalised over those): what a wrong ``k`` would compute;
- ``capacity``: every expert takes at most 1.0 x the mean load of a call
  (``tokens x k / experts``) in token order and the overflow is dropped: what
  a capacity-and-drop layer would compute. A comparison that cannot tell this
  from the dropless layer cannot guard "no token is dropped".

    python3 benchmarks/tools/readings_moe.py <cell> --program 1,2,3 --control 1,2,3 --fault 1,2,3 --choice 1
    python3 benchmarks/tools/readings_moe.py <cell> --drift 1 --steps 48 --every 4

``--drift`` seeds follow the cell's training for ``--steps`` steps twice, in the
float32 reference under its own AdamW and in the program's compiled step, and
count after every ``--every`` steps the assignments of the FIRST batch that
land on this chip's experts, layer by layer: where the load goes as the
routers train, witnessed by both (PERF.md section 6, "PR 28").

``--choice`` seeds also count, on the first batch, the tokens whose chosen experts differ between the program
(bfloat16 storage, float32 router) and the float32 reference, layer by layer,
and print the program's ``routing_stats``.
One JSON line a reading, also appended to
``chiprun_out/readings_<cell>.jsonl``.
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


def seeds(text):
    return [int(s) for s in text.split(",") if s]


def top_fewer(route):
    """``route`` with one expert fewer chosen."""
    return lambda scores, k: route(scores, k - 1)


def capped(route, factor: float = 1.0):
    """``route`` under a capacity of ``factor`` x the mean load: an expert
    keeps the first assignments it gets, in token order, and drops the rest
    (their weight becomes 0; the others are not renormalised)."""
    import jax.numpy as jnp

    def faulty(scores, k):
        w = route(scores, k)
        flat = w.reshape(-1, w.shape[-1])
        chosen = flat > 0
        capacity = int(factor * flat.shape[0] * k / flat.shape[1])
        rank = jnp.cumsum(chosen.astype(jnp.int32), axis=0)
        return jnp.where(chosen & (rank <= capacity), flat, 0.0).reshape(
            w.shape)
    return faulty


@contextlib.contextmanager
def planted(reference, make):
    """The reference's ``route`` with ``make`` around it."""
    sound = reference.route
    reference.route = make(sound)
    try:
        yield
    finally:
        reference.route = sound


FAULTS = {"fault_top7": top_fewer, "fault_capacity": capped}


def count_choice_gaps(bench, cell, runner, reference, seed, batches, log):
    """Tokens of the first batch whose chosen experts differ between the
    program and the float32 reference, by sparse layer; and the program's
    routing_stats."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    ref_train = bench.module("reference", "train")
    numerics = bench.module("reference", "numerics")
    values = cell.config.values
    family = bench.module("families", values["family"])
    model = family.build_model(values)
    dtype = jnp.dtype(values["dtype"])
    if dtype == jnp.bfloat16:
        model = model.bfloat16()
    shapes = reference.param_shapes(values)
    made = ref_train.make_params(shapes, seed, dtype,
                                 values["initializer_range"])
    params = {family.program_name(k): v for k, v in made.items()}
    ids, _ = batches(0)
    stats = model.routing_stats(ids, params=params)
    got = model.chosen_experts(ids, params=params)
    del model, params
    gaps = {}
    with jax.default_matmul_precision("highest"):
        for r in range(ids.shape[0]):
            want = jax.device_get(reference.chosen_experts(
                made, jnp.asarray(ids[r:r + 1]), values, numerics.Exact()))
            for layer, w in want.items():
                mine = np.sort(np.asarray(got[layer][r:r + 1]), axis=-1)
                gaps[layer] = gaps.get(layer, 0) + int(
                    (mine != np.asarray(w)).any(-1).sum())
    rec = {"cell": cell.name, "kind": "choice", "seed": seed,
           "tokens": int(ids.size), "tokens_choosing_otherwise": gaps,
           "routing_stats": stats}
    log(rec)


def follow_routing(bench, cell, runner, reference, seed, batches, steps,
                   every, log):
    """Assignments of the first batch that land here, by sparse layer, as
    the cell trains: the reference's count (float32 storage, ``follow``'s
    AdamW, the whole batch one block) beside the program's
    ``routing_stats``."""
    import jax
    import jax.numpy as jnp
    ref_train = bench.module("reference", "train")
    numerics = bench.module("reference", "numerics")
    values, p = cell.config.values, cell.params
    family = bench.module("families", values["family"])
    first, held, _ = reference._share(values)
    probe = jnp.asarray(batches(0)[0])
    exact = numerics.Exact()

    def landed(params):
        chosen = reference.chosen_experts(params, probe, values, exact)
        return [jnp.sum((c >= first) & (c < first + held))
                for _, c in sorted(chosen.items())]

    def grad(params, ids, labels):
        return jax.grad(lambda q: jnp.mean(reference.token_losses(
            q, ids, labels, values, exact)))(params)

    landed, grad = jax.jit(landed), jax.jit(grad)
    model = family.build_model(values)
    if jnp.dtype(values["dtype"]) == jnp.bfloat16:
        model = model.bfloat16()
    prog = runner.build_program(bench, cell, seed)
    params = {k: v.astype(jnp.float32) for k, v in ref_train.make_params(
        reference.param_shapes(values), seed, jnp.float32,
        values["initializer_range"]).items()}
    m1, m2 = {}, {}
    with jax.default_matmul_precision("highest"):
        for t in range(steps + 1):
            if t % every == 0 or t == steps:
                log({"cell": cell.name, "kind": "drift", "seed": seed,
                     "step": t, "tokens": int(probe.size),
                     "reference_here": [int(n) for n in landed(params)],
                     "program_here": [s["assignments_here"] for s in
                                      model.routing_stats(
                                          probe, params=prog.params)]})
            if t == steps:
                break
            ids, labels = batches(t)
            grads = grad(params, jnp.asarray(ids), jnp.asarray(labels))
            for k in list(params):
                shape = params[k].shape
                params[k], m1[k], m2[k] = ref_train._adamw_leaf(
                    params[k], grads.pop(k),
                    m1.get(k, jnp.zeros(shape, jnp.float32)),
                    m2.get(k, jnp.zeros(shape, jnp.float32)),
                    float(t + 1), float(p["lr"]),
                    float(p["weight_decay"]), decay=params[k].ndim >= 2,
                    dtype=jnp.dtype(jnp.float32))
            runner.drive(prog, batches, p["prefetch_depth"], steps=1)


def main(argv=None, *, benchmark_json=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--program", type=seeds, default=[])
    ap.add_argument("--control", type=seeds, default=[])
    ap.add_argument("--fault", type=seeds, default=[])
    ap.add_argument("--choice", type=seeds, default=[])
    ap.add_argument("--drift", type=seeds, default=[])
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--every", type=int, default=4)
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
        follow_routing(bench, cell, runner, reference, seed,
                       runner.batch_fn(bench, cell, seed), args.steps,
                       args.every, log)
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
            t0 = time.perf_counter()
            emit("fault_half_batch", seed, runner.follow_reference(
                bench, cell, seed, batches,
                leave_out_rows=cell.params["batch"] // 2), want, t0)
            for kind, make in FAULTS.items():
                t0 = time.perf_counter()
                with planted(reference, make):
                    emit(kind, seed, runner.follow_reference(
                        bench, cell, seed, batches), want, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
