"""Runner of the training cells: one process, one chip, one jitted step.

The system under test is ``models.trainer.create_train_step`` driven by
``models.trainer.run_steps`` over ``io.prefetch_to_device``. Everything else
here is the benchmark's: the weights and the batches (from ``--seed``), the
clock, the counters it reads, the first steps it keeps for the comparison with
the plain reference, and the trace.

A run goes: build (model, weights from the seed, optimizer, the step) ->
first steps (they compile, and their losses, the first gradient's norms and
the parameters' change are kept) -> warm steps -> the measured window ->
memory and counters read -> the program's state freed -> the reference
follows the first steps -> the comparison.
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import time

import numpy as np

# steps between the first steps and the window: they let the prefetcher, the
# allocator and the lagged fetch reach their steady state
WARM_STEPS = 3
# the traced run traces this many steps, from this fetched step on
TRACE_FROM, TRACE_STEPS = 4, 8
STEP_PATTERN = r"jit_train_step"


@dataclasses.dataclass
class Program:
    """The compiled step with its state: one object from set-up to window."""
    step: object
    params: dict
    opt_state: dict
    key: object
    lr: float
    names: dict            # reference leaf name -> program leaf name
    next_step: int = 0


def _family(bench, cell):
    fam = cell.config.values["family"]
    return bench.module("families", fam), bench.module("reference", fam)


def build_program(bench, cell, seed: int) -> Program:
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import create_train_step, write_back

    ref_train = bench.module("reference", "train")
    family, reference = _family(bench, cell)
    values, p = cell.config.values, cell.params
    dtype = jnp.dtype(values["dtype"])

    paddle.seed(int(seed) & 0x7FFFFFFF)
    model = family.build_model(values)
    if dtype == jnp.bfloat16:
        model = model.bfloat16()
    model.train()
    # the weights are the benchmark's, made on the device from the seed in
    # one jitted call, and written into the model before the step is made
    shapes = reference.param_shapes(values)
    names = {k: family.program_name(k) for k in shapes}
    made = ref_train.make_params(shapes, seed, dtype,
                                 values["initializer_range"])
    write_back(model, {names[k]: v for k, v in made.items()}, strict=True)
    del made
    opt = paddle.optimizer.AdamW(learning_rate=p["lr"],
                                 weight_decay=p["weight_decay"],
                                 parameters=model.parameters())
    step, params, opt_state = create_train_step(model, opt,
                                                donate=p["donate"])
    missing = set(names.values()) ^ set(params)
    if missing:
        raise SystemExit(f"leaves the reference and the program do not "
                         f"share: {sorted(missing)[:6]}")
    return Program(step=step, params=params, opt_state=opt_state,
                   key=ref_train.seed_key(seed), lr=float(p["lr"]),
                   names=names)


def batch_fn(bench, cell, seed: int):
    """step index -> (ids, labels): the general generator of the training
    traffic. Its parameters are the cell's ``batch`` and ``seq`` and the
    configuration's ``vocab_size``."""
    make = bench.module("reference", "train").make_batch
    p, vocab = cell.params, cell.config.values["vocab_size"]
    return lambda i: make(seed, i, p["batch"], p["seq"], vocab)


def route_of(prog: Program, batch) -> int:
    """How many Mosaic custom calls the lowered step holds: the attention
    route the step took (copied from chip_smoke.py::leg_train)."""
    ids, labels = batch
    return prog.step.lower(prog.params, prog.opt_state, prog.key, ids,
                           labels, prog.lr).as_text().count("tpu_custom_call")


def drive(prog: Program, batches, depth: int, *, steps=None, deadline=None,
          on_log=None, wrap_step=None):
    """Run the step over batches ``prog.next_step, ...`` through the
    window's own call and feed: ``run_steps`` over ``prefetch_to_device``.
    Ends after ``steps`` steps, or at the first batch that would be made after
    ``deadline[0]`` (a time on ``time.perf_counter``'s clock). Returns the
    fetched losses and the feed's counters."""
    from paddle_tpu.io import prefetch_to_device
    from paddle_tpu.models import run_steps

    start = prog.next_step

    def source():
        i = start
        while (steps is None or i < start + steps) and \
                (deadline is None or time.perf_counter() < deadline[0]):
            yield batches(i)
            i += 1

    feed = prefetch_to_device(source(), depth=depth, name="bench_feed")
    try:
        step = wrap_step(prog.step) if wrap_step else prog.step
        prog.params, prog.opt_state, losses = run_steps(
            step, prog.params, prog.opt_state, feed, key=prog.key,
            lr=prog.lr, log_every=1 if on_log else 0, on_log=on_log,
            start_step=start)
        counters = feed.metrics.snapshot()
    finally:
        feed.close()
    prog.next_step = start + len(losses)
    return [float(v) for v in losses], counters


def first_steps(bench, cell, prog: Program, batches, seed: int,
                wrap_step=None) -> dict:
    """Drive the program through the steps the reference will follow and keep
    what is compared. The gradient's norms come from Adam's first moment
    after one step (m = (1 - beta1) g); the change is taken against weights
    made anew from the seed, since the step has consumed the ones it got."""
    ref_train = bench.module("reference", "train")
    _, reference = _family(bench, cell)
    import jax.numpy as jnp
    values, p = cell.config.values, cell.params
    n = p["check"]["steps"]
    depth = p["prefetch_depth"]
    back = {v: k for k, v in prog.names.items()}
    losses, _ = drive(prog, batches, depth, steps=1, wrap_step=wrap_step)
    m1 = ref_train.leaf_norms(
        {back[k]: st["moment1"] for k, st in prog.opt_state.items()})
    grad_norm = {k: v / (1 - ref_train.BETA1) for k, v in m1.items()}
    if n > 1:
        more, _ = drive(prog, batches, depth, steps=n - 1,
                        wrap_step=wrap_step)
        losses += more
    start = ref_train.make_params(reference.param_shapes(values), seed,
                                  jnp.dtype(values["dtype"]),
                                  values["initializer_range"])
    change = ref_train.change_norms(
        {back[k]: v for k, v in prog.params.items()}, start)
    del start
    return {"loss": losses, "grad_norm": grad_norm, "change_norm": change}


def follow_reference(bench, cell, seed: int, batches, math=None,
                     leave_out_rows: int = 0) -> dict:
    """The plain reference over the same first steps, from weights made anew
    from the seed."""
    import jax
    import jax.numpy as jnp
    ref_train = bench.module("reference", "train")
    numerics = bench.module("reference", "numerics")
    _, reference = _family(bench, cell)
    values, p = cell.config.values, cell.params
    chk = p["check"]
    dtype = jnp.dtype(values["dtype"])
    params0 = ref_train.make_params(reference.param_shapes(values), seed,
                                    dtype, values["initializer_range"])
    with jax.default_matmul_precision("highest"):
        return ref_train.follow(
            reference.token_losses, values, params0,
            [batches(i) for i in range(chk["steps"])], lr=float(p["lr"]),
            weight_decay=float(p["weight_decay"]),
            math=math or numerics.Exact(), row_block=chk["row_block"],
            store_dtype=dtype,
            moments_on_host=chk.get("moments_on_host", False),
            leave_out_rows=leave_out_rows)


def run(ctx: dict) -> dict:
    """One run of one cell. ``ctx``: bench, cell, seed, seconds, trace,
    t_start (the process's start on ``time.perf_counter``'s clock), log (a
    function that prints a line to standard error), and for the benchmark's
    tests ``wrap_step`` (plants a fault under the timed path)."""
    import jax

    from benchmarks.harness import compare
    from benchmarks.harness import trace as _trace
    from benchmarks.harness.watch import compile_watch

    bench, cell, seed, log = ctx["bench"], ctx["cell"], ctx["seed"], ctx["log"]
    wrap_step = ctx.get("wrap_step")
    p = cell.params
    depth = p["prefetch_depth"]
    dev = jax.devices()[0]
    batches = batch_fn(bench, cell, seed)

    prog = build_program(bench, cell, seed)
    t_built = time.perf_counter()
    route = route_of(prog, batches(0))
    log(f"route: {route} tpu_custom_call in the lowered step "
        f"({'Pallas/Mosaic' if route else 'XLA'} attention), batch "
        f"{p['batch']} x seq {p['seq']}, donate={p['donate']!r}")
    with compile_watch() as setup_watch:
        got = first_steps(bench, cell, prog, batches, seed, wrap_step)
        drive(prog, batches, depth, steps=WARM_STEPS, wrap_step=wrap_step)
    log(f"set-up: build {t_built - ctx['t_start']:.1f} s, first steps + warm "
        f"{time.perf_counter() - t_built:.1f} s, compile requests "
        f"{setup_watch['compiles']}, persistent cache hits "
        f"{setup_watch['cache_hits']} misses {setup_watch['cache_misses']}")

    # -- the measured window -------------------------------------------------
    stamps = []
    trace_dir = os.path.join(bench.root, bench.paths[0], ".trace")
    tracing = {"on": False, "done": False}

    def on_log(i, loss):
        del loss
        stamps.append(time.perf_counter())
        if not ctx["trace"]:
            return
        n = len(stamps)
        if n == TRACE_FROM and not tracing["on"]:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            tracing["on"] = True
        elif n == TRACE_FROM + TRACE_STEPS + 2 and tracing["on"]:
            jax.profiler.stop_trace()
            tracing["on"], tracing["done"] = False, True

    deadline = [float("inf")]
    with compile_watch() as watch:
        t_open = time.perf_counter()
        deadline[0] = t_open + ctx["seconds"]
        try:
            losses, feed_counters = drive(prog, batches, depth,
                                          deadline=deadline, on_log=on_log,
                                          wrap_step=wrap_step)
            raised = None
        except Exception as e:      # a step that raises has failed
            losses, feed_counters, raised = [], {}, e
        t_close = time.perf_counter()
    if tracing["on"]:
        jax.profiler.stop_trace()
        tracing["done"] = True
    window_s = t_close - t_open
    attempted = len(losses) + (1 if raised else 0)
    failed = sum(1 for v in losses if not np.isfinite(v)) + \
        (1 if raised else 0)
    if raised:
        log(f"a step raised: {raised!r}")
    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    log(f"memory_stats: {mem}")
    log(f"window: {len(losses)} steps in {window_s:.3f} s, compile requests "
        f"inside {watch['compiles']}, loss {losses[0] if losses else None} "
        f"-> {losses[-1] if losses else None}")

    # -- free the program, then let the reference follow ----------------------
    prog.params = prog.opt_state = prog.step = None
    del prog
    reduced = None
    if tracing["done"]:
        reduced = _trace.reduce(
            _trace.load_events(_trace.find_xplane(trace_dir)), STEP_PATTERN)
        shutil.rmtree(trace_dir, ignore_errors=True)
    t_ref = time.perf_counter()
    want = follow_reference(bench, cell, seed, batches)
    log(f"reference: {p['check']['steps']} steps followed in "
        f"{time.perf_counter() - t_ref:.1f} s")
    compared = compare.training(got, want, p["check"]["limits"])
    compared.append({"name": "compiles_in_window", "value": watch["compiles"],
                     "limit": 0})
    correct = compare.passes(compared) and failed == 0 and attempted > 0

    steps = len(losses)
    gaps = np.diff(stamps) * 1e3 if len(stamps) > 1 else np.array([])
    return {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "compared": compared,
        "end_to_end": {
            "train_tokens_per_s": steps * p["batch"] * p["seq"] / window_s,
            "setup_s": t_open - ctx["t_start"]},
        "trace": reduced,
        "counters": {
            "steps": steps, "tokens_per_step": p["batch"] * p["seq"],
            "feed": feed_counters, "step_gaps_ms": gaps.tolist(),
            "peak_bytes_in_use": peak}}
