"""Runner of a training cell whose plain reference does not fit
``reference/train.py::follow`` (float32 weights, accumulator and gradient
together): ``runners/train.py`` itself, to the byte, with the one call that
follows the reference sent to ``reference/train_lean.py::follow``. The timed
path, the first steps, the window, the counters, the trace and the comparison
are that file's own code, loaded here as a private module so that the other
cells' runner is left as it is.

One number is compared beside that file's: ``choice_gap``, where the cell's
file gives it a limit. A family whose queries choose the keys they attend
(``reference.layer_choices``) is held to the choice itself, since under
seeded random weights the norms of a gradient hardly tell which keys a query
took: the (query, block) pairs that the program and the plain reference
choose differently on the first batch, at the weights the seed gives, over
the pairs the reference chooses.
"""
from __future__ import annotations

import importlib.util
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_NAME = "_bench_runners_train_under_lean"


def _base():
    if _NAME not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            _NAME, os.path.join(_HERE, "train.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[_NAME] = mod
        spec.loader.exec_module(mod)
        mod.follow_reference = follow_reference
    return sys.modules[_NAME]


def __getattr__(name):
    """``build_program``, ``batch_fn``, ``drive``, ``first_steps``: the
    training runner's."""
    return getattr(_base(), name)


def follow_reference(bench, cell, seed: int, batches, math=None) -> dict:
    """The plain reference over the same first steps, from weights made anew
    from the seed, through the lean follower."""
    import jax
    import jax.numpy as jnp
    ref_train = bench.module("reference", "train")
    lean = bench.module("reference", "train_lean")
    numerics = bench.module("reference", "numerics")
    values, p = cell.config.values, cell.params
    reference = bench.module("reference", values["family"])
    chk = p["check"]
    dtype = jnp.dtype(values["dtype"])
    shapes = reference.param_shapes(values)

    def make_start():
        return ref_train.make_params(shapes, seed, dtype,
                                     values["initializer_range"])
    with jax.default_matmul_precision("highest"):
        return lean.follow(
            reference.token_losses, values, make_start,
            [batches(i) for i in range(chk["steps"])], lr=float(p["lr"]),
            weight_decay=float(p["weight_decay"]),
            math=math or numerics.Exact(), store_dtype=dtype)


def program_choices(bench, cell, seed: int, ids) -> dict:
    """layer index -> the blocks each query of ``ids`` chooses, [B, Hkv, S,
    blocks] bool: the program's own selection at the weights the seed gives,
    in a forward pass off the step's path."""
    import jax.numpy as jnp
    ref_train = bench.module("reference", "train")
    values = cell.config.values
    family = bench.module("families", values["family"])
    reference = bench.module("reference", values["family"])
    dtype = jnp.dtype(values["dtype"])
    model = family.build_model(values)
    if dtype == jnp.bfloat16:
        model = model.bfloat16()
    made = ref_train.make_params(reference.param_shapes(values), seed, dtype,
                                 values["initializer_range"])
    return model.chosen_blocks(
        ids, params={family.program_name(k): a for k, a in made.items()})


def reference_choices(bench, cell, seed: int, ids, math=None) -> dict:
    """The same of the plain reference, from weights made anew."""
    import jax
    import jax.numpy as jnp
    ref_train = bench.module("reference", "train")
    numerics = bench.module("reference", "numerics")
    values = cell.config.values
    reference = bench.module("reference", values["family"])
    made = ref_train.make_params(
        reference.param_shapes(values), seed, jnp.dtype(values["dtype"]),
        values["initializer_range"])
    math = math or numerics.Exact()
    with jax.default_matmul_precision("highest"):
        return jax.device_get(jax.jit(
            lambda p, x: reference.layer_choices(p, x, values, math))(
                made, jnp.asarray(ids)))


def choice_gap(got: dict, want: dict) -> float:
    """The (query, block) pairs chosen by one side alone, over the pairs
    ``want`` chose, all layers together."""
    import numpy as np
    if set(got) != set(want) or not want:
        return float("inf")
    alone = sum(int((np.asarray(got[i]) != np.asarray(want[i])).sum())
                for i in want)
    return alone / max(sum(int(np.asarray(a).sum())
                           for a in want.values()), 1)


def run(ctx: dict) -> dict:
    from benchmarks.harness import compare
    result = _base().run(ctx)
    bench, cell, seed = ctx["bench"], ctx["cell"], ctx["seed"]
    limits = cell.params["check"]["limits"]
    if "choice_gap" in limits:
        ids = _base().batch_fn(bench, cell, seed)(0)[0]
        row = {"name": "choice_gap", "limit": limits["choice_gap"],
               "value": choice_gap(
                   program_choices(bench, cell, seed, ids),
                   reference_choices(bench, cell, seed, ids))}
        result["compared"].append(row)
        result["correct"] = result["correct"] and compare.passes([row])
    return result
