"""Per-direction (fwd+bwd) measured impl selection for fused ops.

A hand-written kernel whose backward loses to XLA must never ship: an
earlier on-chip capture showed the Pallas CE/norm backwards and the GQA
flash backward losing to XLA's autodiff even where the forward wins (not
re-measured on a whole chip under the installed jax — ROADMAP S2). The
reference gates this class
of regression with kernel autotuning (paddle/phi/kernels/autotune/) and CI
thresholds (tools/ci_op_benchmark.sh); here every fused op routes through a
(op, shape)-keyed choice whose *measurement includes the vjp*:

- FLAGS_use_autotune + concrete operands: measure each variant fwd+vjp on
  the live device, cache the winner (core/autotune.py, persisted to the
  git-ignored artifacts/autotune_tpu.json by bench_kernels.py).
- traced calls (jit / inside the tape's deferred jax.vjp): consult-only.
- no cache entry: the measured-on-v5e default heuristic rules.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["pick_grad_impl", "vjp_probe"]


def vjp_probe(fn, args, diff_argnums):
    """Run ``fn(*args)`` forward + vjp (cotangent = ones) and fetch ONE
    element of every grad to the host, so a timed window really includes
    the backward kernels: dispatch is asynchronous, and bytes that
    data-depend on each grad cannot arrive before it is computed.
    Returns the forward output."""
    diff = tuple(args[i] for i in diff_argnums)

    def f(*d):
        full = list(args)
        for i, v in zip(diff_argnums, d):
            full[i] = v
        return fn(*full)

    out, vjp = jax.vjp(f, *diff)
    grads = vjp(jnp.ones_like(out))
    for gr in grads:
        jax.device_get(gr.ravel()[0])
    return out


def pick_grad_impl(tag, variants, args, default, diff_argnums=(0,),
                   key_arrays=None, class_key=None):
    """Return ``(choice, out)`` where ``choice`` is a key of ``variants``
    and ``out`` is the already-computed forward output when the measurement
    just ran the winner (eager cache miss), else None.

    ``variants``: name -> callable(*args) returning one array.
    ``default``: heuristic choice when autotune is off / cache is cold.
    ``diff_argnums``: which args the measured vjp differentiates — the
    measurement must include every backward kernel the training step runs.
    ``class_key``: shape-class key into the measured-defaults table
    (core/autotune.py) — a traced cold-cache call takes the class winner
    from a prior capture before degrading to ``default``.
    """
    from ...core import autotune as _at

    def call(name):
        return vjp_probe(variants[name], args, diff_argnums)

    choice, out = _at.pick_impl(tag, variants, args, call,
                                key_arrays=key_arrays,
                                class_key=class_key)
    if choice is None or choice not in variants:
        return default, None
    return choice, out
