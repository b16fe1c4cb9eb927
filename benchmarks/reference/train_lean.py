"""The plain training step of ``reference/train.py`` for a model too large to
hold float32 weights, a float32 gradient AND that file's float32 accumulator
on one chip: the gradient of the whole batch is taken in one piece (a cell
with one row a step has nothing to accumulate) and Adam's moments live in host
memory between steps. The arithmetic is ``reference/train.py``'s own: its
``_adamw_leaf``, norms and batches are imported, not copied. Nothing here
imports the program.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.train import (_adamw_leaf, change_norms,
                                        leaf_norms)


def _parking():
    """(park, fetch): an array to host memory and back. Pinned host memory
    where the device has it (a TPU: the copies are DMAs at the link's rate),
    else numpy."""
    dev = jax.devices()[0]
    kinds = {m.kind for m in dev.addressable_memories()}
    if "pinned_host" not in kinds or dev.platform == "cpu":
        return np.asarray, jnp.asarray
    from jax.sharding import SingleDeviceSharding
    host = SingleDeviceSharding(dev, memory_kind="pinned_host")
    here = SingleDeviceSharding(dev, memory_kind="device")
    return (lambda a: jax.device_put(a, host),
            lambda a: jax.device_put(a, here))


def follow(token_losses, values: dict, make_start, batches: list, *,
           lr: float, weight_decay: float, math,
           store_dtype=jnp.bfloat16) -> dict:
    """Train from ``make_start()`` (the weights from the seed, in the dtype
    they are stored in; called again at the end for the change) over
    ``batches`` and return what is compared: each step's loss, each leaf's
    gradient norm at the first step, each leaf's change over all the
    steps."""

    grad = jax.jit(jax.value_and_grad(lambda q, ids, labels: jnp.mean(
        token_losses(q, ids, labels, values, math))))
    park, fetch = _parking()

    # every step's weights pass through ``fetch``: one placement, so the
    # gradient is traced and compiled once and not again at step 2
    start = make_start()
    p = {}
    for k in list(start):
        p[k] = fetch(start.pop(k).astype(jnp.float32))
    del start
    m, v2 = {}, {}
    out = {"loss": [], "grad_norm": None, "change_norm": None}
    for t, (ids, labels) in enumerate(batches, start=1):
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        t0 = time.perf_counter()
        loss, grads = grad(p, ids, labels)
        if t == 1:
            out["grad_norm"] = leaf_norms(grads)
        out["loss"].append(float(loss))
        t1 = time.perf_counter()
        for k in list(p):
            mk = fetch(m.pop(k)) if k in m \
                else jnp.zeros(p[k].shape, jnp.float32)
            vk = fetch(v2.pop(k)) if k in v2 \
                else jnp.zeros(p[k].shape, jnp.float32)
            p[k], mk, vk = _adamw_leaf(
                p[k], grads.pop(k), mk, vk, float(t), lr, weight_decay,
                decay=p[k].ndim >= 2, dtype=jnp.dtype(store_dtype))
            p[k] = fetch(p[k])
            if t < len(batches):
                m[k], v2[k] = park(mk), park(vk)
            del mk, vk
        jax.block_until_ready(p)
        print(f"reference step {t}: gradient {t1 - t0:.1f} s, update "
              f"{time.perf_counter() - t1:.1f} s", file=sys.stderr,
              flush=True)
    out["change_norm"] = change_norms(p, make_start())
    return out
