"""The plain training step every family's reference is driven through: loss,
gradients and AdamW, written from the published descriptions (Loshchilov &
Hutter 2019, "Decoupled weight decay regularization", algorithm 2).

What the cells state and this follows: parameters are STORED in the
configuration's dtype (bfloat16) and every update is computed in float32 from
the stored value and rounded back to it; the moments are float32; weight decay
applies to tensors of two or more dimensions and not to biases or norm gains;
the loss is the mean over all tokens of the batch. The gradient of a batch is
taken in blocks of rows and summed, so that the reference fits beside nothing
else on one chip. Nothing here imports the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BETA1, BETA2, EPS = 0.9, 0.999, 1e-8


def make_params(shapes: dict, seed: int, dtype, init_range: float) -> dict:
    """Every leaf from ``seed`` in one jitted call, on the device, in the
    dtype it is trained in. ``shapes``: name -> (shape, kind)."""
    names = list(shapes)

    def build(key):
        out = {}
        for i, n in enumerate(names):
            shape, kind = shapes[n]
            if kind == "normal":
                out[n] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                            jnp.float32)
                          * init_range).astype(dtype)
            elif kind == "ones":
                out[n] = jnp.ones(shape, dtype)
            else:
                out[n] = jnp.zeros(shape, dtype)
        return out
    return jax.jit(build)(seed_key(seed))


def seed_key(seed: int):
    """A key from any whole number: the low 31 bits seed it, the rest fold in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_batch(seed: int, step: int, batch: int, seq: int, vocab: int):
    """The batch of step ``step``: uniform ids below ``vocab``, all rows
    different, the labels the ids shifted by one. numpy, on the host."""
    rng = np.random.default_rng([int(seed), int(step)])
    ids = rng.integers(0, vocab, size=(batch, seq + 1), dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]


def leaf_norms(tree: dict) -> dict:
    return {k: float(v) for k, v in jax.device_get(_norms(tree)).items()}


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def _diff_norms(a, b):
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k].astype(jnp.float32) - b[k].astype(jnp.float32)))) for k in a}


def change_norms(after: dict, before: dict) -> dict:
    return {k: float(v)
            for k, v in jax.device_get(_diff_norms(after, before)).items()}


@functools.partial(jax.jit, static_argnames=("decay", "dtype"),
                   donate_argnums=(0, 2, 3))
def _adamw_leaf(p, g, m, v, t, lr, wd, *, decay, dtype):
    m = BETA1 * m + (1 - BETA1) * g
    v = BETA2 * v + (1 - BETA2) * jnp.square(g)
    mhat = m / (1 - BETA1 ** t)
    vhat = v / (1 - BETA2 ** t)
    if decay:
        p = p * (1.0 - lr * wd)
    p = p - lr * mhat / (jnp.sqrt(vhat) + EPS)
    # stored in the configuration's dtype; held as float32 of that value.
    # reduce_precision, not astype there and back: XLA removes that pair
    info = jnp.finfo(dtype)
    if info.bits < 32:
        p = jax.lax.reduce_precision(p, info.nexp, info.nmant)
    return p, m, v


def follow(token_losses, values: dict, params0: dict, batches: list, *,
           lr: float, weight_decay: float, math, row_block: int,
           store_dtype=jnp.bfloat16, moments_on_host: bool = False,
           leave_out_rows: int = 0) -> dict:
    """Train from ``params0`` over ``batches`` (a list of (ids, labels)) and
    return what is compared: each step's loss, each leaf's gradient norm at
    the first step, each leaf's change over all the steps.

    ``moments_on_host`` parks Adam's moments in host memory between steps,
    for a model whose float32 parameters, gradients and moments do not fit
    the chip together. ``leave_out_rows`` plants a fault for the benchmark's
    tests: that many of a batch's last rows are left out and the mean is
    taken over the rest."""

    def block_grad(p, ids, labels, denom, acc):
        loss, g = jax.value_and_grad(
            lambda q: jnp.sum(token_losses(q, ids, labels, values, math))
            / denom)(p)
        return loss, jax.tree_util.tree_map(jnp.add, acc, g)

    block_grad = jax.jit(block_grad, donate_argnums=(4,))

    p = {k: v.astype(jnp.float32) for k, v in params0.items()}
    start = {k: v.astype(store_dtype) for k, v in params0.items()}
    m, v2 = {}, {}
    out = {"loss": [], "grad_norm": None, "change_norm": None}
    last = len(batches)
    for t, (ids, labels) in enumerate(batches, start=1):
        if leave_out_rows:
            ids, labels = ids[:-leave_out_rows], labels[:-leave_out_rows]
        rows, seq = ids.shape
        denom = float(rows * seq)
        loss = 0.0
        grads = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
        for r in range(0, rows, row_block):
            lo, grads = block_grad(p, jnp.asarray(ids[r:r + row_block]),
                                   jnp.asarray(labels[r:r + row_block]),
                                   denom, grads)
            loss += float(lo)
        out["loss"].append(loss)
        if t == 1:
            out["grad_norm"] = leaf_norms(grads)
        for k in list(p):
            mk = jnp.asarray(m.pop(k)) if k in m \
                else jnp.zeros(p[k].shape, jnp.float32)
            vk = jnp.asarray(v2.pop(k)) if k in v2 \
                else jnp.zeros(p[k].shape, jnp.float32)
            p[k], mk, vk = _adamw_leaf(
                p[k], grads.pop(k), mk, vk, float(t), lr, weight_decay,
                decay=p[k].ndim >= 2, dtype=jnp.dtype(store_dtype))
            if t < last:
                m[k] = np.asarray(mk) if moments_on_host else mk
                v2[k] = np.asarray(vk) if moments_on_host else vk
            del mk, vk
    out["change_norm"] = change_norms(p, start)
    return out
