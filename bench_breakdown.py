"""Step-time breakdown for the GPT-2 bench config (perf diagnosis tool).

Times each component of the jitted train step in isolation so the gap
between measured MFU and the 45% target can be attributed: full step,
fwd+bwd (no optimizer), fwd only, the LM-head+CE block, the encoder
stack, the embedding+final-norm shell, and the AdamW sweep. Prints one
JSON line. One process: the one that holds the chip; it exits non-zero
without a TPU and on any exception. These are host-clock differences of
separately jitted programs, not a device trace: ROADMAP S2 replaces them.
"""
from __future__ import annotations

import json
import time

import numpy as np


def timed(fn, *args, iters=10, windows=3):
    out = fn(*args)
    np.asarray(jax_device_get_scalar(out))
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        np.asarray(jax_device_get_scalar(out))
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1e3


def jax_device_get_scalar(out):
    import jax
    leaves = jax.tree_util.tree_leaves(out)
    # fetch one scalar reduced from the first leaf: closes the window
    return jax.device_get(leaves[0].sum() if leaves[0].ndim else leaves[0])


def main():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core import random as _random
    from paddle_tpu.core.autograd import tape_paused
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import GPTConfig, GPTForCausalLM, create_train_step
    from paddle_tpu.nn.layer.layers import _swapped_state, functional_state

    from bench import require_tpu
    dev = require_tpu()
    cfg = GPTConfig(vocab_size=50304, max_position_embeddings=1024,
                    hidden_size=768, num_layers=12, num_heads=12,
                    intermediate_size=3072, dropout=0.0)
    batch, seq = 8, 1024

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()
    opt = paddle.optimizer.AdamW(learning_rate=3e-4, weight_decay=0.01,
                                 parameters=model.parameters())
    step, params0, opt_state0 = create_train_step(model, opt)
    params0 = {k: (v.astype(jnp.bfloat16)
                   if jnp.issubdtype(v.dtype, jnp.floating) else v)
               for k, v in params0.items()}
    all0 = functional_state(model)
    trainable = functional_state(model, trainable_only=True)
    frozen = {k: v for k, v in all0.items() if k not in trainable}

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq + 1)),
                      jnp.int32)
    x, y = ids[:, :-1], ids[:, 1:]
    key = jax.random.key(0)

    def loss_of(params, ids_, labels_):
        with _random.key_context(key):
            with _swapped_state(model, {**params, **frozen}):
                with tape_paused():
                    return model.loss(Tensor(ids_), Tensor(labels_))._data

    def hidden_of(params, ids_):
        with _random.key_context(key):
            with _swapped_state(model, {**params, **frozen}):
                with tape_paused():
                    return model.gpt(Tensor(ids_))._data

    res = {}
    # 0. per-dispatch floor: every component number below carries it
    # additively, so DIFFERENCES between rows are floor-free; absolute
    # rows are (compute + floor)
    res["dispatch_floor_ms"] = timed(
        jax.jit(lambda p: p["gpt.ln_f.weight"].sum()), params0)

    # 1. full step
    res["full_step_ms"] = timed(
        lambda p, o: step(p, o, key, x, y, 3e-4), params0,
        jax.tree_util.tree_map(jnp.copy, opt_state0))

    # 2. fwd+bwd only
    vg = jax.jit(lambda p: jax.value_and_grad(
        lambda q: loss_of(q, x, y))(p))
    res["fwd_bwd_ms"] = timed(vg, params0)

    # 3. fwd only
    fwd = jax.jit(lambda p: loss_of(p, x, y))
    res["fwd_ms"] = timed(fwd, params0)

    # 4. hidden states only (encoder stack + embeddings, no LM head/CE)
    hid = jax.jit(lambda p: hidden_of(p, x))
    res["fwd_hidden_ms"] = timed(hid, params0)

    hid_g = jax.jit(lambda p: jax.grad(
        lambda q: hidden_of(q, x).astype(jnp.float32).sum())(p))
    res["fwd_bwd_hidden_ms"] = timed(hid_g, params0)

    # 5. LM head + CE block alone at [B*S, H] -> [B*S, V]
    h = jnp.asarray(rng.randn(batch * seq, cfg.hidden_size),
                    jnp.bfloat16) * 0.02
    w = params0["gpt.wte.weight"]
    labels_flat = y.reshape(-1)

    def ce_block(h_, w_):
        logits = jnp.matmul(h_, w_.T)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(lp, labels_flat[:, None], 1).mean()

    res["ce_block_fwd_ms"] = timed(jax.jit(ce_block), h, w)
    res["ce_block_fwd_bwd_ms"] = timed(
        jax.jit(lambda a, b: sum(
            g.astype(jnp.float32).sum()
            for g in jax.grad(ce_block, argnums=(0, 1))(a, b))), h, w)

    # fused / blockwise alternatives
    from paddle_tpu.ops.fused_ce import (blockwise_linear_cross_entropy,
                                         fused_linear_cross_entropy)
    res["ce_fused_fwd_bwd_ms"] = timed(
        jax.jit(lambda a, b: sum(
            g.astype(jnp.float32).sum()
            for g in jax.grad(lambda p, q: fused_linear_cross_entropy(
                p, q, labels_flat), argnums=(0, 1))(a, b))), h, w)
    res["ce_blockwise_fwd_bwd_ms"] = timed(
        jax.jit(lambda a, b: sum(
            g.astype(jnp.float32).sum()
            for g in jax.grad(lambda p, q: blockwise_linear_cross_entropy(
                p, q, labels_flat), argnums=(0, 1))(a, b))), h, w)

    # 6. optimizer sweep alone
    grads = {k: jnp.ones_like(v) * 1e-3 for k, v in params0.items()}
    opt_step = jax.jit(lambda p, g, s: opt.apply_gradients(p, g, s, 3e-4))
    res["adamw_sweep_ms"] = timed(
        lambda p, s: opt_step(p, grads, s), params0,
        jax.tree_util.tree_map(jnp.copy, opt_state0))

    # 7. what a chain of large bf16 matmuls reaches on this chip: 32 links
    # of 8192^3 inside one execute, best-of-3 -- the closest measurable
    # proxy for the peak the MFU denominator assumes.
    n, links = 8192, 32
    # magnitude-preserving chain: with all-(1/n) operands every link
    # maps a constant-(1/n) matrix to itself (row dot = n * 1/n * 1/n
    # = 1/n, exact in bf16 — powers of two), so the chain cannot
    # overflow and the synchronizing f32 sum stays finite at n
    a = jnp.full((n, n), 1.0 / n, jnp.bfloat16)
    bmat = jnp.full((n, n), 1.0 / n, jnp.bfloat16)

    @jax.jit
    def mm_chain(a, b):
        c = a
        for _ in range(links):
            c = c @ b
        return c.astype(jnp.float32).sum()

    float(jax.device_get(mm_chain(a, bmat)))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        # graft-lint: disable=GL503 -- timing: re-dispatching the
        # same chain and syncing on it IS the measurement
        float(jax.device_get(mm_chain(a, bmat)))
        best = min(best, time.perf_counter() - t0)
    res["measured_matmul_tflops"] = round(
        links * 2 * n ** 3 / best / 1e12, 1)
    del a, bmat

    res = {k: (round(v, 3) if isinstance(v, (int, float)) else v)
           for k, v in res.items()}
    res["derived"] = {
        "optimizer_overhead_ms": round(
            res["full_step_ms"] - res["fwd_bwd_ms"], 3),
        "bwd_ms": round(res["fwd_bwd_ms"] - res["fwd_ms"], 3),
        "ce_share_of_fwd_bwd_ms": res["ce_block_fwd_bwd_ms"],
        "encoder_share_fwd_bwd_ms": res["fwd_bwd_hidden_ms"],
    }
    print(json.dumps({"metric": "gpt2s_step_breakdown",
                      "platform": dev.platform, "device": str(dev),
                      "device_kind": dev.device_kind,
                      "device_count": jax.device_count(),
                      "captured_at_unix": time.time(),
                      "batch": batch, "seq": seq, **res}))


if __name__ == "__main__":
    main()
