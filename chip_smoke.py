"""chip_smoke.py -- does the main path still start on the chip?

    python3 chip_smoke.py        # needs a TPU; exits non-zero without one

One process, the entry points a user calls, published widths (depth may be
cut), random weights from a seed. Legs, each checked by the repo's own
means, any failure making the exit code non-zero:

  kernels    every Pallas family compiled by Mosaic (interpret=False on the
             chip), forward and backward, against its XLA twin at the
             shapes the main path uses
  laguna     Laguna-XS.2 as laguna-xs2-train-s8192 runs it (5 layers, 32 of
             256 experts held, batch 2 x 8192) through create_train_step
             driven by run_steps: the windowed flash kernels and the
             grouped matmuls are in the lowered step, three steps on one
             batch give a finite falling loss, and routing_stats of that
             batch is printed
  glm        GLM-4.7-Flash as glm47-flash-train-s8192 runs it (5 layers and
             the MTP module, latent attention at a head of 256, 8 of 64
             bias-corrected experts held, batch 2 x 8192): the flash
             kernels and the grouped matmuls are in the lowered step, the
             route and tile plan of its attention are printed, three
             steps give a finite falling loss
  sala       MiniCPM-SALA as minicpm-sala-train-s12288 runs it (one period:
             a block-sparse layer and three lightning layers at 9B widths,
             an eighth of the vocabulary, batch 1 x 12288, the layer body
             recomputed): the step is lowered and compiled at that size,
             both mixers' plans, the kernel names in the lowered text and
             the compiler's memory count are printed, and three steps
             give a finite falling loss
  train      GPT-2 small, seq 1024, batch 8, bf16 params, through
             create_train_step(donate=True) driven by run_steps: loss
             finite and falling, no compile after the first step, the
             compiled step contains the Mosaic flash-attention kernel
  serve      serving.decode.DecodeServer over the same model: warmup, a
             few in-flight requests of mixed prompt lengths, greedy; no
             compile after warmup, KV pools donated, ids agree with a
             full-context forward of the same model
  four_chip  (>= 4 devices) Llama-7B widths cut to 2 layers on a
             (dp=2, tp=2) mesh, Megatron TP then TP+FSDP, against a
             single-device step of the same model on the same batch

Any time printed here is set-up or smoke wall time, never a metric.
The last stdout line of a passing run is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.

tests/test_chip_smoke.py runs the same legs at the TINY preset on the CPU
mesh (kernels in interpret mode).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import json
import re
import sys
import time
import traceback

import numpy as np

# -- tolerances, each with its reason ---------------------------------------

# Pallas kernel vs XLA twin, both fed the same bf16 inputs, compared as
# max|a-b| / max|b|. bf16 keeps 8 mantissa bits (eps = 2^-8 = 3.9e-3); the
# two sides round at different points (the kernels accumulate in f32 and
# round once, XLA rounds the probabilities before the second matmul), so a
# few eps is the honest distance. A wrong mask, scale or index map moves
# the result by O(1).
KERNEL_FWD_TOL = 2e-2
KERNEL_BWD_TOL = 4e-2

# Served ids vs a full-context forward of the same model. Random weights
# make argmax brittle: the top-2 logits are often closer than the bf16
# rounding of either path (bf16 ulp is 2^-6 = 0.016 for |logit| in [2, 4),
# and the decode path attends in f32 over an f32 cache while the full
# forward attends in bf16). So a served token passes when it IS the
# reference argmax, or when the reference scores it within SERVE_MARGIN_TOL
# of its own argmax (a near-tie). Teacher forcing -- the reference runs on
# prompt + served ids -- keeps positions independent, so one near-tie
# cannot cascade. At least half the positions must match exactly, so the
# near-tie rule cannot carry the check alone.
SERVE_MARGIN_TOL = 0.0625
SERVE_MIN_EXACT = 0.5

# Sharded step vs single-device step, same bf16 model, same batch: the
# sharded matmuls sum partial products in another order, so logits differ
# by O(bf16 eps) relative and the token-mean loss by less. Compared on the
# first TWO steps: at random init every layout scores about ln(V), only
# the second loss has been through the gradients of every shard.
FOUR_CHIP_LOSS_RTOL = 1e-2
# per-device bytes in use must agree within this share of the largest
MEMORY_BALANCE_TOL = 0.10

# the XLA twin keeps B*H*S*S float32 scores and probabilities for its
# backward; over this many bytes of scores it goes through the batch a row
# at a time
XLA_TWIN_SCORE_BYTES = 2 << 30

# -- presets ----------------------------------------------------------------
# flash cases: (name, batch, seq, q heads, kv heads, head_dim, dropout),
# bf16 throughout; an eighth entry gives q and k another dtype, a ninth a
# window

CHIP = {
    "kernels": {
        "flash": [
            ("fa_gpt2_b8_s1024_h12_d64", 8, 1024, 12, 12, 64, 0.0),
            ("fa_gpt2_b8_s1024_h12_d64_dropout", 8, 1024, 12, 12, 64, 0.1),
            ("fa_llama_mha_b2_s2048_h32_d128", 2, 2048, 32, 32, 128, 0.0),
            ("fa_llama_gqa_b1_s4096_h32kv8_d128", 1, 4096, 32, 8, 128, 0.0),
            # the benchmark cells' own attention shapes (BENCHMARK.json):
            # gpt2s-train-s1024, and mistral7b-l2-train-s4096, whose q and
            # k arrive float32 from RoPE's float32 tables
            ("fa_cell_gpt2s_b32_s1024_h12_d64", 32, 1024, 12, 12, 64, 0.0),
            ("fa_cell_mistral_b4_s4096_h32kv8_d128_f32qk", 4, 4096, 32, 8,
             128, 0.0, "float32"),
            # laguna-xs2-train-s8192: a window layer (flash_win_*) and a
            # full layer
            ("fa_cell_laguna_b2_s8192_h64kv8_d128_w512", 2, 8192, 64, 8,
             128, 0.0, "bfloat16", 512),
            ("fa_cell_laguna_b2_s8192_h48kv8_d128", 2, 8192, 48, 8, 128,
             0.0),
        ],
        "norm_rows": 8192, "norm_cols": (768, 4096),
        "ce": [(8192, 50304), (8192, 32000)],
    },
    "laguna": {"tiny": False, "batch": 2, "seq": 8192, "steps": 3,
               "lr": 3e-4},
    "glm": {"tiny": False, "batch": 2, "seq": 8192, "steps": 3, "lr": 3e-4},
    "sala": {"tiny": False, "batch": 1, "seq": 12288, "steps": 3,
             "lr": 3e-4},
    "train": {"model": "gpt2_small", "batch": 8, "seq": 1024, "steps": 8,
              "lr": 3e-4},
    "serve": {"model": "gpt2_small", "max_slots": 4, "page_len": 128,
              "max_context": 1024, "batch_buckets": [4],
              "prefill_buckets": [64, 256, 1024],
              # (prompt length, new tokens): five requests over four slots,
              # so one waits for an eviction; the long one fills the
              # largest page bucket
              "requests": [(7, 24), (33, 16), (100, 24), (250, 16),
                           (900, 24)]},
    "four_chip": {"model": "llama_7b", "num_layers": 2, "batch": 4,
                  "seq": 1024, "lr": 1e-3},
}

TINY = {
    "kernels": {
        "flash": [
            ("fa_mha_tiny", 1, 256, 2, 2, 64, 0.0),
            ("fa_mha_tiny_dropout", 1, 128, 2, 2, 64, 0.1),
            ("fa_gqa_tiny", 1, 128, 4, 2, 128, 0.0),
            ("fa_gqa_window_tiny", 1, 256, 6, 2, 64, 0.0, "bfloat16", 100),
        ],
        "norm_rows": 16, "norm_cols": (128, 256),
        "ce": [(16, 512), (16, 384)],
    },
    "laguna": {"tiny": True, "batch": 2, "seq": 32, "steps": 3, "lr": 1e-2},
    "glm": {"tiny": True, "batch": 2, "seq": 32, "steps": 3, "lr": 1e-2},
    # 64 tokens: past the tiny preset's dense_len of 32
    "sala": {"tiny": True, "batch": 2, "seq": 64, "steps": 3, "lr": 1e-2},
    "train": {"model": "gpt2_tiny", "batch": 2, "seq": 128, "steps": 4,
              "lr": 1e-2},
    "serve": {"model": "gpt2_tiny", "max_slots": 4, "page_len": 16,
              "max_context": 128, "batch_buckets": [4],
              "prefill_buckets": [16, 64],
              "requests": [(3, 8), (10, 6), (20, 8), (40, 6), (7, 8)]},
    "four_chip": {"model": "llama_tiny", "num_layers": 1, "batch": 4,
                  "seq": 32, "lr": 1e-2},
}

SEED = 0


# -- shared helpers ---------------------------------------------------------

def on_chip() -> bool:
    import jax
    return jax.default_backend() == "tpu"


@contextlib.contextmanager
def compile_watch():
    """Count, for the duration of the block, the compile requests jax sends
    to the backend (every trace that reaches XLA, persistent-cache hit or
    not) and the persistent cache's hits and misses."""
    import jax
    counts = {"compiles": 0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event, duration_secs, **kw):
        del duration_secs, kw
        if event == "/jax/core/compile/backend_compile_duration":
            counts["compiles"] += 1

    def on_event(event, **kw):
        del kw
        if event == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield counts
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise AssertionError(f"shape {got.shape} != {want.shape}")
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def _build_gpt(name):
    """GPT-2 at the named width with bf16 params, dropout off (one repeated
    batch must fall monotonically; the kernels leg covers in-kernel
    dropout)."""
    import paddle_tpu as paddle
    from paddle_tpu import models
    paddle.seed(SEED)
    cfg = dataclasses.replace(getattr(models, name)(), dropout=0.0)
    return models.GPTForCausalLM(cfg).bfloat16(), cfg


# -- leg: kernels -----------------------------------------------------------

def kernel_cases(p, interpret):
    """(name, pallas_fn, xla_twin, args, n_diff) for every kernel case of
    the preset: the first ``n_diff`` args are differentiated."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.flash_attention import _attention_xla
    from paddle_tpu.nn.functional.loss import _softmax_xent_core_xla
    from paddle_tpu.nn.functional.norm import _layer_norm_xla, _rms_norm_xla
    from paddle_tpu.ops.pallas.cross_entropy import softmax_xent_pallas
    from paddle_tpu.ops.pallas.flash_attention import (flash_attention_ext,
                                                       seed_from_key)
    from paddle_tpu.ops.pallas.norms import (layer_norm_pallas,
                                             rms_norm_pallas)

    rng = np.random.RandomState(SEED)
    bf16 = jnp.bfloat16

    for name, b, s, hq, hk, d, rate, *more in p["flash"]:
        qk_dtype = jnp.dtype(more[0]) if more else bf16
        window = more[1] if len(more) > 1 else None
        q = jnp.asarray(rng.randn(b, s, hq, d) * 0.5, qk_dtype)
        k = jnp.asarray(rng.randn(b, s, hk, d) * 0.5, qk_dtype)
        v = jnp.asarray(rng.randn(b, s, hk, d) * 0.5, bf16)
        scale = float(d) ** -0.5
        key = jax.random.key(SEED)
        seed = seed_from_key(key)

        # the XLA twin draws the same (seed, position)-hashed mask
        def twin(q, k, v, _r=rate, _s=scale, _key=key, _w=window):
            return _attention_xla(q, k, v, None, True, _s, _r,
                                  _key if _r > 0.0 else None, _w)

        if rate == 0.0 and hq * s * s * 4 > XLA_TWIN_SCORE_BYTES:
            # nor does one row's S x S scores of every head fit: a head
            # at a time
            twin = functools.partial(_by_head, twin)
        elif rate == 0.0 and b * hq * s * s * 4 > XLA_TWIN_SCORE_BYTES:
            # attention is independent across the batch: the twin takes
            # one row at a time, so its S x S scores fit beside the kernel
            twin = functools.partial(_by_batch_row, twin)
        yield (name,
               # no blocks given: the tiles are the kernels' own plan, as
               # on the training path
               lambda q, k, v, _r=rate, _s=scale, _seed=seed, _w=window:
               flash_attention_ext(q, k, v, None, _seed, None, None, True,
                                   _s, _r, None, None, interpret, _w),
               twin, (q, k, v), 3)

    rows = p["norm_rows"]
    for n in p["norm_cols"]:
        x = jnp.asarray(rng.randn(rows, n), bf16)
        w = jnp.asarray(1.0 + 0.1 * rng.randn(n), bf16)
        bias = jnp.asarray(0.1 * rng.randn(n), bf16)
        yield (f"rms_norm_{rows}x{n}",
               lambda x, w: rms_norm_pallas(x, w, 1e-5, interpret),
               lambda x, w: _rms_norm_xla(x, w, 1e-5), (x, w), 2)
        yield (f"layer_norm_{rows}x{n}",
               lambda x, w, b: layer_norm_pallas(x, w, b, 1e-5, interpret),
               lambda x, w, b: _layer_norm_xla(x, w, b, 1e-5, 1),
               (x, w, bias), 3)

    for r, vocab in p["ce"]:
        logits = jnp.asarray(rng.randn(r, vocab) * 2.0, bf16)
        labels = jnp.asarray(rng.randint(0, vocab, (r,)), jnp.int32)
        for bwd in ("xla", "pallas"):   # both backwards dispatch can pick
            yield (f"softmax_ce_{r}x{vocab}_bwd_{bwd}",
                   lambda lg, lb, _b=bwd: softmax_xent_pallas(
                       lg, lb, interpret, _b),
                   _softmax_xent_core_xla, (logits, labels), 1)


def _by_batch_row(fn, q, k, v):
    import jax
    return jax.lax.map(lambda r: fn(r[0][None], r[1][None], r[2][None])[0],
                       (q, k, v))


def _by_head(fn, q, k, v):
    """``fn`` one (row, query head) at a time, each with its kv head, and
    nothing kept between them for the backward pass but the inputs."""
    import jax
    import jax.numpy as jnp
    b, s, hq, d = q.shape
    rep = hq // k.shape[2]

    def heads(x, times):
        return jnp.repeat(x.transpose(0, 2, 1, 3), times, axis=1).reshape(
            b * hq, s, d)

    def one(r):
        qh, kh, vh = (x[None, :, None] for x in r)
        return fn(qh, kh, vh)[0, :, 0]
    out = jax.lax.map(jax.checkpoint(one),
                      (heads(q, 1), heads(k, rep), heads(v, rep)))
    return out.reshape(b, hq, s, d).transpose(0, 2, 1, 3)


def fwd_and_vjp(fn, n_diff):
    """jit of (out, grads of the first n_diff args) under one fixed,
    non-constant cotangent."""
    import jax
    import jax.numpy as jnp

    def run(*a):
        out, vjp = jax.vjp(lambda *d: fn(*d, *a[n_diff:]), *a[:n_diff])
        ct = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)
                     ).reshape(out.shape).astype(out.dtype)
        return (out,) + tuple(vjp(ct))
    return jax.jit(run)


def leg_kernels(p) -> dict:
    import jax

    interpret = not on_chip()   # Mosaic on the chip, the interpreter here
    errors, failures = {}, []
    for name, pallas_fn, xla_fn, args, n_diff in kernel_cases(p, interpret):
        try:
            got = jax.device_get(fwd_and_vjp(pallas_fn, n_diff)(*args))
            want = jax.device_get(fwd_and_vjp(xla_fn, n_diff)(*args))
            fwd = _rel_err(got[0], want[0])
            bwd = max(_rel_err(g, w) for g, w in zip(got[1:], want[1:]))
            errors[name] = {"fwd": round(fwd, 5), "bwd": round(bwd, 5)}
            if not (fwd <= KERNEL_FWD_TOL and bwd <= KERNEL_BWD_TOL):
                failures.append(f"{name}: fwd {fwd:.4g} (tol "
                                f"{KERNEL_FWD_TOL}) bwd {bwd:.4g} (tol "
                                f"{KERNEL_BWD_TOL})")
        except Exception as e:  # noqa: BLE001 -- every case is attempted,
            # then the leg raises with all of them (a chip call is too
            # dear to stop at the first refusal)
            traceback.print_exc()
            failures.append(f"{name}: {type(e).__name__}: {str(e)[:300]}")
    if failures:
        raise AssertionError("kernels: " + "; ".join(failures))
    return {"interpret": interpret, "cases": len(errors),
            "rel_err": errors}


# -- leg: laguna ------------------------------------------------------------

def _moe_leg(name, model, cfg, p, kernels) -> dict:
    """Three steps of a decoder with DroplessMoE layers through the trainer
    on one repeated batch: ``routing_stats`` of the batch, the ``kernels``
    named in the lowered step (on the chip), no compile after the first
    step, a finite falling loss."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import create_train_step, run_steps

    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=p["lr"], weight_decay=0.01,
                                 parameters=model.parameters())
    rng = np.random.RandomState(SEED)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                  (p["batch"], p["seq"] + 1)), jnp.int32)
    x, y = ids[:, :-1], ids[:, 1:]
    # off the step's path, and before the step consumes the weights
    routing = model.routing_stats(x)
    if len(routing) != len(model.sparse_layers()) or any(
            r["assignments_here"] < 1 for r in routing):
        raise AssertionError(f"{name}: routing_stats {routing}")
    step, params, opt_state = create_train_step(model, opt,
                                                donate="consume")
    key = jax.random.key(SEED)
    text = step.lower(params, opt_state, key, x, y, p["lr"]).as_text()
    names = {n: text.count(n) for n in kernels}
    if on_chip() and not all(names.values()):
        raise AssertionError(f"{name}: kernels missing from the lowered "
                             f"step: {names}")
    params, opt_state, first = run_steps(
        step, params, opt_state, [(x, y)], key=key, lr=p["lr"])
    with compile_watch() as watch:
        params, opt_state, rest = run_steps(
            step, params, opt_state, [(x, y)] * (p["steps"] - 1), key=key,
            lr=p["lr"], start_step=1)
    losses = [float(v) for v in first + rest]
    if watch["compiles"]:
        raise AssertionError(f"{name}: {watch['compiles']} compile(s) "
                             "after the first step")
    if not (np.isfinite(losses).all() and losses[-1] < losses[0]
            and max(losses[1:]) < losses[0]):
        raise AssertionError(f"{name}: loss not finite and falling on one "
                             f"repeated batch: {losses}")
    return {"losses": [round(v, 4) for v in losses],
            "kernels_in_step": names, "routing_stats": routing,
            "mosaic_calls_in_step": text.count("tpu_custom_call"),
            # what a recomputed block keeps, and the calls of the flash
            # forward kernel's jitted ``_fwd`` that the lowered step makes:
            # one a layer under "flash_saveable", two under "full"
            "recompute_policy": cfg.recompute_policy,
            "flash_fwd_calls_in_step": len(
                re.findall(r"call @_fwd(?:_\d+)?\(", text))}


def leg_laguna(p) -> dict:
    """The decoder of laguna-xs2-train-s8192 (BENCHMARK.json) through the
    trainer: published widths, 5 layers of the pattern, experts 0-31 of 256
    and an eighth of the vocabulary here, the layer body recomputed."""
    import paddle_tpu as paddle
    from paddle_tpu.models import (LagunaConfig, LagunaForCausalLM,
                                   laguna_tiny)

    paddle.seed(SEED)
    if p["tiny"]:
        cfg = laguna_tiny(experts_held=(4, 4), use_recompute=True)
    else:
        cfg = LagunaConfig(
            vocab_size=12544, layer_types=LagunaConfig.layer_types[:5],
            mlp_layer_types=LagunaConfig.mlp_layer_types[:5],
            num_heads_per_layer=LagunaConfig.num_heads_per_layer[:5],
            experts_held=(0, 32), use_recompute=True)
    return _moe_leg("laguna", LagunaForCausalLM(cfg).bfloat16(), cfg, p,
                    ("flash_win_fwd", "flash_win_bwd_dq",
                     "flash_win_bwd_dkv", "moe_gmm_fwd", "moe_gmm_bwd_x",
                     "moe_gmm_bwd_w"))


def leg_glm(p) -> dict:
    """The decoder of glm47-flash-train-s8192 (BENCHMARK.json) through the
    trainer: published widths, the dense layer, 4 sparse ones and the MTP
    module, experts 0-7 of 64 and an eighth of the vocabulary here, the
    layer body recomputed; the ``mla::plan`` of its attention is printed
    (route, rule, tiles)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import (GlmMoeLiteConfig, GlmMoeLiteForCausalLM,
                                   glm_moe_lite_tiny)
    from paddle_tpu.models.glm_moe_lite import MLA_PLAN_TALLY
    from paddle_tpu.profiler import tracing

    paddle.seed(SEED)
    if p["tiny"]:
        cfg = glm_moe_lite_tiny(experts_held=(4, 4), use_recompute=True)
    else:
        cfg = GlmMoeLiteConfig(vocab_size=19360, num_hidden_layers=5,
                               experts_held=(0, 8), use_recompute=True)
    model = GlmMoeLiteForCausalLM(cfg).bfloat16()
    before = sum(MLA_PLAN_TALLY.values())
    tracing.reset_tracing()
    # a whole step's events: a smaller ring left by an earlier caller
    # would keep only the last few
    tracing.enable_tracing(ring_size=tracing.DEFAULT_RING_SIZE)
    try:
        out = _moe_leg("glm", model, cfg, p,
                       ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                        "moe_gmm_fwd", "moe_gmm_bwd_x", "moe_gmm_bwd_w"))
        plans = [e["args"] for e in tracing.snapshot_events()
                 if e["name"] == "mla::plan"]
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    layers = cfg.num_hidden_layers + cfg.num_nextn_predict_layers
    lowered = sum(MLA_PLAN_TALLY.values()) - before
    if not plans or lowered % layers or (
            on_chip() and plans[-1]["route"] != "kernel"):
        raise AssertionError(f"glm: {lowered} attention layers planned for "
                             f"steps of {layers}, last plan "
                             f"{plans[-1] if plans else None}")
    out["mla_plan"] = {k: plans[-1][k] for k in ("route", "rule", "tiles")}
    return out


# -- leg: sala --------------------------------------------------------------

SALA_KERNELS = ("sparse_attn_fwd", "sparse_attn_bwd_dq", "sparse_attn_bwd_dkv",
                "linear_attn_fwd", "linear_attn_bwd")


def leg_sala(p) -> dict:
    """The decoder of minicpm-sala-train-s12288 (BENCHMARK.json) through the
    trainer: published widths, one period of the mixers, an eighth of the
    vocabulary, the layer body recomputed. The step is lowered and compiled
    once and that program runs the steps."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import (MiniCPMSALAConfig, MiniCPMSALAForCausalLM,
                                   create_train_step, minicpm_sala_tiny,
                                   run_steps)
    from paddle_tpu.profiler import tracing

    paddle.seed(SEED)
    if p["tiny"]:
        cfg = minicpm_sala_tiny(use_recompute=True)
    else:
        cfg = MiniCPMSALAConfig(
            vocab_size=9216, mixer_types=MiniCPMSALAConfig.mixer_types[:4],
            use_recompute=True)
    model = MiniCPMSALAForCausalLM(cfg).bfloat16()
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=p["lr"], weight_decay=0.01,
                                 parameters=model.parameters())
    rng = np.random.RandomState(SEED)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                  (p["batch"], p["seq"] + 1)), jnp.int32)
    x, y = ids[:, :-1], ids[:, 1:]
    step, params, opt_state = create_train_step(model, opt,
                                                donate="consume")
    key = jax.random.key(SEED)
    tracing.reset_tracing()
    tracing.enable_tracing(ring_size=tracing.DEFAULT_RING_SIZE)
    try:
        lowered = step.lower(params, opt_state, key, x, y, p["lr"])
        events = tracing.snapshot_events()
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    plans = {name: [e["args"] for e in events if e["name"] == name]
             for name in ("sparse_attn::plan", "linear_attn::plan",
                          "recompute::plan")}
    sparse_layers = sum(k == "minicpm4" for k in cfg.mixer_types)
    if len(plans["sparse_attn::plan"]) < sparse_layers or \
            len(plans["linear_attn::plan"]) < cfg.num_layers - sparse_layers:
        raise AssertionError(f"sala: plans {plans}")
    text = lowered.as_text()
    names = {n: text.count(n) for n in SALA_KERNELS}
    if on_chip() and not all(names.values()):
        raise AssertionError(f"sala: kernels missing from the lowered "
                             f"step: {names}")
    with compile_watch() as first:
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    memory = {k: getattr(mem, k, None) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes")} if mem else {}
    with compile_watch() as watch:
        params, opt_state, losses = run_steps(
            compiled, params, opt_state, [(x, y)] * p["steps"], key=key,
            lr=p["lr"])
    losses = [float(v) for v in losses]
    if watch["compiles"]:
        raise AssertionError(f"sala: {watch['compiles']} compile(s) after "
                             "the step was compiled")
    if not (np.isfinite(losses).all() and losses[-1] < losses[0]
            and max(losses[1:]) < losses[0]):
        raise AssertionError(f"sala: loss not finite and falling on one "
                             f"repeated batch: {losses}")
    return {"losses": [round(v, 4) for v in losses],
            "kernels_in_step": names,
            "mosaic_calls_in_step": text.count("tpu_custom_call"),
            "sparse_plan": plans["sparse_attn::plan"][-1],
            "linear_plan": plans["linear_attn::plan"][-1],
            "recompute_plan": plans["recompute::plan"][0],
            "recompute_policy": cfg.recompute_policy,
            "compiler_memory": memory,
            "compile_requests": first["compiles"]}


# -- leg: train -------------------------------------------------------------

def leg_train(p) -> dict:
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import device
    from paddle_tpu.models import create_train_step, run_steps

    model, cfg = _build_gpt(p["model"])
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=p["lr"], weight_decay=0.01,
                                 parameters=model.parameters())
    step, params, opt_state = create_train_step(model, opt, donate=True)

    rng = np.random.RandomState(SEED)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                  (p["batch"], p["seq"] + 1)), jnp.int32)
    x, y = ids[:, :-1], ids[:, 1:]
    key = jax.random.key(SEED)

    # the route check: lower the step the runner is about to compile and
    # count the Mosaic custom calls in it. At this shape flash attention
    # is the default route; a silent reroute to XLA must fail the leg.
    mosaic_calls = step.lower(params, opt_state, key, x, y,
                              p["lr"]).as_text().count("tpu_custom_call")
    if on_chip() and mosaic_calls < 3 * cfg.num_layers:
        raise AssertionError(
            f"train: the lowered step holds {mosaic_calls} Mosaic calls, "
            f"expected >= {3 * cfg.num_layers} (flash fwd + dq + dkv per "
            "layer): attention was rerouted")

    # first step compiles; the rest must not
    params, opt_state, first = run_steps(
        step, params, opt_state, [(x, y)], key=key, lr=p["lr"])
    with compile_watch() as watch:
        params, opt_state, rest = run_steps(
            step, params, opt_state, [(x, y)] * (p["steps"] - 1), key=key,
            lr=p["lr"], start_step=1)
    losses = [float(v) for v in first + rest]
    if watch["compiles"]:
        raise AssertionError(f"train: {watch['compiles']} compile(s) "
                             "after the first step")
    if not np.isfinite(losses).all():
        raise AssertionError(f"train: non-finite loss {losses}")
    # falling: well below the start at the end, never above it on the way
    if not (losses[-1] < losses[0] - 0.3 and max(losses[1:]) < losses[0]):
        raise AssertionError("train: loss not falling on one repeated "
                             f"batch: {losses}")
    stats = device.memory_stats()
    if on_chip() and not stats:
        raise AssertionError("train: device.memory_stats() is empty")
    bad = [k for k, v in params.items()
           if jnp.issubdtype(v.dtype, jnp.floating)
           and v.dtype != jnp.bfloat16]
    if bad:
        raise AssertionError(f"train: params no longer bf16: {bad[:3]}")
    return {"losses": [round(v, 4) for v in losses],
            "mosaic_calls_in_step": mosaic_calls,
            "compiles_after_first_step": watch["compiles"],
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


# -- leg: serve -------------------------------------------------------------

def leg_serve(p) -> dict:
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.serving.decode import DecodeServer

    model, cfg = _build_gpt(p["model"])
    model.eval()
    rng = np.random.RandomState(SEED + 1)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n, _ in p["requests"]]
    budgets = [m for _, m in p["requests"]]

    srv = DecodeServer(model, max_slots=p["max_slots"],
                       page_len=p["page_len"],
                       max_context=p["max_context"],
                       batch_buckets=p["batch_buckets"],
                       prefill_buckets=p["prefill_buckets"])
    try:
        first_pools = list(srv._pools)
        srv.warmup()
        warm = srv.stats()["compile_count"]
        buckets = srv.bucket_config()
        want = (len(buckets["batch_buckets"]) * len(buckets["page_buckets"])
                + len(buckets["prefill_buckets"]))
        if warm != want:
            raise AssertionError(f"serve: warmup compiled {warm} "
                                 f"executables, bucket sets say {want}")
        with compile_watch() as watch:
            streams = [srv.submit(pr, max_new_tokens=m)
                       for pr, m in zip(prompts, budgets)]
            outs = [np.asarray(s.result(timeout=600)) for s in streams]
        stats = srv.stats()
    finally:
        srv.shutdown()

    if stats["compile_count"] != warm or watch["compiles"]:
        raise AssertionError(
            f"serve: compiled after warmup (compile_count {warm} -> "
            f"{stats['compile_count']}, backend compile requests "
            f"{watch['compiles']})")
    if stats["completed"] != len(prompts) or stats["failed"]:
        raise AssertionError(f"serve: completed {stats['completed']} of "
                             f"{len(prompts)}, failed {stats['failed']}")
    for out, m in zip(outs, budgets):
        if out.shape != (m,) or out.min() < 0 or out.max() >= cfg.vocab_size:
            raise AssertionError(f"serve: bad stream {out.shape} {out}")
    # donation is what engine.py does off-CPU: a donated pool buffer is
    # deleted by the step that consumed it
    donated = all(a.is_deleted() for a in first_pools)
    if donated != on_chip():
        raise AssertionError(f"serve: KV pools donated={donated} on "
                             f"backend {jax.default_backend()!r}")

    # reference: ONE full-context forward over prompt + served ids,
    # right-padded to the context (causal: the pad cannot reach back)
    ctx = p["max_context"]
    full = np.zeros((len(prompts), ctx), np.int32)
    for i, (pr, out) in enumerate(zip(prompts, outs)):
        full[i, :len(pr)] = pr
        full[i, len(pr):len(pr) + len(out)] = out
    with paddle.no_grad():
        logits = paddle.jit.to_static(model)(paddle.to_tensor(full))._data
    exact = total = 0
    worst = 0.0
    for i, (pr, out) in enumerate(zip(prompts, outs)):
        # logits at position t score token t+1
        rows = np.asarray(jax.device_get(
            logits[i, len(pr) - 1:len(pr) - 1 + len(out)]), np.float32)
        if not np.isfinite(rows).all():
            raise AssertionError("serve: non-finite reference logits")
        margin = rows.max(axis=-1) - rows[np.arange(len(out)), out]
        exact += int((margin == 0.0).sum())
        total += len(out)
        worst = max(worst, float(margin.max()))
    if worst > SERVE_MARGIN_TOL or exact < SERVE_MIN_EXACT * total:
        raise AssertionError(
            f"serve: ids disagree with the full-context forward: "
            f"{exact}/{total} exact, worst margin {worst:.4f} (tol "
            f"{SERVE_MARGIN_TOL})")
    return {"executables": warm, "requests": len(prompts),
            "tokens": total, "exact_match": f"{exact}/{total}",
            "worst_margin": round(worst, 4), "pools_donated": donated,
            "decode_steps": stats["decode_steps"]}


# -- leg: four chips --------------------------------------------------------

def _shard_shape(shape, spec, mesh):
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = list(shape)
    for d, entry in enumerate(spec):
        for axis in (entry if isinstance(entry, tuple) else (entry,)):
            if axis is not None:
                out[d] //= sizes[axis]
    return tuple(out)


def leg_four_chip(p) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu import models, profiler
    from paddle_tpu.models import (create_sharded_train_step,
                                   create_train_step, llama_fsdp_spec,
                                   llama_param_spec, write_back)

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs).reshape(2, 2), ("dp", "tp"))
    cfg = dataclasses.replace(getattr(models, p["model"])(),
                              num_layers=p["num_layers"])
    rng = np.random.RandomState(SEED)
    ids = rng.randint(0, cfg.vocab_size, (p["batch"], p["seq"] + 1))
    x, y = ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)
    key = jax.random.key(SEED)

    def build():
        # the same weights every time: the seed, not a copy kept on a device
        paddle.seed(SEED)
        model = models.LlamaForCausalLM(cfg).bfloat16()
        model.train()
        return model, paddle.optimizer.AdamW(
            learning_rate=p["lr"], parameters=model.parameters())

    def two_steps(step, params, opt_state, put):
        out = []
        for i in range(2):
            loss, params, opt_state = step(
                params, opt_state, jax.random.fold_in(key, i), put(x),
                put(y), p["lr"])
            out.append(float(jax.device_get(loss)))
        return out, params, opt_state

    # donate="consume": no second copy of params and moments at set-up,
    # so oracle and sharded runs of a ~0.7 B model fit one after another
    model, opt = build()
    step, params, opt_state = create_train_step(model, opt,
                                                donate="consume")
    oracle, params, opt_state = two_steps(step, params, opt_state,
                                          jnp.asarray)
    del model, opt, step, params, opt_state

    result = {"oracle_losses": [round(v, 4) for v in oracle]}
    fallbacks0 = profiler.pipeline_stats()["placement_fallbacks"]
    strict0 = paddle.get_flags("spmd_strict")["spmd_strict"]
    paddle.set_flags({"spmd_strict": True})
    try:
        for layout in ("tp", "tp_fsdp"):
            model, opt = build()
            shapes = {k: tuple(v.shape)
                      for k, v in model.named_parameters()}
            if layout == "tp":
                spec_fn = llama_param_spec
            else:
                def spec_fn(name):
                    return llama_fsdp_spec(name, shapes[name], 2)
            step, params, opt_state, shard_batch = \
                create_sharded_train_step(model, opt, mesh, spec_fn,
                                          donate="consume")
            # the model now points at the sharded arrays; its
            # single-device originals are freed
            write_back(model, params, strict=True)

            for name, arr in params.items():
                spec = spec_fn(name)
                if not any(e is not None for e in spec):
                    continue
                shards = arr.addressable_shards
                want = _shard_shape(arr.shape, spec, mesh)
                if (len({s.device for s in shards}) != 4
                        or any(s.data.shape != want for s in shards)):
                    raise AssertionError(
                        f"four_chip[{layout}]: {name} {arr.shape} spec "
                        f"{spec}: shards "
                        f"{[(s.device.id, s.data.shape) for s in shards]}"
                        f", want {want} on four devices")

            losses, params, opt_state = two_steps(step, params, opt_state,
                                                  shard_batch)
            for got, want in zip(losses, oracle):
                if not abs(got - want) <= FOUR_CHIP_LOSS_RTOL * abs(want):
                    raise AssertionError(
                        f"four_chip[{layout}]: losses {losses} != "
                        f"single-device {oracle} (rtol "
                        f"{FOUR_CHIP_LOSS_RTOL})")
            result[f"{layout}_losses"] = [round(v, 4) for v in losses]

            # nothing piled on device 0: only this layout's state is
            # alive now (earlier legs' arrays and executables dropped)
            del model, opt, step
            gc.collect()
            jax.clear_caches()
            in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                      for d in devs]
            if on_chip():
                if None in in_use or (max(in_use) - min(in_use)
                                      > MEMORY_BALANCE_TOL * max(in_use)):
                    raise AssertionError(
                        f"four_chip[{layout}]: per-device bytes in use "
                        f"{in_use} differ by more than "
                        f"{MEMORY_BALANCE_TOL:.0%}")
                result[f"{layout}_bytes_in_use"] = in_use
            del params, opt_state
    finally:
        paddle.set_flags({"spmd_strict": strict0})
    fallbacks = profiler.pipeline_stats()["placement_fallbacks"]
    if fallbacks != fallbacks0:
        raise AssertionError(f"four_chip: placement fallbacks {fallbacks}")
    return result


# -- driver -----------------------------------------------------------------

LEGS = {"kernels": leg_kernels, "laguna": leg_laguna, "glm": leg_glm,
        "sala": leg_sala, "train": leg_train,
        "serve": leg_serve, "four_chip": leg_four_chip}


def run_legs(preset, names) -> bool:
    """Run the named legs in order, one printed result line each. A leg
    that raises is a failure: its traceback is printed, the remaining legs
    still run (they are independent), and the return value is False."""
    ok = True
    for name in names:
        t0 = time.perf_counter()
        try:
            print(f"leg {name}: ok {json.dumps(LEGS[name](preset[name]))}",
                  flush=True)
        except Exception:  # noqa: BLE001 -- the boundary: record, go on
            traceback.print_exc()
            print(f"leg {name}: FAILED", flush=True)
            ok = False
        print(f"leg {name}: smoke wall time (not a metric) "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        gc.collect()
    return ok


def main(legs=None) -> int:
    t0 = time.perf_counter()
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"platform: {device['platform']}  device_kind: {device['kind']}"
          f"  device count: {device['count']}", flush=True)
    if dev.platform != "tpu":
        # jax itself falls back to the CPU with a warning; this does not
        print(f"chip_smoke: needs a TPU, jax found platform "
              f"{dev.platform!r} ({dev.device_kind}); not running",
              file=sys.stderr)
        return 2

    from paddle_tpu.core.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if legs is None:
        legs = [n for n in LEGS
                if n != "four_chip" or device["count"] >= 4]
    with compile_watch() as watch:
        ok = run_legs(CHIP, legs)
    print(f"compile requests: {watch['compiles']}  persistent cache hits: "
          f"{watch['cache_hits']}  misses: {watch['cache_misses']}")
    print(f"set-up + smoke wall time (not a metric): "
          f"{time.perf_counter() - t0:.1f} s")
    print(f"legs run: {', '.join(legs)}")
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
