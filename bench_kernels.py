"""Pallas-kernel-vs-XLA-fallback microbenchmarks (VERDICT r2 item #2).

The fused Pallas kernels exist only to beat the XLA lowerings they replace
(reference capability: paddle/phi/kernels/gpu/flash_attn_kernel.cu:91 and
the fused-op inventory in paddle/phi/kernels/fusion/). This suite measures
each family at training shapes (seq 1k-8k, GQA, LM-head vocab) against the
exact XLA implementation dispatch would otherwise use, and prints ONE JSON
line with per-kernel fwd / fwd+bwd times and speedup ratios
(ratio = xla_ms / pallas_ms; >1.0 means the Pallas kernel wins).

Timing honesty: every timed window is closed by a ``jax.device_get`` of a
scalar that data-depends on the full output (fwd: sum(out); bwd: sum of all
grads), so lazy dispatch cannot shrink the window.

One process: the one that holds the chip. It exits non-zero without a TPU
(interpret-mode timing is meaningless) and when any case recorded an
error. ROADMAP S1/S2 replace it with trace-derived kernel times.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def _timed(fn, args, iters=3, windows=3):
    """Min-of-windows ms per call; fn must return a scalar (device_get of it
    closes the window)."""
    out = fn(*args)
    float(np.asarray(out))  # warmup/compile + sync
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        float(np.asarray(out))
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1e3


def dispatch_floor_ms():
    """Per-execute overhead of the device path: time a trivial jitted
    scalar op. Reported in the artifact so per-kernel numbers are
    interpretable."""
    import jax
    import jax.numpy as jnp
    x = jnp.ones((8, 128), jnp.float32)
    return round(_timed(jax.jit(lambda x: x.sum()), (x,), iters=10), 3)


def bench_pair(name, pallas_fn, xla_fn, args, results, iters=3,
               diff_argnums=None, chain=8, feedback=None, shipped_fn=None):
    """Measure per-call fwd and fwd+bwd time for a (pallas, xla) pair,
    plus — when ``shipped_fn`` is given — the SHIPPED implementation (the
    dispatch-level wrapper with its per-direction routing + autotune,
    VERDICT r3 #2). ``shipped_ratio = xla_ms / shipped_ms`` is the gated
    number: it must stay >= 1.0 (a routed impl can always fall back to
    XLA, so a sustained loss is a routing bug); the raw pallas ratio stays
    as a diagnostic.

    The op is CHAINED ``chain`` times inside ONE jitted program — each
    iteration's output feeds the next call's first argument — so the
    reported per-call time is compute, not the per-execute dispatch floor
    (which drowns every ms-scale kernel). ``feedback(out, carry)`` adapts ops whose
    output shape differs from the carried argument (default: the output IS
    the next carry)."""
    import jax
    import jax.numpy as jnp

    if diff_argnums is None:
        diff_argnums = tuple(range(len(args)))
    if feedback is None:
        feedback = lambda out, carry: out.astype(carry.dtype)  # noqa: E731

    variants = [("pallas", pallas_fn), ("xla", xla_fn)]
    if shipped_fn is not None:
        variants.append(("shipped", shipped_fn))
        # one EAGER call first: triggers the per-direction autotune
        # measurement (select.pick_grad_impl / _tuned_blocks) so the
        # jitted chain below consults a warm cache
        jax.block_until_ready(shipped_fn(*args))

    def chained(f):
        def run(*a):
            c = a[0]
            for _ in range(chain):
                c = feedback(f(c, *a[1:]), c)
            return c.astype(jnp.float32).sum()
        return run

    entry = {}
    for tag, make in (
        ("fwd", lambda f: jax.jit(chained(f))),
        ("fwd_bwd", lambda f: jax.jit(
            lambda *a: sum(
                g.astype(jnp.float32).sum() for g in jax.grad(
                    chained(f), argnums=diff_argnums)(*a)))),
    ):
        row = {}
        for vname, fn in variants:
            try:
                row[f"{vname}_ms"] = round(
                    _timed(make(fn), args, iters=iters) / chain, 3)
            except Exception as e:  # noqa: BLE001 — record, keep benching
                row[f"{vname}_error"] = f"{type(e).__name__}: {e}"[:200]
        if "pallas_ms" in row and "xla_ms" in row and row["pallas_ms"] > 0:
            row["ratio"] = round(row["xla_ms"] / row["pallas_ms"], 3)
        if "shipped_ms" in row and "xla_ms" in row and row["shipped_ms"] > 0:
            row["shipped_ratio"] = round(
                row["xla_ms"] / row["shipped_ms"], 3)
        entry[tag] = row
    results[name] = entry


def _assemble(dev, results, tuning, at_status):
    """The JSON artifact."""
    import jax
    ratios = [e[tag]["ratio"] for e in results.values()
              for tag in ("fwd", "fwd_bwd") if "ratio" in e[tag]]
    shipped = [e[tag]["shipped_ratio"] for e in results.values()
               for tag in ("fwd", "fwd_bwd") if "shipped_ratio" in e[tag]]
    errors = [f"{n}.{tag}: {e[tag][k]}" for n, e in results.items()
              for tag in ("fwd", "fwd_bwd")
              for k in ("pallas_error", "xla_error", "shipped_error")
              if k in e[tag]]
    errors.extend(f"autotune {key}: {impls}"
                  for key, impls in at_status["failed"].items())
    out = {
        "metric": "pallas_vs_xla_kernel_ratios",
        "platform": dev.platform,
        # the gate compares this against the baseline's seed time to refuse
        # stale evidence (tests/test_kernel_gate.py staleness check)
        "captured_at_unix": time.time(),
        "device": str(dev),
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "dispatch_floor_ms": dispatch_floor_ms(),
        "results": results,
        "autotune": {**at_status, **tuning},
        "summary": {
            "n_measured": len(ratios),
            "min_ratio": round(min(ratios), 3) if ratios else None,
            "geomean_ratio": round(float(np.exp(np.mean(np.log(ratios)))), 3)
            if ratios else None,
            # the gated numbers: shipped (dispatch-routed) vs XLA — must
            # stay >= 1.0 modulo timing noise (tests/test_kernel_gate.py)
            "n_shipped": len(shipped),
            "min_shipped_ratio": round(min(shipped), 3) if shipped
            else None,
            "geomean_shipped_ratio": round(
                float(np.exp(np.mean(np.log(shipped)))), 3) if shipped
            else None,
        },
    }
    if errors:
        out["error"] = "; ".join(errors)[:600]
    return out


def main():
    import os

    import jax
    import jax.numpy as jnp

    from bench import require_tpu
    dev = require_tpu()

    from paddle_tpu.core import autotune as _at
    from paddle_tpu.ops.pallas.cross_entropy import (
        _softmax_xent_pallas_impl, softmax_xent_pallas)
    from paddle_tpu.ops.pallas.flash_attention import (
        _attention_pallas, _tuned_blocks, flash_attention_ext,
        seed_from_key)
    from paddle_tpu.ops.pallas.norms import (
        _layer_norm_pallas_impl, _rms_norm_pallas_impl, layer_norm_pallas,
        rms_norm_pallas)
    from paddle_tpu.nn.functional.flash_attention import _attention_xla

    # on-chip block-size autotuning (VERDICT r2 #2: pick bq/bk on the real
    # MXU): each eager call below measures the candidate tilings fwd+bwd
    # and persists the winner (artifacts/autotune_tpu.json, git-ignored);
    # the timed jitted calls consult the same cache
    _at.use_artifacts_cache(os.path.dirname(os.path.abspath(__file__)))

    rng = np.random.RandomState(0)
    results = {}
    tuning = {"blocks": {}}

    # ---- flash attention: training shapes, causal, bf16, incl. GQA -------
    fa_configs = [
        # exact bench.py GPT-2 attention shape
        ("fa_gpt2_s1k_h12d64", 8, 1024, 12, 12, 64),
        ("fa_s1k_h16", 8, 1024, 16, 16, 128),
        ("fa_s2k_h16", 4, 2048, 16, 16, 128),
        ("fa_s4k_h16", 2, 4096, 16, 16, 128),
        ("fa_s8k_h16", 1, 8192, 16, 16, 128),
        ("fa_s4k_gqa32_8", 2, 4096, 32, 8, 128),
    ]
    zero_seed = jnp.zeros((1,), jnp.int32)

    def tune_blocks(name, q, k, v, seed_arr, rate, dkey=None):
        # measure candidate tilings (and the whole-op XLA candidate)
        # fwd+bwd on-chip, persist the winner; a candidate that raised is
        # in autotune_status()["failed"] and fails the run (_assemble)
        imp, bq, bk, _ = _tuned_blocks(q, k, v, None, seed_arr, True,
                                       float(q.shape[-1]) ** -0.5,
                                       rate, False, dropout_key=dkey)
        tuning["blocks"][name] = [bq, bk] if imp != "xla" else "xla"
        return bq, bk

    for name, B, S, Hq, Hk, D in fa_configs:
        q = jnp.asarray(rng.randn(B, S, Hq, D), jnp.bfloat16) * 0.1
        k = jnp.asarray(rng.randn(B, S, Hk, D), jnp.bfloat16) * 0.1
        v = jnp.asarray(rng.randn(B, S, Hk, D), jnp.bfloat16) * 0.1
        scale = float(D) ** -0.5
        bq, bk = tune_blocks(name, q, k, v, zero_seed, 0.0)
        bench_pair(
            name,
            lambda q, k, v, _s=scale, _a=bq, _b=bk: flash_attention_ext(
                q, k, v, None, zero_seed, None, None, True, _s, 0.0, _a,
                _b, False),
            lambda q, k, v, _s=scale: _attention_xla(
                q, k, v, None, True, _s, 0.0, None),
            (q, k, v), results,
            iters=2, chain=4 if S >= 4096 else 8,
            shipped_fn=lambda q, k, v, _s=scale: _attention_pallas(
                q, k, v, None, True, _s, 0.0, None))

    # ---- flash attention with in-kernel dropout (VERDICT r2 #3: the
    # dropout training config must keep the fast path) --------------------
    B, S, Hq, Hk, D = 2, 4096, 16, 16, 128
    q = jnp.asarray(rng.randn(B, S, Hq, D), jnp.bfloat16) * 0.1
    k = jnp.asarray(rng.randn(B, S, Hk, D), jnp.bfloat16) * 0.1
    v = jnp.asarray(rng.randn(B, S, Hk, D), jnp.bfloat16) * 0.1
    seed = seed_from_key(jax.random.key(0))
    dkey = jax.random.key(0)
    scale = float(D) ** -0.5
    dbq, dbk = tune_blocks("fa_s4k_dropout0.1", q, k, v, seed, 0.1,
                           dkey=dkey)
    bench_pair(
        "fa_s4k_dropout0.1",
        lambda q, k, v, _s=scale: flash_attention_ext(
            q, k, v, None, seed, None, None, True, _s, 0.1, dbq, dbk,
            False),
        lambda q, k, v, _s=scale: _attention_xla(
            q, k, v, None, True, _s, 0.1, dkey),
        (q, k, v), results, iters=2, chain=4,
        shipped_fn=lambda q, k, v, _s=scale: _attention_pallas(
            q, k, v, None, True, _s, 0.1, dkey))

    # ---- blockwise (vocab-streamed) LM-head+CE vs the unfused block:
    # the sweep candidate bench.py relies on for batch>=16 --------------
    from paddle_tpu.ops.fused_ce import blockwise_linear_cross_entropy
    h_lm = jnp.asarray(rng.randn(8192, 768), jnp.bfloat16) * 0.02
    w_lm = jnp.asarray(rng.randn(50304, 768), jnp.bfloat16) * 0.02
    lab_lm = jnp.asarray(rng.randint(0, 50304, (8192,)), jnp.int32)

    def unfused_lm(hh, ww):
        logits = jnp.matmul(hh, ww.T,
                            preferred_element_type=jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lab_lm[:, None], 1)[:, 0]
        return jnp.mean(lse - tgt)

    bench_pair(
        "lmce_8k_50k_blockwise_vs_plain",
        lambda hh, ww: blockwise_linear_cross_entropy(hh, ww, lab_lm),
        unfused_lm,
        (h_lm, w_lm), results, chain=2,
        # scalar loss: nudge the carry through one element per link
        feedback=lambda out, hh: hh.at[:1, :1].add(
            (out * np.float32(1e-30)).astype(hh.dtype)))

    # ---- fused cross-entropy at LM-head shapes --------------------------
    for name, rows, vocab in (("ce_4k_50k", 4096, 50304),
                              ("ce_8k_50k", 8192, 50304)):
        logits = jnp.asarray(rng.randn(rows, vocab), jnp.float32)
        labels = jnp.asarray(rng.randint(0, vocab, (rows,)), jnp.int32)
        bench_pair(
            name,
            # raw diagnostic: the hand kernel with its Pallas backward
            lambda lg, lb: softmax_xent_pallas(lg, lb, False, "pallas"),
            lambda lg, lb: -jnp.take_along_axis(
                jax.nn.log_softmax(lg, -1), lb[:, None], 1)[:, 0],
            (logits, labels), results, diff_argnums=(0,), chain=12,
            shipped_fn=_softmax_xent_pallas_impl,
            # CE returns per-row losses, not a logits-shaped carry: inject
            # the dependency into ONE column (values unchanged in f32, not
            # DCE-foldable) — a full-buffer elementwise feedback would add
            # a logits-sized HBM pass per link and distort the absolutes
            feedback=lambda out, lg: lg.at[:, :1].add(
                out[:, None] * np.float32(1e-30)))

    # ---- norms at transformer activation shapes -------------------------
    for name, rows, hidden in (("rms_8k_4k", 8192, 4096),
                               ("rms_16k_8k", 16384, 8192)):
        x = jnp.asarray(rng.randn(rows, hidden), jnp.float32)
        w = jnp.asarray(rng.randn(hidden), jnp.float32)
        bench_pair(
            name,
            lambda x, w: rms_norm_pallas(x, w, 1e-6, False),
            lambda x, w: x * jax.lax.rsqrt(
                jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w,
            (x, w), results, chain=12,
            shipped_fn=lambda x, w: _rms_norm_pallas_impl(x, w, 1e-6))
    x = jnp.asarray(rng.randn(8192, 4096), jnp.float32)
    w = jnp.asarray(rng.randn(4096), jnp.float32)
    b = jnp.asarray(rng.randn(4096), jnp.float32)
    bench_pair(
        "ln_8k_4k",
        lambda x, w, b: layer_norm_pallas(x, w, b, 1e-6, False),
        lambda x, w, b: (x - x.mean(-1, keepdims=True)) * jax.lax.rsqrt(
            x.var(-1, keepdims=True) + 1e-6) * w + b,
        (x, w, b), results, chain=12,
        shipped_fn=lambda x, w, b: _layer_norm_pallas_impl(
            x, w, b, 1e-6, 1))

    # ---- ring-attention chunk compute at s8k (VERDICT r4 #5): the per-
    # device ring step — 4 chunks of 2048, flash block kernel per pair,
    # lse merge — vs the monolithic whole-sequence kernel. The "ratio"
    # here is monolithic_ms / chunked_ms: single-chip ring compute
    # overhead (expected < 1.0; diagnostic, not gated — no shipped_fn).
    # LAST on purpose: its 10-kernel unrolled compile is the longest shot
    # in this file, and a blowup here must not cost the gated cases above
    from paddle_tpu.distributed.long_context import ring_chunked_single
    B, S, Hq, D = 1, 8192, 16, 128
    q = jnp.asarray(rng.randn(B, S, Hq, D), jnp.bfloat16) * 0.1
    k = jnp.asarray(rng.randn(B, S, Hq, D), jnp.bfloat16) * 0.1
    v = jnp.asarray(rng.randn(B, S, Hq, D), jnp.bfloat16) * 0.1
    scale = float(D) ** -0.5
    bench_pair(
        "ring_chunks_s8k_c4",
        lambda q, k, v, _s=scale: ring_chunked_single(
            q, k, v, 4, True, _s, False),
        lambda q, k, v, _s=scale: flash_attention_ext(
            q, k, v, None, zero_seed, None, None, True, _s, 0.0, 128,
            128, False),
        (q, k, v), results, iters=2, chain=2)

    out = _assemble(dev, results, tuning, _at.autotune_status())
    print(json.dumps(out))
    return 1 if out.get("error") else 0


if __name__ == "__main__":
    sys.exit(main())
