"""Benchmark: GPT-2 small causal-LM training throughput on one TPU chip.

One process, one configuration: batch 8, seq 1024, plain CE, bf16 params,
the jitted functional train step (forward + backward + AdamW in one XLA
program, donated buffers). Prints ONE JSON line: {"metric", "value",
"unit", "vs_baseline", "extra"}; vs_baseline = achieved MFU / 0.45
(BASELINE.md target MFU for the hybrid-parallel north star).

The process that runs this holds the chip. It exits non-zero without a
TPU, for a ``device_kind`` that is not in ``DEVICE_PEAKS``, on a
non-finite or stuck loss, on an implausible MFU and on any exception:
there is no CPU substitute, no stored result and no assumed peak.
ROADMAP S1 replaces this script with the table of cells.

Timing contract:
- the timed window is closed by a host fetch (``jax.device_get``) of the
  final loss -- the step chain (loss_i depends on params_{i-1}) means the
  scalar's bytes cannot arrive before every timed step has executed;
- MFU is computed from config-derived matmul FLOPs with causal attention
  counted at half density;
- loss is fetched before and after the timed window and must advance and
  stay finite.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time

import numpy as np

# Published peaks of ONE chip, keyed by jax's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s). A device that is not in the table is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def device_peaks(device) -> dict:
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise SystemExit(
            f"no published peaks for device_kind {device.device_kind!r}; "
            f"known: {sorted(DEVICE_PEAKS)} (add it to bench.DEVICE_PEAKS "
            "with its source)") from None


def require_tpu():
    """The device the bench scripts measure on: a TPU, or no run at all
    (jax itself falls back to the CPU with a warning). Also places the
    persistent compile cache (core/compile_cache.py)."""
    import jax

    from paddle_tpu.core.compile_cache import enable_compile_cache
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"this benchmark needs a TPU; jax found platform "
            f"{dev.platform!r} ({dev.device_kind})")
    enable_compile_cache()
    return dev


def main():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import (GPTForCausalLM, create_train_step,
                                   gpt2_small)

    dev = require_tpu()
    peak = device_peaks(dev)["bf16_flops_per_s"]

    cfg = dataclasses.replace(gpt2_small(), dropout=0.0)
    batch, seq, iters, windows = 8, 1024, 40, 3

    paddle.seed(0)
    model = GPTForCausalLM(cfg).bfloat16()   # bf16 params; AdamW state f32
    opt = paddle.optimizer.AdamW(learning_rate=3e-4, weight_decay=0.01,
                                 parameters=model.parameters())
    # donate=True: params + opt state are aliased in place by XLA,
    # freeing ~1.3 GB of HBM at GPT-2-small scale
    step, params, opt_state = create_train_step(model, opt, donate=True)
    n_params = sum(int(np.prod(v.shape)) for v in params.values())

    rng = np.random.RandomState(0)
    key = jax.random.key(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq + 1)),
                      dtype=jnp.int32)
    x, y = ids[:, :-1], ids[:, 1:]
    # warmup / compile; host fetch = hard sync
    loss, params, opt_state = step(params, opt_state, key, x, y, 3e-4)
    loss_start = float(jax.device_get(loss))
    best_dt = float("inf")
    step_i = 0
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss, params, opt_state = step(
                params, opt_state, jax.random.fold_in(key, step_i),
                x, y, 3e-4)
            step_i += 1
        # the fetch closes the window: the scalar's bytes depend on the
        # whole step chain, so they cannot arrive before the work is done
        # graft-lint: disable=GL504 -- timing honesty: the same-iteration
        # sync IS the measurement (closes the timed window)
        loss_end = float(jax.device_get(loss))
        best_dt = min(best_dt, time.perf_counter() - t0)
    tokens_per_sec = batch * seq * iters / best_dt
    ms_per_step = best_dt / iters * 1e3

    # config-derived matmul FLOPs: per layer qkv+proj (4 H^2) + mlp (2 H I),
    # plus the logits projection (V H); x6 for fwd+bwd; causal attention at
    # half density: 2*S/2*H fwd per layer per token, x3 fwd+bwd = 3*S*H
    H, L, I, V = (cfg.hidden_size, cfg.num_layers, cfg.intermediate_size,
                  cfg.vocab_size)
    matmul_params = L * (4 * H * H + 2 * H * I) + V * H
    flops_per_tok = 6 * matmul_params + 3 * L * seq * H
    mfu = tokens_per_sec * flops_per_tok / peak

    errors = []
    if not (mfu < 1.0):
        errors.append(f"implausible mfu {mfu:.3f} >= 1.0: timing did not "
                      "capture real device work")
    if not (np.isfinite(loss_start) and np.isfinite(loss_end)):
        errors.append("non-finite loss")
    if loss_end == loss_start:
        errors.append("loss did not advance across the timed window")
    if errors:
        raise SystemExit("bench: " + "; ".join(errors))

    print(json.dumps({
        "metric": "gpt2s_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {"mfu": round(mfu, 4), "ms_per_step": round(ms_per_step, 3),
                  "loss_start": round(loss_start, 4),
                  "loss_end": round(loss_end, 4),
                  "params": n_params, "batch": batch, "seq": seq,
                  "lm_ce": cfg.lm_ce, "timing": f"loop{iters}/best-of-"
                                                f"{windows}",
                  "platform": dev.platform,
                  "device_kind": dev.device_kind,
                  "device_count": jax.device_count()},
    }))


if __name__ == "__main__":
    sys.exit(main())
