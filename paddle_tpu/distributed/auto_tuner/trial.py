"""Profile-based trial launcher (VERDICT r2 item 9; parity:
auto_tuner/tuner.py:21 — the reference tuner launches a real training run
per candidate via `launch`, reads back the recorded metric, and feeds
failures into history pruning; it never ranks from a cost model alone).

Each candidate is measured in a child OS process, like the reference's
launch-based trials: the child builds a device mesh sized to the candidate
(`dp*mp*pp*sharding` virtual CPU devices by default, the real accelerator
when ``trial_platform`` says so), jits one llama train step with the
candidate's placements — TP via the Megatron spec map, ZeRO-3 via the FSDP
overlay, pp via the 1F1B PipelineParallel engine on `llama_pipeline_model`
— times a few steps, and prints ONE json line. Crashes, hangs, OOMs and
compile failures come back as error records that drive
``prune_by_history``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict

__all__ = ["launch_trial", "measure_candidate"]


def _degrees(cfg: Dict):
    return (cfg.get("dp_degree", 1), cfg.get("mp_degree", 1),
            cfg.get("pp_degree", 1), cfg.get("sharding_degree", 1))


def measure_candidate(tuner_cfg: Dict, cfg: Dict) -> Dict:
    """Run one short training trial for `cfg` in THIS process and return
    {"tokens_per_sec", "steps", "loss"}. Assumes jax sees at least
    dp*mp*pp*sharding devices (the subprocess parent guarantees it)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   create_sharded_train_step,
                                   llama_fsdp_spec, llama_param_spec,
                                   llama_pipeline_model)

    dp, mp, pp, sh = _degrees(cfg)
    world = dp * mp * pp * sh
    devs = jax.devices()
    if len(devs) < world:
        raise RuntimeError(
            f"trial needs {world} devices, found {len(devs)}")

    model = dict(tuner_cfg.get("model_cfg", {}))
    seq = int(model.get("seq_length",
                        model.get("max_position_embeddings", 128)))
    mcfg = LlamaConfig(
        vocab_size=int(model.get("vocab_size", 256)),
        hidden_size=int(model.get("hidden_size", 64)),
        intermediate_size=int(model.get("intermediate_size",
                                        4 * model.get("hidden_size", 64))),
        num_layers=int(model.get("num_layers", 2)),
        num_heads=int(model.get("num_heads", 4)),
        num_kv_heads=int(model.get("num_kv_heads",
                                   model.get("num_heads", 4))),
        max_position_embeddings=seq,
        dropout=0.0,
        use_recompute=bool(cfg.get("use_recompute", False)))

    mbs = int(cfg.get("micro_batch_size", 1))
    gbs = int(tuner_cfg.get("global_batch_size", mbs * dp * sh))
    steps = int(tuner_cfg.get("trial_steps", 3))
    rng = np.random.RandomState(0)
    paddle.seed(0)

    if pp > 1:
        if mp > 1 or sh > 1 or dp > 1:
            # the 1F1B engine places stages on disjoint sub-meshes; an
            # in-stage dp/TP/ZeRO overlay is a hybrid the trial path cannot
            # measure honestly yet — reject rather than mis-rank it
            raise RuntimeError(
                "unsupported-combo: pp>1 with dp/mp/sharding>1")
        acc = max(pp, gbs // max(mbs, 1))
        pipe = llama_pipeline_model(mcfg, num_stages=pp)

        class _S:
            pipeline_configs = {"accumulate_steps": acc,
                                "micro_batch_size": mbs}

        from paddle_tpu.distributed.fleet.meta_parallel import \
            PipelineParallel
        engine = PipelineParallel(pipe, None, _S())
        engine.train()  # training mode recursively: recompute stays active
        opt = paddle.optimizer.AdamW(1e-3, parameters=pipe.parameters())
        batch = acc * mbs
        ids = paddle.to_tensor(rng.randint(
            0, mcfg.vocab_size, (batch, seq)).astype(np.int64))
        labels = paddle.to_tensor(rng.randint(
            0, mcfg.vocab_size, (batch, seq)).astype(np.int64))
        loss = engine.train_batch((ids, labels), opt)   # warmup/compile
        float(loss)  # sync before opening the window
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch((ids, labels), opt)
        final = float(loss)  # host fetch closes the timed window
        dt = time.perf_counter() - t0
        tokens = batch * seq * steps
    else:
        data_par = dp * sh
        mesh = Mesh(np.array(devs[:world]).reshape(data_par, mp),
                    ("dp", "tp"))
        net = LlamaForCausalLM(mcfg)
        if sh > 1:
            named = {k: tuple(v.shape) for k, v in net.named_parameters()}
            spec_fn = lambda name: llama_fsdp_spec(  # noqa: E731
                name, named.get(name, (1,)), data_par)
        else:
            spec_fn = llama_param_spec
        opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())
        step, params, opt_state, shard_batch = create_sharded_train_step(
            net, opt, mesh, spec_fn)
        batch = mbs * data_par
        ids = shard_batch(rng.randint(0, mcfg.vocab_size, (batch, seq)))
        labels = shard_batch(rng.randint(0, mcfg.vocab_size, (batch, seq)))
        key = jax.random.key(0)
        loss, params, opt_state = step(params, opt_state, key, ids,
                                       labels, 1e-3)   # warmup/compile
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss, params, opt_state = step(params, opt_state, key, ids,
                                           labels, 1e-3)
        final = float(jax.device_get(loss))
        dt = time.perf_counter() - t0
        tokens = batch * seq * steps

    if not np.isfinite(final):
        raise RuntimeError(f"trial loss not finite: {final}")
    return {"tokens_per_sec": tokens / max(dt, 1e-9), "steps": steps,
            "loss": final}


def _force_cpu_platform(n_devices: int) -> None:
    """Pin this child to an n-device virtual CPU platform before jax
    initializes a backend (the parent may hold the chip). Importing this
    module already imported jax, which read JAX_PLATFORMS then, so the
    live config is set too."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")


def _child_main() -> int:
    payload = json.loads(sys.stdin.read())
    try:
        if payload["tuner_cfg"].get("trial_platform", "cpu") == "cpu":
            dp, mp, pp, sh = _degrees(payload["cfg"])
            _force_cpu_platform(dp * mp * pp * sh)
        out = measure_candidate(payload["tuner_cfg"], payload["cfg"])
        out["ok"] = True
    except Exception as e:  # noqa: BLE001 — the parent classifies it
        out = {"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
    print(json.dumps(out), flush=True)
    return 0


def launch_trial(tuner_cfg: Dict, cfg: Dict) -> float:
    """Measure `cfg` in a child process; return tokens/sec.

    Raises MemoryError on OOM (so AutoTuner records 'oom' and
    prune_by_history drops dominated candidates) and RuntimeError on any
    other failure."""
    dp, mp, pp, sh = _degrees(cfg)
    world = dp * mp * pp * sh
    env = dict(os.environ)
    # make paddle_tpu importable in the child regardless of the parent's
    # cwd (run-from-checkout layout: package root = .../paddle_tpu/..)
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    env["PYTHONPATH"] = (pkg_root + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else pkg_root)
    platform = tuner_cfg.get("trial_platform", "cpu")
    env["JAX_PLATFORMS"] = platform
    if platform == "cpu":
        flags = env.get("XLA_FLAGS", "")
        flags = " ".join(f for f in flags.split()
                         if "host_platform_device_count" not in f)
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={world}"
        ).strip()
    timeout = float(tuner_cfg.get("trial_timeout", 600))
    try:
        r = subprocess.run(
            [sys.executable, "-m",
             "paddle_tpu.distributed.auto_tuner.trial"],
            input=json.dumps({"tuner_cfg": tuner_cfg, "cfg": cfg}),
            capture_output=True, text=True, timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        raise RuntimeError(f"trial timeout after {timeout}s")
    line = (r.stdout or "").strip().splitlines()
    out = None
    for ln in reversed(line):
        try:
            parsed = json.loads(ln)
        except ValueError:
            continue
        if isinstance(parsed, dict):
            out = parsed
            break
    if out is None:
        raise RuntimeError(
            f"trial child died rc={r.returncode}: {(r.stderr or '')[-300:]}")
    if out.get("ok"):
        return float(out["tokens_per_sec"])
    err = out.get("error", "unknown")
    if ("RESOURCE_EXHAUSTED" in err or "oom" in err.lower()
            or "MemoryError" in err or "bad_alloc" in err):
        raise MemoryError(err)
    raise RuntimeError(err)


if __name__ == "__main__":
    sys.exit(_child_main())
