"""Fully automatic parallel planning: the Engine is given NO mesh at all —
the degree planner factorizes the device count into (dp, tp) candidates,
prunes them with the auto-tuner's rules (degree product, head/hidden
divisibility, batch divisibility, memory), scores the survivors with the
Completer's comm/compute/memory plan cost, and picks the layout. With
``Strategy({"tuning": {"enable": True, "profile": True}})`` the survivors
are instead ranked by ONE timed real train step each (the auto-tuner's
profile-trial mode).

Run:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/degree_planner.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed import Strategy
from paddle_tpu.distributed.auto_parallel import Engine
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.models.llama import causal_lm_loss


def main():
    cfg = llama_tiny()
    rng = np.random.RandomState(0)
    data = rng.randint(0, cfg.vocab_size, (8, 17)).astype(np.int64)
    xy = (data[:, :-1], data[:, 1:])

    # 1) cost-model planning: no mesh, no placements, no degrees
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(1e-2, parameters=model.parameters())
    engine = Engine(model, loss=causal_lm_loss, optimizer=opt)
    history = engine.fit(xy, epochs=3, batch_size=8)
    info = engine.prepare()._planned_info
    print("cost-planned:", info["chosen"])
    print("  candidates:", info["candidates"])
    print("  pruned:    ", info["pruned"])
    print("  loss:      ", [round(l, 4) for l in history["loss"]])

    # 2) profile-trial planning: one measured step per surviving candidate
    paddle.seed(0)
    model2 = LlamaForCausalLM(cfg)
    opt2 = paddle.optimizer.AdamW(1e-2, parameters=model2.parameters())
    strat = Strategy({"tuning": {"enable": True, "profile": True}})
    engine2 = Engine(model2, loss=causal_lm_loss, optimizer=opt2,
                     strategy=strat)
    engine2.fit(xy, epochs=1, batch_size=8)
    info2 = engine2.prepare()._planned_info
    print("profile-planned:", info2["chosen"],
          "trial_s:", info2.get("chosen_trial_s"))
    print("  trials:", info2.get("profiled_s"))

    assert history["loss"][-1] < history["loss"][0]
    print("ok: planner chose degrees and the model trained")


if __name__ == "__main__":
    main()
