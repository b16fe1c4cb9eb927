"""Semi-auto SPMD with the Engine: NO user placements — the Completer
derives every parameter's layout over the mesh with its comm/compute
cost model, then fit/evaluate/save run over the distributed program.

Run:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/auto_parallel_engine.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed.auto_parallel import Engine
from paddle_tpu.distributed.process_mesh import ProcessMesh
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.models.llama import causal_lm_loss


def main():
    cfg = llama_tiny()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    opt = paddle.optimizer.AdamW(1e-2, parameters=model.parameters())
    mesh = ProcessMesh(np.arange(8).reshape(2, 4), ["dp", "tp"])
    engine = Engine(model, loss=causal_lm_loss, optimizer=opt, mesh=mesh)

    rng = np.random.RandomState(0)
    data = rng.randint(0, cfg.vocab_size, (8, 17)).astype(np.int64)
    history = engine.fit((data[:, :-1], data[:, 1:]), epochs=4, batch_size=4)
    print("fit losses:", [round(l, 4) for l in history["loss"]])
    metrics = engine.evaluate((data[:, :-1], data[:, 1:]), batch_size=4)
    print("eval:", metrics)


if __name__ == "__main__":
    main()
