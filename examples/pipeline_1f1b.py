"""Pipeline parallelism: 2-stage 1F1B over disjoint sub-meshes, with the
hybrid pp x tp x dp variant (named 2-D stage meshes).

Run:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/pipeline_1f1b.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed.fleet import LayerDesc, PipelineLayer
from paddle_tpu.distributed.fleet.meta_parallel import PipelineParallel


def main():
    paddle.seed(0)
    descs = []
    for _ in range(4):
        descs.append(LayerDesc(paddle.nn.Linear, 8, 8))
        descs.append(LayerDesc(paddle.nn.Tanh))
    pipe = PipelineLayer(descs, num_stages=2, loss_fn=paddle.nn.MSELoss())

    strategy = dist.fleet.DistributedStrategy()
    strategy.pipeline_configs = {"accumulate_steps": 4,
                                 "micro_batch_size": 2}
    engine = PipelineParallel(pipe, None, strategy,
                              stage_mesh_axes={"dp": 2, "tp": 2},
                              batch_axis="dp")
    opt = paddle.optimizer.SGD(0.05, parameters=pipe.parameters())
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(8, 8).astype("float32"))
    y = paddle.to_tensor(rng.randn(8, 8).astype("float32"))
    for step in range(5):
        loss = engine.train_batch((x, y), opt)
        print(f"1f1b (pp2 x tp2 x dp2) step {step}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
