"""Fleet hybrid parallel: TP x DP over an 8-device mesh.

Run on the virtual CPU mesh:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/fleet_hybrid.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed.fleet.layers.mpu import (ColumnParallelLinear,
                                                     RowParallelLinear)


def main():
    strategy = dist.fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4,
                               "pp_degree": 1, "sharding_degree": 1}
    dist.fleet.init(is_collective=True, strategy=strategy)

    paddle.seed(0)
    model = paddle.nn.Sequential(
        ColumnParallelLinear(16, 32, gather_output=False),
        paddle.nn.Tanh(),
        RowParallelLinear(32, 4, input_is_parallel=True),
    )
    model = dist.fleet.distributed_model(model)
    opt = paddle.optimizer.AdamW(1e-2, parameters=model.parameters())
    opt = dist.fleet.distributed_optimizer(opt)
    mse = paddle.nn.MSELoss()

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(8, 16).astype("float32"))
    y = paddle.to_tensor(rng.randn(8, 4).astype("float32"))
    for step in range(5):
        loss = mse(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        print(f"tp x dp step {step}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
