"""Parameter-server mode: sparse embedding tables live in server host
RAM (sharded across PS servers over TCP); trainers pull rows, compute,
and push gradients that the server-side accessor applies — the
CTR-style workflow, here with two server shards and a sync communicator.

Run (single host, servers + trainer in-process):
    JAX_PLATFORMS=cpu python examples/parameter_server.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed.ps import (Communicator, PSClient, PSServer,
                                       SparseEmbedding)


def main():
    servers = [PSServer().start() for _ in range(2)]
    client = PSClient([s.endpoint for s in servers])
    comm = Communicator(client, mode="sync").start()
    try:
        paddle.seed(0)
        emb = SparseEmbedding("user", dim=8, accessor="adagrad",
                              init_scale=0.1, seed=3).bind(comm)
        lin = paddle.nn.Linear(8, 1)
        opt = paddle.optimizer.SGD(0.1, parameters=lin.parameters())
        mse = paddle.nn.MSELoss()

        rng = np.random.RandomState(0)
        ids = rng.randint(0, 50, (64,))
        target = (ids % 2).astype(np.float32).reshape(-1, 1)
        for step in range(10):
            x = emb(paddle.to_tensor(ids.reshape(-1, 1)))  # pull
            loss = mse(lin(x), paddle.to_tensor(target))
            loss.backward()          # embedding grads push via the comm
            opt.step()
            opt.clear_grad()
            if step % 3 == 0:
                print(f"ps step {step}: loss {float(loss):.4f}")
    finally:
        comm.stop()
        client.close()
        for s in servers:
            s.stop()


if __name__ == "__main__":
    main()
