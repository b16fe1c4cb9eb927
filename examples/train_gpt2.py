"""Train GPT-2 on random tokens — the two training surfaces.

1. Eager (dygraph): loss.backward() / opt.step() per batch.
2. The TPU performance path: create_train_step stages forward + backward
   + AdamW into ONE jitted XLA program per step.

Run (any backend; sizes here are CPU-friendly):
    JAX_PLATFORMS=cpu python examples/train_gpt2.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM, create_train_step


def main():
    import jax

    cfg = GPTConfig(vocab_size=512, max_position_embeddings=128,
                    hidden_size=64, num_layers=2, num_heads=4,
                    intermediate_size=128, dropout=0.0)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (4, 33))
    x, y = ids[:, :-1], ids[:, 1:]

    # --- eager ---
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    for step in range(3):
        loss = model.loss(paddle.to_tensor(x), paddle.to_tensor(y))
        loss.backward()
        opt.step()
        opt.clear_grad()
        print(f"eager step {step}: loss {float(loss):.4f}")

    # --- jitted functional step (the benchmark path) ---
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step_fn, params, opt_state = create_train_step(model, opt)
    key = jax.random.key(0)
    for step in range(5):
        loss, params, opt_state = step_fn(params, opt_state, key,
                                          x.astype(np.int32),
                                          y.astype(np.int32), 1e-3)
        print(f"jit step {step}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
