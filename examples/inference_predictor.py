"""Export a trained model with jit.save (StableHLO) and serve it with the
inference Config/Predictor — the deployment surface (a pure-C driver over
csrc/inference_capi.cpp speaks the same artifact).

Run:
    JAX_PLATFORMS=cpu python examples/inference_predictor.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import os
import tempfile

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import inference
from paddle_tpu.static import InputSpec


def main():
    paddle.seed(0)
    model = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
                                 paddle.nn.Linear(16, 4))
    model.eval()
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "net")
        paddle.jit.save(model, path,
                        input_spec=[InputSpec([4, 8], "float32")])

        config = inference.Config(path)
        predictor = inference.create_predictor(config)
        x = np.random.RandomState(0).randn(4, 8).astype("float32")
        in_names = predictor.get_input_names()
        predictor.get_input_handle(in_names[0]).copy_from_cpu(x)
        predictor.run()
        out = predictor.get_output_handle(
            predictor.get_output_names()[0]).copy_to_cpu()
        print("prediction shape:", out.shape)
        ref = model(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(out, ref, rtol=1e-5)
        print("predictor output matches the eager model")


if __name__ == "__main__":
    main()
