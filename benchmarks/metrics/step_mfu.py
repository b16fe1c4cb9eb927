"""The whole step's share of the chip's bf16 peak: required FLOPs per token
(``harness/flops.py::train_flops_per_token``: 6 x matmul parameters plus
causal attention at half density, nothing recomputed counted) x tokens per
second of the traced window / the peak of ``peaks.json``. The traced window's
rate is its steps x tokens a step over its length on the device's clock."""
from benchmarks.harness import flops

NAME = "step_mfu"
UNIT = "%"
LAYER = "model step"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    t = run["trace"]
    if not t or not t["steps"] or not t["window_s"]:
        return None
    cell = run["cell"]
    values = cell.config.values
    dims = run["bench"].module("reference", values["family"]).dims(values)
    per_token = flops.train_flops_per_token(dims, cell.params["seq"])
    rate = t["steps"] * run["counters"]["tokens_per_step"] / t["window_s"]
    return 100.0 * per_token * rate / run["peaks"]["bf16_flops_per_s"]
