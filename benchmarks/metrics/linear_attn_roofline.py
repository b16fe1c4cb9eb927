"""Linear attention's share of its roofline: the least time the chip could
take for the recurrence the traced steps require, every ``lightning-attn``
layer's state update and read-out a token and head with q, k, v, o and their
gradients moved once (``harness/sala_work.py::linear_attention``: forward and
backward, nothing recomputed, no intra-chunk product), over the summed device
time of the operations whose instruction name holds ``linear_attn_``. The
reader says which side bounds it."""
from benchmarks.harness import sala_work

NAME = "linear_attn_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
PREFIX = "linear_attn_"
KIND = "lightning-attn"


def read(run):
    cell = run["cell"]
    return sala_work.read_roofline(
        run, NAME, PREFIX, KIND, sala_work.linear_attention(
            cell.config.values, cell.params["batch"], cell.params["seq"]))
