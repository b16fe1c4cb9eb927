"""The Gated DeltaNet kernels' share of their roofline: the least time the
chip could take for the recurrence the traced steps require, every DeltaNet
layer's state update and two reads a token and value head with q, k, v, g,
beta, o and their gradients moved once (``harness/gdn_work.py``: forward and
backward, nothing recomputed, no intra-chunk product), over the summed device
time of the operations whose instruction name holds ``gated_delta_``. The
reader says which side bounds it."""
from benchmarks.harness import gdn_work

NAME = "gdn_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
PREFIX = "gated_delta_"


def read(run):
    return gdn_work.read_roofline(run, NAME, PREFIX)
