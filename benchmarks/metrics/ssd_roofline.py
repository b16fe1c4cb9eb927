"""Mamba-2's SSD kernels' share of their roofline: the least time the chip
could take for the scan the traced steps require, every Mamba-2 layer's state
update and read a token and head with x, dt, B, C, y and their gradients
moved once (``harness/ssd_work.py``: forward and backward, nothing
recomputed, no intra-chunk product), over the summed device time of the
operations whose instruction name holds ``ssd_fwd`` or ``ssd_bwd``. The
reader says which side bounds it."""
from benchmarks.harness import ssd_work

NAME = "ssd_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return ssd_work.read_roofline(run, NAME)
