"""Device time of the chosen-block attention kernels, a step: the summed
device time of the traced window's operations whose instruction name holds
``sparse_attn_`` (``ops/pallas/sparse_attention.py``'s three Mosaic calls:
the forward sweep, dq and dkv), over the steps. The split by kernel goes to
standard error. Nothing where the trace holds no such event (another family,
a sequence under ``dense_len``, a route to XLA, a program without the
model)."""
from benchmarks.harness import sala_work

NAME = "sparse_attn_device_ms_per_step"
UNIT = "ms"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
PREFIX = "sparse_attn_"


def read(run):
    return sala_work.read_device_ms(run, NAME, PREFIX)
