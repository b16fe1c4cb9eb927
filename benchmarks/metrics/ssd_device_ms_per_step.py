"""Device time of Mamba-2's SSD kernels, a step: the summed device time of the
traced window's operations whose instruction name holds ``ssd_fwd`` or
``ssd_bwd`` (``ops/pallas/ssd.py``'s two Mosaic calls: the chunked scan
forward and the reversed scan), over the steps. The split by kernel goes to
standard error. Nothing where the trace holds no such event (another family,
a route to XLA, a program without the model)."""
from benchmarks.harness import ssd_work

NAME = "ssd_device_ms_per_step"
UNIT = "ms"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return ssd_work.read_device_ms(run, NAME)
