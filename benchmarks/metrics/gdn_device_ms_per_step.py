"""Device time of the Gated DeltaNet kernels, a step: the summed device time
of the traced window's operations whose instruction name holds
``gated_delta_`` (``ops/pallas/gated_delta.py``'s two Mosaic calls: the
chunked scan forward, twice where the layer body is recomputed, and the
reversed scan), over the steps. The split by kernel goes to standard error.
Nothing where the trace holds no such event (another family, a route to XLA,
a program without the model)."""
from benchmarks.harness import sala_work

NAME = "gdn_device_ms_per_step"
UNIT = "ms"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
PREFIX = "gated_delta_"


def read(run):
    return sala_work.read_device_ms(run, NAME, PREFIX)
