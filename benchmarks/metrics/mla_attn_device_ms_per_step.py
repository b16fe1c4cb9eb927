"""Device time of latent attention's kernels, a step: the summed device time
of the traced window's Mosaic calls with an operand shaped ``[B x heads, S,
nope + rope]`` (``harness/mla_events.py``: the flash kernels of
``models/glm_moe_lite.py``'s attention calls, forward, dq and dkv, the MTP
module's among them and the forward twice where the layer body is recomputed),
over the steps. The count and the forward / backward split go to standard
error. Nothing where the trace holds no such event (another family, a route
to XLA, a program without the model)."""
from benchmarks.harness import mla_events

NAME = "mla_attn_device_ms_per_step"
UNIT = "ms"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = mla_events.find(run)
    if found is None:
        return None
    t = run["trace"]
    run["log"](f"mla_attn_device_ms_per_step: {found['count']} events by "
               f"shape, {found['seconds'] * 1e3:.3f} ms in {t['steps']} "
               f"steps; forward {found['forward_s'] * 1e3:.3f} ms, backward "
               f"{found['backward_s'] * 1e3:.3f} ms")
    return found["seconds"] * 1e3 / t["steps"]
