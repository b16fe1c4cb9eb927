"""Device busy time of the traced window over the steps in it: the union of
the device-op intervals between the start of the first whole step program in
the trace and the end of the last."""
NAME = "step_device_ms"
UNIT = "ms"
LAYER = "model step"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    return t["busy_s"] * 1e3 / t["steps"]
