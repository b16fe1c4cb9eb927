"""Device time of the expert layers' kernels, a step: the summed device time
of the traced window's operations whose own name has ``moe_``, over the steps.

Found by NAME, as ``attn_device_ms_per_step`` finds attention: an "XLA Ops"
event is named by its whole HLO instruction, and the instruction's own name
(before `` = ``) carries a Pallas kernel's ``name=``: ``moe_gmm_fwd``,
``moe_gmm_bwd_x``, ``moe_gmm_bwd_w`` (``ops/pallas/grouped_matmul.py``).
Routing, the sort, dispatch and combine around them are XLA fusions whose
names carry nothing (no event has ``op_name``; PERF.md Open questions C3): they
are NOT in this number. A trace of a program without the names reads nothing.
Forward (with the recomputed forward) and backward go to standard error."""
import re

NAME = "moe_device_ms_per_step"
UNIT = "ms"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
PATTERN = re.compile(r"moe_")
BACKWARD = re.compile(r"bwd")


def read(run):
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    fwd = bwd = 0.0
    count = 0
    for start, end, name in t["ops"]:
        head = name.partition(" = ")[0]
        if not PATTERN.search(head):
            continue
        count += 1
        if BACKWARD.search(head):
            bwd += end - start
        else:
            fwd += end - start
    if not count:
        return None
    run["log"](f"moe_device_ms_per_step: {count} events by name, "
               f"{(fwd + bwd) / 1e6:.3f} ms in {t['steps']} steps; forward "
               f"{fwd / 1e6:.3f} ms, backward {bwd / 1e6:.3f} ms")
    return (fwd + bwd) / 1e6 / t["steps"]
