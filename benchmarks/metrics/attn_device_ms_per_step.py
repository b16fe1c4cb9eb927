"""Device time of attention, a step: the summed device time of the traced
window's operations that the program names as attention, over the steps.

Found by NAME, and by nothing else. An "XLA Ops" event is named by its whole
HLO instruction, and the instruction's own name (what stands before `` = ``)
is built from the end of the op's name stack: the Pallas kernels'
``name=`` (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``:
``ops/pallas/flash_attention.py``) and the scope ``attention``
(``nn/functional/flash_attention.py::ATTENTION_SCOPE``) reach it. No shape
and no custom-call target is in the pattern, so a kernel with another
layout or another implementation under the same names is still found. A
trace of a program without the names (before PR 25) reads nothing. Operands
are not looked at: a fusion that consumes ``%flash_fwd.1`` is not attention.

The count and the forward/backward split go to standard error. A time, not a
share: ``flash_attn_roofline`` logs the seconds it found by shape, and the
two agree on today's kernels."""
import re

NAME = "attn_device_ms_per_step"
UNIT = "ms"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
PATTERN = re.compile(r"attention|flash_")
BACKWARD = re.compile(r"transpose|bwd")


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
    run["log"](f"attn_device_ms_per_step: {count} events by name, "
               f"{(fwd + bwd) / 1e6:.3f} ms in {t['steps']} steps; forward "
               f"{fwd / 1e6:.3f} ms, backward {bwd / 1e6:.3f} ms")
    return (fwd + bwd) / 1e6 / t["steps"]
