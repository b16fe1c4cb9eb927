"""The grouped matmuls' share of their roofline: the least time the chip
could take for the grouped products the traced steps require (every sparse
layer's gate-and-up and down products over the held experts, forward and
backward, at the EXPECTED assignments a layer, nothing recomputed and no
padding row counted; ``harness/sparse_flops.py``) over the summed device time
of the ``moe_gmm`` events in the traced window. The reader says which side
bounds it. The time holds the recomputed forward and the padded rows; the
required work holds neither. Where the routing leaves the expectation the
share leaves its meaning (``harness/sparse_flops.py``)."""
import re

from benchmarks.harness import sparse_flops

NAME = "moe_gmm_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
PATTERN = re.compile(r"moe_gmm")


def read(run):
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    spent = [e - s for s, e, name in t["ops"]
             if PATTERN.search(name.partition(" = ")[0])]
    seconds = sum(spent) / 1e9
    if not spent or seconds <= 0:
        return None
    cell = run["cell"]
    v = cell.config.values
    n = v["num_hidden_layers"]
    sparse = sum(1 for m in v["mlp_layer_types"][:n] if m == "sparse")
    tokens = cell.params["batch"] * cell.params["seq"]
    ops, moved = sparse_flops.grouped_products(v, tokens)
    least, side = sparse_flops.least_seconds(
        t["steps"] * sparse * ops, t["steps"] * sparse * moved, run["peaks"])
    run["log"](f"moe_gmm_roofline: {len(spent)} events, {seconds * 1e3:.3f} "
               f"ms in {t['steps']} steps; least time {least * 1e3:.3f} ms "
               f"at {sparse_flops.expected_assignments(v, tokens):.0f} "
               f"expected assignments a layer: {side}-bound")
    return 100.0 * least / seconds
