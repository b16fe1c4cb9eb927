"""The grouped matmuls' share of their roofline in a family that places its
sparse layers by Qwen's keys (``decoder_sparse_step``, ``mlp_only_layers``:
layer i is sparse when it is not in ``mlp_only_layers`` and ``(i + 1) %
decoder_sparse_step == 0``). The count is ``moe_gmm_roofline``'s, which reads
``mlp_layer_types`` instead: every sparse layer's gate-and-up and down products
over the held experts, forward and backward, at the EXPECTED assignments a
layer (``harness/sparse_flops.py``, nothing recomputed, no padding row), over
the summed device time of the ``moe_gmm`` events. The reader says which side
bounds it; where the routing leaves the expectation the share leaves its
meaning (``harness/sparse_flops.py``). Nothing where the family has no
``decoder_sparse_step`` or the trace no such event."""
from benchmarks.harness import sala_work, sparse_flops

NAME = "qwen_moe_gmm_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
PREFIX = "moe_gmm"


def sparse_layers(values: dict) -> int:
    step, dense = values["decoder_sparse_step"], values["mlp_only_layers"]
    return sum(1 for i in range(values["num_hidden_layers"])
               if i not in dense and (i + 1) % step == 0)


def read(run):
    t = run["trace"]
    v = run["cell"].config.values
    if not t or not t["steps"] or "decoder_sparse_step" not in v:
        return None
    seconds, count, _ = sala_work.kernel_seconds(t, PREFIX)
    if not count or seconds <= 0:
        return None
    tokens = run["cell"].params["batch"] * run["cell"].params["seq"]
    ops, moved = sparse_flops.grouped_products(v, tokens)
    n = t["steps"] * sparse_layers(v)
    least, side = sparse_flops.least_seconds(n * ops, n * moved, run["peaks"])
    run["log"](f"{NAME}: {count} events, {seconds * 1e3:.3f} ms in "
               f"{t['steps']} steps; least time {least * 1e3:.3f} ms at "
               f"{sparse_flops.expected_assignments(v, tokens):.0f} expected "
               f"assignments a layer: {side}-bound")
    return 100.0 * least / seconds
