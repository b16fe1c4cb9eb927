"""Chosen-block attention's share of its roofline: the least time the chip
could take for the attention the traced steps require, every ``minicpm4``
layer's queries over the keys of the blocks the rule lets them choose
(``harness/sala_work.py::sparse_attention``: forward and backward, nothing
recomputed, no key outside the chosen blocks), over the summed device time of
the operations whose instruction name holds ``sparse_attn_``. The kernels
sweep every causal key tile some query of the tile chose from, so with
scattered choices the time is dense causal attention's and the share reads
the sparsity the sweep could not use. The reader says which side bounds
it."""
from benchmarks.harness import sala_work

NAME = "sparse_attn_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
PREFIX = "sparse_attn_"
KIND = "minicpm4"


def read(run):
    cell = run["cell"]
    v = cell.config.values
    b, s = cell.params["batch"], cell.params["seq"]
    reference = run["bench"].module("reference", v["family"])
    nbar = reference.mean_attended_keys(s, v["sparse_config"])
    return sala_work.read_roofline(
        run, NAME, PREFIX, KIND, sala_work.sparse_attention(v, b, s, nbar),
        note=f" at {nbar:.1f} keys a query")
