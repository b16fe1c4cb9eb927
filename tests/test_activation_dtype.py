"""A bfloat16 model's train step multiplies bfloat16 by bfloat16.

One op that returns float32 for bfloat16 inputs turns the residual stream,
and with it every later projection, the head, the loss and every weight
gradient, into float32 tensors: twice the bytes read, written and kept for
the backward pass, and nobody chose it (``models/llama.py``'s rotation did
that to the dense decoder until PR 29). A dtype has no counter; its witness
is the traced step. These cases trace ``create_train_step`` at the families'
tier-1 presets and read every ``dot_general``'s operand and result dtypes.
"""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.nn.functional.flash_attention import ATTENTION_SCOPE

BATCH, SEQ = 2, 32

_FAMILIES = {
    "llama_tiny": (models.LlamaForCausalLM, models.llama_tiny),
    "gpt2_tiny": (models.GPTForCausalLM, models.gpt2_tiny),
    "laguna_tiny": (models.LagunaForCausalLM, models.laguna_tiny),
}


def _step_matmuls(family, bf16):
    """(in the attention op?, lhs, rhs and result dtype, the three shapes) of
    every ``dot_general`` of the family's traced train step."""
    cls, preset = _FAMILIES[family]
    cfg = preset()
    paddle.seed(0)
    model = cls(cfg)
    if bf16:
        model = model.bfloat16()
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step, params, opt_state = models.create_train_step(model, opt)
    ids = np.zeros((BATCH, SEQ + 1), np.int32)
    traced = step.trace(params, opt_state, jax.random.key(0), ids[:, :-1],
                        ids[:, 1:], 1e-3)
    found = []

    def walk(jaxpr, stack):
        for e in jaxpr.eqns:
            # an inner jaxpr's name stacks start anew: carry the outer one
            here = f"{stack}/{e.source_info.name_stack}"
            if e.primitive.name == "dot_general":
                avals = [v.aval for v in (*e.invars, *e.outvars)]
                found.append((ATTENTION_SCOPE in here,
                              tuple(str(a.dtype) for a in avals),
                              tuple(tuple(a.shape) for a in avals)))
            for p in e.params.values():
                for inner in (p if isinstance(p, (list, tuple)) else (p,)):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        walk(inner, here)
    walk(traced.jaxpr.jaxpr, "")
    return cfg, found


def _router_shapes(cfg):
    """The shapes of Laguna's router product, forward and backward, each
    sorted (the weight's gradient comes out transposed): float32 on purpose
    (a top-k is a comparison; ``incubate/moe/dropless.py``)."""
    if not hasattr(cfg, "num_experts_per_tok"):
        return set()
    t, h, e = BATCH * SEQ, cfg.hidden_size, cfg.num_experts
    return {tuple(sorted(s)) for s in ((t, h), (h, e), (t, e))}


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_bf16_step_has_no_float32_matmul_operand(family):
    cfg, found = _step_matmuls(family, bf16=True)
    outside = [m for m in found if not m[0]]
    assert len(outside) >= 3 * 7     # fwd, dx, dw of a layer's projections
    router_shapes, router, stray = _router_shapes(cfg), [], []
    for _, dtypes, shapes in outside:
        if dtypes[:2] == ("bfloat16", "bfloat16") \
                and dtypes[2] in ("bfloat16", "float32"):
            continue     # float32 from bf16 operands is asked for by name
        if dtypes == ("float32",) * 3 and router_shapes == {
                tuple(sorted(s)) for s in shapes}:
            router.append(shapes)
        else:
            stray.append((dtypes, shapes))
    assert not stray, f"float32 reaches {len(stray)} matmuls: {stray[:4]}"
    sparse = sum(kind == models.laguna.SPARSE
                 for kind in getattr(cfg, "mlp_layer_types", ()))
    assert len(router) == 3 * sparse


def test_float32_step_is_float32_throughout():
    _, found = _step_matmuls("llama_tiny", bf16=False)
    assert len(found) >= 3 * 7
    assert {dtypes for _, dtypes, _ in found} == {("float32",) * 3}
