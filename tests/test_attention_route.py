"""Which implementation a dense attention call gets: one table over
``ops/pallas/flash_attention.py::attention_route``, a pure function of the
call's static facts. No row runs a kernel. Every expected route is what the
dispatch answered for that call before the function existed (PR 30 read them
from the parent's ``_attention_pallas`` at default flags); the rule's name is
asserted too, so a reordering of the rules shows.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import dispatch, flags
from paddle_tpu.ops.pallas.flash_attention import (_GQA_XLA_SCORE_BYTES,
                                                   attention_route)

BF16, F32 = jnp.bfloat16, jnp.float32


def qk(b, sq, hq, hk, d=128, sk=None, dtype=BF16):
    """q [B,Sq,Hq,D] and k [B,Sk,Hk,D] as shapes: the function reads no
    values."""
    return (jax.ShapeDtypeStruct((b, sq, hq, d), dtype),
            jax.ShapeDtypeStruct((b, sk or sq, hk, d), dtype))


def bias(*shape):
    return np.zeros(shape, np.float32)


# a causal call on the chip without bias, dropout, window or mesh; a row
# names what it changes
CALL = dict(bias=None, dropout_rate=0.0, has_key=False, causal=True,
            window=None, meshed=False, on_tpu=True, force_interpret=False)
FORCED = dict(on_tpu=False, force_interpret=True)

# scores of exactly the budget: 100 * 8 * 1250 * 1125 * 4 bytes
AT_BUDGET = qk(100, 1250, 8, 4, sk=1125)
assert 100 * 8 * 1250 * 1125 * 4 == _GQA_XLA_SCORE_BYTES == 4_500_000_000

ROWS = [
    # the attention shapes of the benchmark's cells
    ("cell_gpt2s_b32_s1024_mha64", qk(32, 1024, 12, 12, 64), {},
     "kernel", "default"),
    ("cell_mistral_b4_s4096_gqa_8p6GB", qk(4, 4096, 32, 8), {},
     "kernel", "default"),
    ("cell_laguna_full_b2_s8192_48_8", qk(2, 8192, 48, 8), {},
     "kernel", "default"),
    ("cell_laguna_window512_b2_s8192_64_8", qk(2, 8192, 64, 8),
     dict(window=512), "kernel", "window"),
    # grouped heads against the score budget
    ("gqa_scores_at_budget", AT_BUDGET, {}, "xla", "gqa_scores_fit"),
    ("gqa_scores_one_query_row_over", qk(100, 1251, 8, 4, sk=1125), {},
     "kernel", "default"),
    ("gqa_b1_s2048_on_chip", qk(1, 2048, 32, 8), {},
     "xla", "gqa_scores_fit"),
    ("gqa_b1_s2048_forced_interpret", qk(1, 2048, 32, 8), FORCED,
     "kernel", "default"),
    ("gqa_b2_s4096_head64", qk(2, 4096, 32, 8, 64), {},
     "xla", "gqa_scores_fit"),
    ("gqa_b8_s8192_over_budget", qk(8, 8192, 32, 8, 64), {},
     "kernel", "default"),
    ("gqa_decode_sq1_kv2048", qk(8, 1, 32, 8, sk=2048), {},
     "xla", "gqa_scores_fit"),
    ("mha_scores_fit", qk(1, 2048, 32, 32), {}, "kernel", "default"),
    ("mha_decode_sq1_kv4096", qk(8, 1, 32, 32, sk=4096), {},
     "kernel", "default"),
    # the budget counts float32 scores whatever the operands' dtype
    ("float32_mistral_shape", qk(4, 4096, 32, 8, dtype=F32), {},
     "kernel", "default"),
    ("float32_gqa_b1_s2048", qk(1, 2048, 32, 8, dtype=F32), {},
     "xla", "gqa_scores_fit"),
    # kv length on the chip
    ("kv_1023_on_chip", qk(2, 1023, 8, 8), {}, "xla", "short_kv"),
    ("kv_1024_on_chip", qk(2, 1024, 8, 8), {}, "kernel", "default"),
    ("cross_sq128_kv1024_on_chip", qk(2, 128, 8, 8, sk=1024), {},
     "kernel", "default"),
    ("cross_sq4096_kv512_on_chip", qk(2, 4096, 8, 8, sk=512), {},
     "xla", "short_kv"),
    ("kv_128_forced_interpret", qk(2, 128, 8, 8), FORCED,
     "kernel", "default"),
    # head size
    ("head_256", qk(2, 1024, 8, 8, 256), {}, "kernel", "default"),
    ("head_264", qk(2, 1024, 8, 8, 264), {}, "xla", "head_dim"),
    # bias layouts
    ("bias_per_key", qk(2, 1024, 8, 8), dict(bias=bias(2, 1, 1, 1024)),
     "kernel", "default"),
    ("bias_rank2_sq_sk", qk(1, 1024, 2, 2), dict(bias=bias(1024, 1024)),
     "kernel", "default"),
    ("bias_half_the_keys", qk(2, 1024, 8, 8),
     dict(bias=bias(2, 1, 1, 512)), "xla", "bias_layout"),
    ("bias_other_head_count", qk(2, 1024, 8, 8),
     dict(bias=bias(1, 4, 1, 1024)), "xla", "bias_layout"),
    ("bias_layout_before_head_dim", qk(2, 1024, 8, 8, 264),
     dict(bias=bias(2, 1, 1, 512)), "xla", "bias_layout"),
    # dropout
    ("dropout_with_key", qk(2, 1024, 8, 8),
     dict(dropout_rate=0.1, has_key=True), "kernel", "default"),
    ("dropout_without_key", qk(2, 1024, 8, 8), dict(dropout_rate=0.1),
     "xla", "dropout_without_key"),
    ("dropout_0_without_key", qk(2, 1024, 8, 8), {}, "kernel", "default"),
    ("dropout_with_key_forced_interpret", qk(1, 256, 2, 2),
     dict(dropout_rate=0.1, has_key=True, **FORCED), "kernel", "default"),
    # GSPMD-owned mesh axes around the call
    ("mesh_plain", qk(4, 2048, 8, 8), dict(meshed=True),
     "kernel", "default"),
    ("mesh_with_bias", qk(4, 2048, 8, 8),
     dict(meshed=True, bias=bias(4, 1, 1, 2048)),
     "xla", "mesh_with_bias_or_dropout"),
    ("mesh_with_dropout", qk(4, 2048, 8, 8),
     dict(meshed=True, dropout_rate=0.1, has_key=True),
     "xla", "mesh_with_bias_or_dropout"),
    ("mesh_gqa_scores_fit", qk(4, 2048, 32, 8), dict(meshed=True),
     "xla", "gqa_scores_fit"),
    # chip_smoke.py::leg_four_chip: b4 s1024 at llama_7b's heads, mesh (2, 2)
    ("four_chip_leg_b4_s1024_32_32", qk(4, 1024, 32, 32),
     dict(meshed=True), "kernel", "default"),
    # windows
    ("window_with_bias", qk(2, 2048, 8, 8),
     dict(window=512, bias=bias(2, 1, 1, 2048)),
     "xla", "window_with_bias_or_not_causal"),
    ("window_not_causal", qk(2, 2048, 8, 8),
     dict(window=512, causal=False),
     "xla", "window_with_bias_or_not_causal"),
    ("window_gqa_scores_would_fit", qk(1, 2048, 32, 8), dict(window=512),
     "kernel", "window"),
    ("window_short_kv_on_chip", qk(2, 512, 8, 8), dict(window=128),
     "xla", "short_kv"),
    ("window_under_mesh", qk(4, 2048, 32, 8),
     dict(window=512, meshed=True), "kernel", "window"),
    ("window_forced_interpret", qk(1, 256, 4, 2),
     dict(window=64, **FORCED), "kernel", "window"),
    # off the chip the kernels run only when asked to
    ("interpret_not_forced", qk(2, 2048, 8, 8), dict(on_tpu=False),
     "xla", "interpret_not_forced"),
    ("interpret_not_forced_window", qk(2, 2048, 8, 8),
     dict(on_tpu=False, window=512), "xla", "interpret_not_forced"),
    ("not_causal_mha", qk(2, 2048, 8, 8), dict(causal=False),
     "kernel", "default"),
]


@pytest.mark.parametrize("shapes,changed,impl,rule",
                         [r[1:] for r in ROWS], ids=[r[0] for r in ROWS])
def test_attention_route(shapes, changed, impl, rule):
    q, k = shapes
    call = {**CALL, **changed}
    assert attention_route(q, k, call.pop("bias"), **call) == (impl, rule)


FUSED_OPS = ["flash_attention", "rms_norm", "layer_norm",
             "softmax_xent_core"]


def test_fused_ops_are_the_registered_ones():
    dispatch._load_pallas_impls()
    assert sorted(n for n, d in dispatch.OPS.items()
                  if "pallas" in d.impls) == sorted(FUSED_OPS)


@pytest.mark.parametrize("op", FUSED_OPS)
def test_select_impl_follows_use_pallas_kernels(op):
    impls = dispatch.OPS[op].impls
    assert dispatch.select_impl(op) is impls["pallas"]
    flags.set_flags({"use_pallas_kernels": False})
    try:
        assert dispatch.select_impl(op) is impls["xla"]
    finally:
        flags.set_flags({"use_pallas_kernels": True})
