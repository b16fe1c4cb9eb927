"""The dropless expert layer (incubate/moe/dropless.py) and its grouped
matmul (ops/pallas/grouped_matmul.py), on the CPU in interpret mode, against
the benchmark's plain reference (benchmarks/reference/laguna.py: expert by
expert under a mask, no sort, no grouped product)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import glm4_moe_lite as ref_glm  # noqa: E402
from benchmarks.reference import laguna as ref  # noqa: E402
from benchmarks.reference import numerics  # noqa: E402
from paddle_tpu.core.autograd import tape_paused  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.incubate.moe import MOE_PLAN_TALLY, DroplessMoE  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig, LlamaMLP  # noqa: E402
from paddle_tpu.ops.pallas import grouped_matmul as gm  # noqa: E402

D, F, OF, K = 48, 24, 16, 4          # token width, expert width, experts, top-k
V = {"num_experts_per_tok": K, "moe_routed_scaling_factor": 2.5}
# the same layer as the GLM MoE lite family configures it: the choice by
# score + bias (reference/glm4_moe_lite.py makes the bias of "layer 1")
V_GLM = {"num_experts_per_tok": K, "routed_scaling_factor": 2.5,
         "n_routed_experts": OF, "dtype": "float32",
         "score_bias": {"scale": 0.1, "seed": 5}}


# -- the grouped matmul -------------------------------------------------------

@pytest.mark.parametrize("first,held,tm,expert,tiles", [
    (4, 4, 16, None, None),
    (0, 16, 32, None, {"fwd": (128, 128), "bwd_x": (128, 128),
                       "bwd_w": (128, 128)}),
    (0, 4, 8, [0] * 40 + [1] * 20 + [3] * 4, None),   # uneven, expert 2 empty
    (0, 4, 8, [0] * 64, None),                         # all on one expert
    (0, 4, 8, [9] * 64, None),                         # none lands here
], ids=["share", "tiled", "uneven", "one_expert", "none_here"])
def test_grouped_matmul_matches_the_dense_product(first, held, tm, expert,
                                                  tiles):
    kdim, ndim = (256, 128) if tiles else (64, 32)
    ks = jax.random.split(jax.random.key(1), 4)
    expert = jax.random.randint(ks[0], (200,), 0, OF) if expert is None \
        else jnp.asarray(expert)
    a = expert.shape[0]
    lay = gm.group_layout(expert, first, held, tm)
    rows = gm.padded_rows(a, held, tm)
    x = jax.random.normal(ks[1], (a, kdim))
    w = jax.random.normal(ks[2], (held, kdim, ndim))
    do = jax.random.normal(ks[3], (a, ndim))
    local = expert - first
    here = (local >= 0) & (local < held)

    live = lay.row_src < a

    def grouped(x, w):
        # where, not a product: the rows of dead tiles are never written,
        # in the product and in its gradient alike
        xp = jnp.where(live[:, None], x[jnp.minimum(lay.row_src, a - 1)], 0.)
        y = gm.grouped_matmul(xp, w, lay.tile_group, lay.n_tiles, tiles, True)
        y = y[jnp.minimum(lay.dest, rows - 1)]
        return jnp.where(here[:, None], y * do, 0.0).sum()

    def dense(x, w):
        y = jnp.einsum("ak,akn->an", x, w[jnp.clip(local, 0, held - 1)])
        return jnp.where(here[:, None], y * do, 0.0).sum()

    got = jax.jit(jax.value_and_grad(grouped, (0, 1)))(x, w)
    want = jax.value_and_grad(dense, (0, 1))(x, w)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-3)
    # a held expert nobody chose gets a tile of padding: its gradient is zero
    np.testing.assert_allclose(got[1][0], want[1][0], atol=1e-4)
    np.testing.assert_allclose(got[1][1], want[1][1], atol=1e-3)
    np.testing.assert_array_equal(
        lay.sizes, np.bincount(np.asarray(local)[np.asarray(here)],
                               minlength=held))


def test_group_layout_sorts_by_expert_onto_whole_tiles():
    expert = jax.random.randint(jax.random.key(3), (300,), 0, OF)
    first, held, tm = 8, 8, 16
    lay = jax.tree_util.tree_map(np.asarray,
                                 gm.group_layout(expert, first, held, tm))
    rows = gm.padded_rows(300, held, tm)
    here = (np.asarray(expert) >= first) & (np.asarray(expert) < first + held)
    assert (lay.dest[~here] == rows).all()
    dest = lay.dest[here]
    assert len(set(dest.tolist())) == dest.size          # one row each
    np.testing.assert_array_equal(lay.row_src[dest], np.nonzero(here)[0])
    assert (lay.row_src < 300).sum() == here.sum()       # the rest is padding
    # a row's tile is its expert's: no tile straddles two groups
    np.testing.assert_array_equal(lay.tile_group[dest // tm],
                                  np.asarray(expert)[here] - first)
    n = int(lay.n_tiles[0])
    padded = np.maximum(-(-lay.sizes // tm) * tm, tm)
    assert n == padded.sum() // tm and n * tm <= rows
    assert (np.diff(lay.tile_group[:n]) >= 0).all()
    assert (lay.tile_group[n:] == lay.tile_group[n - 1]).all()


def test_the_three_kernels_carry_their_names():
    lay = gm.group_layout(jnp.zeros((32,), jnp.int32), 0, 2, 8)
    x = jnp.ones((gm.padded_rows(32, 2, 8), 16))
    w = jnp.ones((2, 16, 8))
    text = str(jax.make_jaxpr(jax.grad(
        lambda x, w: gm.grouped_matmul(x, w, lay.tile_group, lay.n_tiles,
                                       None, True).sum(), (0, 1)))(x, w))
    for name in ("moe_gmm_fwd", "moe_gmm_bwd_x", "moe_gmm_bwd_w"):
        assert name in text


# -- the layer ----------------------------------------------------------------

def _weights(seed, held=OF):
    ks = jax.random.split(jax.random.key(seed), 8)
    n = lambda k, *s: jax.random.normal(k, s) * 0.3      # noqa: E731
    return {"router.weight": n(ks[0], D, OF),
            "experts.gate": n(ks[1], held, D, F),
            "experts.up": n(ks[2], held, D, F),
            "experts.down": n(ks[3], held, F, D),
            "shared.gate.weight": n(ks[4], D, F),
            "shared.up.weight": n(ks[5], D, F),
            "shared.down.weight": n(ks[6], F, D)}


def _layer(lp, first, held, shared=True, bias=None):
    """A DroplessMoE holding experts first .. first + held - 1 of ``lp``;
    with ``bias`` [OF] it selects by score + bias."""
    sh = LlamaMLP(LlamaConfig(hidden_size=D, intermediate_size=F)) \
        if shared else None
    layer = DroplessMoE(D, F, OF, K, held=(first, held), shared=sh,
                        routed_scale=2.5, row_tile=8,
                        score_bias=bias is not None)
    if bias is not None:
        layer.e_score_correction_bias._data = jnp.asarray(bias)
    layer.router_weight._data = lp["router.weight"]
    cut = slice(first, first + held)
    layer.gate_proj._data = lp["experts.gate"][cut]
    layer.up_proj._data = lp["experts.up"][cut]
    layer.down_proj._data = lp["experts.down"][cut]
    if shared:
        for name in ("gate", "up", "down"):
            getattr(sh, name + "_proj").weight._data = \
                lp[f"shared.{name}.weight"]
    return layer


def _apply(layer, x):
    with tape_paused():
        return layer(Tensor(x))._data


def _out_and_dx(fn, x, do):
    def run(x):
        out, vjp = jax.vjp(fn, x)
        return out, vjp(do)[0]
    return jax.jit(run)(x)


def _reference(t, lp, first, held):
    v = dict(V, expert_share={"first": first, "held": held, "of": OF})
    cut = slice(first, first + held)
    lp = dict(lp, **{k: lp[k][cut] for k in ("experts.gate", "experts.up",
                                             "experts.down")})
    return ref._experts(t, lp, v, numerics.Exact())


def test_layer_matches_the_reference_on_a_share():
    lp = _weights(5)
    x = jax.random.normal(jax.random.key(6), (2, 32, D))
    do = jax.random.normal(jax.random.key(7), (2, 32, D))
    layer = _layer(lp, 4, 8)
    got, dx = _out_and_dx(lambda x: _apply(layer, x), x, do)
    want, dx_ref = _out_and_dx(lambda x: _reference(x, lp, 4, 8), x, do)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(dx, dx_ref, atol=1e-4)


@pytest.mark.parametrize("family,width", [("laguna", 4), ("glm", 2)])
def test_the_shares_add_up_to_the_uncut_layer(family, width):
    """What the shares give (four of four experts; for the GLM family eight
    of two, each selecting by score + bias), the shared expert counted once,
    is what the uncut reference (held = of) gives for the whole layer:
    forward and the gradient of the input."""
    lp = _weights(11)
    x = jax.random.normal(jax.random.key(12), (2, 32, D))
    do = jax.random.normal(jax.random.key(13), (2, 32, D))
    bias = ref_glm.score_bias(V_GLM, 1) if family == "glm" else None
    shares = [_layer(lp, first, width, shared=(first == 0), bias=bias)
              for first in range(0, OF, width)]

    def cut(x):
        return sum(_apply(layer, x) for layer in shares)

    def uncut(x):
        if family == "glm":
            return ref_glm._experts(x, lp, V_GLM, numerics.Exact(), 1)
        return ref._experts(x, lp, V | {"num_experts": OF}, numerics.Exact())

    def cut_with_loads(x):
        out = cut(x)
        return out, [layer.expert_load._data for layer in shares]

    got, dx = _out_and_dx(cut, x, do)
    want, dx_ref = _out_and_dx(uncut, x, do)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(dx, dx_ref, atol=2e-4)
    # every assignment landed on exactly one share
    _, loads = jax.jit(cut_with_loads)(x)
    assert sum(int(v.sum()) for v in loads) == 2 * 32 * K


def test_the_bias_chooses_and_the_scores_weigh():
    """A bias that flips a choice changes WHO is chosen and leaves every
    chosen expert's weight a function of the scores alone: its score over
    the sum of the chosen scores, whatever the bias was."""
    lp = _weights(41)
    x = jax.random.normal(jax.random.key(42), (64, D))
    scores = np.asarray(jax.nn.sigmoid(x @ lp["router.weight"]))
    plain = _layer(lp, 0, OF)
    bias = np.zeros(OF, np.float32)
    bias[3] = 1.0           # expert 3 is always chosen, expert 5 never
    bias[5] = -1.0
    biased = _layer(lp, 0, OF, bias=bias)
    top_p, w_p = plain.route(x, lp["router.weight"])
    top_b, w_b = biased.route(x, lp["router.weight"],
                              biased.e_score_correction_bias._data)
    top_p, top_b = np.asarray(top_p), np.asarray(top_b)
    assert (top_b == 3).any(-1).all() and not (top_b == 5).any()
    assert not (top_p == 3).any(-1).all() and (top_p == 5).any()
    # the chosen are the k largest of score + bias ...
    want = np.sort(np.argsort(-(scores + bias), axis=-1)[:, :K], -1)
    np.testing.assert_array_equal(np.sort(top_b, -1), want)
    # ... and their weights know nothing of it
    chosen = np.take_along_axis(scores, top_b, -1)
    np.testing.assert_allclose(w_b, chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-6)
    # the layer against the reference, on a share, forward and backward
    v = dict(V_GLM, expert_share={"first": 4, "held": 8, "of": OF})
    made = ref_glm.score_bias(v, 1)
    layer = _layer(lp, 4, 8, bias=made)
    cut = dict(lp, **{k: lp[k][4:12] for k in ("experts.gate", "experts.up",
                                               "experts.down")})
    x3 = x.reshape(2, 32, D)
    do = jax.random.normal(jax.random.key(43), x3.shape)
    got, dx = _out_and_dx(lambda x: _apply(layer, x), x3, do)
    want, dx_ref = _out_and_dx(
        lambda x: ref_glm._experts(x, cut, v, numerics.Exact(), 1), x3, do)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(dx, dx_ref, atol=1e-4)
    # the bias moved choices at this scale (else the check above is idle)
    assert (np.sort(np.asarray(layer.route(
        x, lp["router.weight"], jnp.asarray(made))[0]), -1)
        != np.sort(top_p, -1)).any()


def test_without_the_bias_the_layer_is_what_it_was():
    """``score_bias=False`` (Laguna): no buffer, the lowered layer takes
    the same six operands and holds no trace of a bias; and a zero bias
    gives bit for bit the same output as none."""
    lp = _weights(51)
    x = jax.random.normal(jax.random.key(52), (2, 32, D))
    plain, zero = _layer(lp, 4, 8), _layer(lp, 4, 8, bias=np.zeros(OF))
    assert plain.e_score_correction_bias is None
    assert [n for n, _ in plain.named_buffers()] == []
    assert [n for n, _ in zero.named_buffers()] == [
        "e_score_correction_bias"]
    assert "e_score_correction_bias" not in dict(zero.named_parameters())
    np.testing.assert_array_equal(jax.jit(lambda x: _apply(plain, x))(x),
                                  jax.jit(lambda x: _apply(zero, x))(x))
    text = jax.jit(lambda x: _apply(plain, x)).lower(x).as_text()
    biased = jax.jit(lambda x: _apply(zero, x)).lower(x).as_text()
    assert "tensor<16xf32>" not in text and "tensor<16xf32>" in biased


def test_no_token_is_dropped_under_uneven_routing():
    """Router weights that send every token to held experts 4 and 5 and none
    to held expert 6: the layer still computes every assignment (it agrees
    with the reference, which has no buffer to overflow)."""
    lp = _weights(21)
    wr = np.array(lp["router.weight"]) * 0.05
    wr[0, 4] = wr[0, 5] = 3.0
    wr[0, 6] = -30.0
    lp["router.weight"] = jnp.asarray(wr)
    x = jax.random.normal(jax.random.key(22), (2, 32, D)).at[..., 0].set(4.0)
    layer = _layer(lp, 4, 4)
    do = jnp.ones_like(x)
    got, dx = _out_and_dx(lambda x: _apply(layer, x), x, do)
    want, dx_ref = _out_and_dx(lambda x: _reference(x, lp, 4, 4), x, do)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(dx, dx_ref, atol=2e-4)
    load = np.asarray(jax.jit(
        lambda x: (_apply(layer, x), layer.expert_load._data)[1])(x))
    assert load[0] == load[1] == 64 and load[2] == 0
    assert load.max() > 1.5 * load.mean()


def test_moe_plan_event_and_tally():
    from paddle_tpu.profiler import tracing

    layer = _layer(_weights(31), 4, 8)
    x = jax.random.normal(jax.random.key(32), (2, 16, D))
    key = (8, OF, K, 32, 8)
    before = MOE_PLAN_TALLY[key]
    tracing.reset_tracing()
    tracing.enable_tracing()
    try:
        jax.jit(lambda x: _apply(layer, x))(x)
        events = [e["args"] for e in tracing.snapshot_events()
                  if e["name"] == "moe::plan"]
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    assert MOE_PLAN_TALLY[key] == before + 1 and len(events) == 1
    a = events[0]
    assert (a["held"], a["of"], a["first"], a["top_k"], a["tokens"]) == \
        (8, OF, 4, K, 32)
    assert a["expected_rows"] == 32 * K * 8 // OF
    assert a["buffer_rows"] == gm.padded_rows(32 * K, 8, 8)
    assert a["row_tile"] == 8 and a["gate_up_tile"] == "48x48"
    assert a["score_bias"] is False


def test_held_must_be_a_range_of_the_experts():
    with pytest.raises(ValueError):
        DroplessMoE(D, F, OF, K, held=(12, 8))
    with pytest.raises(ValueError):
        DroplessMoE(D, F, 2, K)
