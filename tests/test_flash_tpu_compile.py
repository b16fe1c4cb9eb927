"""The flash kernels at the tile plan's tiles and sub-tiles, and the grouped
matmuls at the expert layer's, compiled for a described v5e.

Interpret mode cannot say whether Mosaic takes a tile: whether its blocks
fit the scoped VMEM the call asks for (``_call_params``), whether a
384-long block of an unaligned length tiles. The TPU's compiler is installed
here and compiles for a chip that is described and not attached, so these
cases compile forward and backward at real widths: what the chip's compiler
would refuse, it refuses here. Nothing runs; no time is read.

The topology is described inside a fixture (never while a module is
imported): only the worker that is handed this file loads the TPU's library.
"""
import collections

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import gated_delta as gd
from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.ops.pallas import linear_attention as la
from paddle_tpu.ops.pallas import sparse_attention as sa


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def production_numerics():
    """conftest.py asks for "highest" matmuls everywhere; the kernels are
    compiled as the training path compiles them. The persistent compile
    cache is off: an entry written for a described chip cannot be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    before = (jax.config.jax_default_matmul_precision,
              jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_default_matmul_precision", None)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_default_matmul_precision", before[0])
    jax.config.update("jax_enable_compilation_cache", before[1])
    compilation_cache.reset_cache()


# name: (b, s, hq, hk, d, q/k dtype, v dtype, bias, segments, dropout)
_CASES = {
    # the benchmark cells' own attention shapes
    "gpt2s_cell": (32, 1024, 12, 12, 64, "bfloat16", "bfloat16", False,
                   False, 0.0),
    "mistral_cell": (4, 4096, 32, 8, 128, "bfloat16", "bfloat16", False,
                     False, 0.0),
    # what the cell sent until the rotation kept q's and k's dtype (PR 29);
    # other callers may still send it
    "mistral_cell_f32qk": (4, 4096, 32, 8, 128, "float32", "bfloat16",
                           False, False, 0.0),
    # everything that rides along, at once: a full bias (and its dbias
    # tiles out of dq), and dropout
    "bias_dropout_d128": (1, 2048, 4, 4, 128, "bfloat16", "bfloat16", True,
                          False, 0.1),
    "segments_d64": (1, 2048, 4, 4, 64, "bfloat16", "bfloat16", False, True,
                     0.0),
    # 1100 pads to 1152 = 9 x 128: the plan's 384-long blocks
    "s1100_unaligned": (1, 1100, 4, 4, 64, "float32", "float32", False,
                        False, 0.0),
    # the widest head the kernels take, all float32: the budget shrinks
    # the tile
    "d256_f32": (1, 4096, 4, 2, 256, "float32", "float32", False, False,
                 0.0),
    # glm47-flash-train-s8192: latent attention expanded to 20 heads over 20
    # of 192 + 64 = 256, values 256 too
    "glm_cell_d256": (2, 8192, 20, 20, 256, "bfloat16", "bfloat16", False,
                      False, 0.0),
}


@pytest.mark.parametrize("case", list(_CASES))
def test_plan_tiles_compile_for_v5e(case, one_chip, production_numerics):
    b, s, hq, hk, d, qk_dt, v_dt, with_bias, with_seg, rate = _CASES[case]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)
    q = arg((b, s, hq, d), qk_dt)
    k = arg((b, s, hk, d), qk_dt)
    v = arg((b, s, hk, d), v_dt)
    bias = arg((b, hq, s, s), "float32") if with_bias else None
    seg = arg((b, s), "int32") if with_seg else None
    seed = arg((1,), "int32")
    scale = float(d) ** -0.5

    def loss(q, k, v, bias, seg, seed):
        out = fa.flash_attention_ext(q, k, v, bias, seed, seg, seg, True,
                                     scale, rate, None, None, False)
        return out.astype(jnp.float32).sum()

    before = dict(fa.TILE_PLAN_TALLY)
    argnums = (0, 1, 2, 3) if with_bias else (0, 1, 2)
    compiled = jax.jit(jax.grad(loss, argnums)).lower(
        q, k, v, bias, seg, seed).compile()
    lowered = {key for key, n in fa.TILE_PLAN_TALLY.items()
               if n > before.get(key, 0)}
    plan = fa.tile_plan(
        s, s, d, jnp.dtype(qk_dt).itemsize, jnp.dtype(qk_dt).itemsize,
        jnp.dtype(v_dt).itemsize, bias_bytes=4 if with_bias else 0,
        dbias=with_bias, segments=with_seg, dropout=rate > 0.0,
        causal=not with_seg, rep=1 if with_bias else hq // hk)
    # a full bias keeps the split kernels (its dbias tiles come out of the
    # dq kernel's grid); every other case is the forward and one backward
    # kernel
    assert (plan.split_by == "dbias") == with_bias
    assert compiled.as_text().count("tpu_custom_call") >= len(
        plan.lowered())
    # the tally's key is (name, bq, bk, sub_q, sub_k): what compiled is the
    # plan's tile walked in the plan's sub-tile. Under segment ids the
    # diagonals ride in the segment words, the kernels see no causal edge
    # and nothing is padded here: the plan says one piece
    tiles = plan.lowered()
    if with_seg:
        assert all(t[2:4] == t[:2] for t in tiles.values()), plan
    assert lowered == {(fa._KERNEL_NAMES[kernel],) + t[:4]
                       for kernel, t in tiles.items()}
    if case.endswith("_cell"):
        assert all(t[2:4] != t[:2] for t in tiles.values()), plan


# laguna-xs2-train-s8192's attention: name: (b, s, hq, hk, d, window)
_WINDOW_CASES = {
    "laguna_window_layer": (2, 8192, 64, 8, 128, 512),
    "laguna_full_layer": (2, 8192, 48, 8, 128, None),
    # a window that is no multiple of 128, on a length that is none either
    "window_200_s1100": (1, 1100, 6, 2, 64, 200),
}


@pytest.mark.parametrize("case", list(_WINDOW_CASES))
def test_windowed_plan_tiles_compile_for_v5e(case, one_chip,
                                             production_numerics):
    b, s, hq, hk, d, window = _WINDOW_CASES[case]

    def arg(shape, dtype="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    def loss(q, k, v, seed):
        out = fa.flash_attention_ext(q, k, v, None, seed, None, None, True,
                                     float(d) ** -0.5, 0.0, None, None,
                                     False, window)
        return out.astype(jnp.float32).sum()

    before = dict(fa.TILE_PLAN_TALLY)
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        arg((b, s, hq, d)), arg((b, s, hk, d)), arg((b, s, hk, d)),
        arg((1,), "int32")).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    lowered = {key for key, n in fa.TILE_PLAN_TALLY.items()
               if n > before.get(key, 0)}
    plan = fa.tile_plan(s, s, d, window=window, rep=hq // hk)
    names = fa._KERNEL_NAMES if window is None else fa._WIN_KERNEL_NAMES
    assert plan.bwd is not None
    tiles = plan.lowered()
    assert lowered == {(names[kernel],) + t[:4]
                       for kernel, t in tiles.items()}
    if window is not None:     # the tile stops at twice the window, a
        # piece of its walk at the window
        wide = -(-window // 128) * 128
        assert max(max(t[:4]) for t in plan[:4]) <= 2 * wide
        assert max(t[2:4] for t in plan[:4]) <= (wide, wide)
    if case.startswith("laguna"):
        assert all(t[2:4] != t[:2] for t in tiles.values()), plan


# each flash cell's attention call: (b, s, hq, hk, d, window)
_CELL_CALLS = {
    "gpt2s-train-s1024": (32, 1024, 12, 12, 64, None),
    "mistral7b-l2-train-s4096": (4, 4096, 32, 8, 128, None),
    "laguna-xs2-train-s8192.full": (2, 8192, 48, 8, 128, None),
    "laguna-xs2-train-s8192.window": (2, 8192, 64, 8, 128, 512),
    "glm47-flash-train-s8192": (2, 8192, 20, 20, 256, None),
}


@pytest.mark.parametrize("cell", list(_CELL_CALLS))
def test_a_cells_backward_is_one_kernel_its_readers_find(
        cell, one_chip, production_numerics):
    """The compiled forward and backward of a cell's call hold two Mosaic
    calls: the forward and the one backward kernel. The benchmark's readers
    find both by their own patterns in the instruction as a trace names it:
    ``flash_`` (``flash_win_`` under a window) in its own name, an operand
    ``[B*Hq, S, D]`` with the target ``tpu_custom_call`` (which latent
    attention's reader also takes), and ``bwd`` in the backward's name."""
    import re

    from benchmarks.harness import mla_events
    from benchmarks.metrics import (attn_device_ms_per_step,
                                    flash_attn_roofline, window_attn_roofline)

    b, s, hq, hk, d, window = _CELL_CALLS[cell]

    def arg(shape, dtype="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    def loss(q, k, v, seed):
        return fa.flash_attention_ext(
            q, k, v, None, seed, None, None, True, float(d) ** -0.5, 0.0,
            None, None, False, window).astype(jnp.float32).sum()
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        arg((b, s, hq, d)), arg((b, s, hk, d)), arg((b, s, hk, d)),
        arg((1,), "int32")).compile().as_text()
    calls = [ln.strip() for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    heads = [ln.partition(" = ")[0] for ln in calls]
    assert len(calls) == 2, heads
    shaped = re.compile(flash_attn_roofline.PATTERN.format(bh=b * hq, s=s,
                                                           d=d))
    assert shaped.pattern == mla_events.PATTERN.format(bh=b * hq, s=s, d=d)
    named = window_attn_roofline.PATTERN if window is not None else \
        attn_device_ms_per_step.PATTERN
    prefix = "%flash_win_" if window is not None else "%flash_"
    assert sorted(h.split(".")[0] for h in heads) == [prefix + "bwd",
                                                      prefix + "fwd"]
    for line, head in zip(calls, heads):
        assert shaped.search(line) and named.search(head), line
    assert [bool(mla_events.BACKWARD.search(h)) for h in sorted(heads)] \
        == [True, False]


@pytest.mark.parametrize("k,n", [(2048, 1024), (512, 2048)],
                         ids=["gate_up", "down"])
def test_grouped_matmuls_compile_for_v5e(k, n, one_chip, production_numerics):
    """The cell's two grouped products, forward and both backward products,
    over buffers sized for the worst routing of 16,384 tokens x top-8 onto
    32 held experts."""
    held, tm = 32, gm.ROW_TILE
    rows = gm.padded_rows(16384 * 8, held, tm)

    def arg(shape, dtype="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    def loss(x, w, tile_group, n_tiles):
        return gm.grouped_matmul(x, w, tile_group, n_tiles,
                                 interpret=False).astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        arg((rows, k)), arg((held, k, n)), arg((rows // tm,), "int32"),
        arg((1,), "int32")).compile().as_text()
    for name in ("moe_gmm_fwd", "moe_gmm_bwd_x", "moe_gmm_bwd_w"):
        assert name in text, name


@pytest.mark.parametrize("policy", ["flash_saveable", "full"])
def test_recomputed_laguna_step_holds_one_forward_kernel_a_layer(
        policy, one_chip, production_numerics, monkeypatch):
    """What Mosaic is handed, not only the jaxpr: a two-layer Laguna step
    (one full, one window layer, each recomputed in the backward pass)
    compiled for the described chip holds one ``flash_fwd`` and one
    ``flash_win_fwd`` custom call when the blocks keep their flash calls'
    output and statistics (the family's default), and two of each when
    they replay everything."""
    import re

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.ops.pallas import common

    # the step asks the backend for its route; the test steers it
    monkeypatch.setattr(common, "on_tpu", lambda: True)
    paddle.seed(0)
    cfg = models.laguna_tiny(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        head_dim=128, layer_types=("full_attention", "sliding_attention"),
        mlp_layer_types=("dense", "dense"), num_heads_per_layer=(2, 4),
        sliding_window=256, max_position_embeddings=1024,
        lm_ce="blockwise", use_recompute=True, recompute_policy=policy)
    model = models.LagunaForCausalLM(cfg).bfloat16()
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step, params, opt_state = models.create_train_step(model, opt)

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    ids = described(np.zeros((1, 1024), np.int32))
    text = step.lower(
        jax.tree.map(described, params), jax.tree.map(described, opt_state),
        described(jax.random.key(0)), ids, ids, 1e-3).compile().as_text()
    calls = collections.Counter(
        re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*tpu_custom_call", text))
    twice = 1 if policy == "flash_saveable" else 2
    assert {k: n for k, n in calls.items() if "flash" in k} == {
        "flash_fwd": twice, "flash_win_fwd": twice,
        "flash_bwd": 1, "flash_win_bwd": 1}


# minicpm-sala-train-s12288's two mixers at the cell's shapes (B 1, S 12288,
# heads of 128), and a sequence the chunk does not divide
@pytest.mark.parametrize("case,s,tiles", [
    ("sala_cell_plan", 12288, None),
    ("sala_cell_512", 12288, sa.SparseTiles(512, 512)),
    ("s2048", 2048, None),
])
def test_chosen_block_attention_compiles_for_v5e(case, s, tiles, one_chip,
                                                 production_numerics):
    def arg(shape, dtype="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)
    sc = sa.SparseConfig(dense_len=1024)

    def loss(q, k, v):
        chosen = sa.select_blocks(q, k, sc)
        out = sa.sparse_attention(q, k, v, chosen, 128 ** -0.5,
                                  sc.block_size, tiles, False)
        return out.astype(jnp.float32).sum()
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        arg((1, s, 32, 128)), arg((1, s, 2, 128)),
        arg((1, s, 2, 128))).compile().as_text()
    for name in ("sparse_attn_fwd", "sparse_attn_bwd_dq",
                 "sparse_attn_bwd_dkv"):
        assert name in text, name
    if case == "sala_cell_plan":
        assert sa.sparse_tile_plan(s, sc.block_size) == (1024, 1024)


@pytest.mark.parametrize("s,chunk", [(12288, None), (12288, 512),
                                     (1100, None)])
def test_linear_attention_compiles_for_v5e(s, chunk, one_chip,
                                           production_numerics):
    def arg(shape, dtype="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    def loss(q, k, v, rates):
        return la.linear_attention(q, k, v, rates, 128 ** -0.5, chunk,
                                   False).astype(jnp.float32).sum()
    x = arg((1, s, 32, 128))
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        x, x, x, arg((32,), "float32")).compile().as_text()
    assert "linear_attn_fwd" in text and "linear_attn_bwd" in text


@pytest.mark.parametrize("s,chunk", [(16384, None), (16384, 64),
                                     (1100, None)])
def test_gated_delta_compiles_for_v5e(s, chunk, one_chip,
                                      production_numerics):
    """The delta rule's two kernels at qwen3-next-train-s16384's shape: 32
    value heads over 16 key heads of 128, the plan's chunk and the other one
    swept, and a ragged length."""
    def arg(shape, dtype="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    def loss(q, k, v, g, beta):
        return gd.gated_delta(q, k, v, g, beta, chunk, False).astype(
            jnp.float32).sum()
    qk = arg((1, s, 16, 128))
    text = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(
        qk, qk, arg((1, s, 32, 128)), arg((1, s, 32), "float32"),
        arg((1, s, 32), "float32")).compile().as_text()
    assert "gated_delta_fwd" in text and "gated_delta_bwd" in text


@pytest.mark.parametrize("policy", ["sala_saveable", "full"])
def test_a_replayed_sala_block_keeps_its_choice_and_its_sweep(
        policy, one_chip, production_numerics, monkeypatch):
    """A step of the MiniCPM-SALA family (a sparse and a lightning layer,
    heads of 128, 1,024 tokens past a dense_len of 512, layer bodies
    recomputed) compiled for the described chip: under the family's default
    the sparse forward sweep is lowered once, under "full" twice; the linear
    scan is replayed either way."""
    import re

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.ops.pallas import common

    monkeypatch.setattr(common, "on_tpu", lambda: True)
    paddle.seed(0)
    cfg = models.minicpm_sala_tiny(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        mixer_types=("minicpm4", "lightning-attn"), head_dim=128,
        lightning_nh=2, lightning_head_dim=128, dim_model_base=256,
        sparse=sa.SparseConfig(topk=6, window_size=128, dense_len=512),
        max_position_embeddings=1024, lm_ce="blockwise", use_recompute=True,
        recompute_policy=policy)
    model = models.MiniCPMSALAForCausalLM(cfg).bfloat16()
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step, params, opt_state = models.create_train_step(model, opt)

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    ids = described(np.zeros((1, 1024), np.int32))
    text = step.lower(
        jax.tree.map(described, params), jax.tree.map(described, opt_state),
        described(jax.random.key(0)), ids, ids, 1e-3).compile().as_text()
    calls = collections.Counter(
        re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*tpu_custom_call", text))
    twice = 1 if policy == "sala_saveable" else 2
    assert {k: n for k, n in calls.items() if "_attn_" in k} == {
        "sparse_attn_fwd": twice, "sparse_attn_bwd_dq": 1,
        "sparse_attn_bwd_dkv": 1, "linear_attn_fwd": 2,
        "linear_attn_bwd": 1}


@pytest.mark.parametrize("policy", ["flash_saveable", "full"])
def test_a_replayed_deltanet_block_keeps_its_forward_kernels_results(
        policy, one_chip, production_numerics, monkeypatch):
    """A step of the Qwen3-Next family (a DeltaNet layer, 4 value heads of
    128 over 2 key heads, and a gated full layer of 4 heads, without GQA so
    that its attention is a flash call at 1,024 tokens; layer bodies
    recomputed) compiled for the described chip: under the family's default
    each forward kernel is lowered once, the delta rule's output and chunk
    states and the flash call's output and statistics kept for the backward
    kernels; under "full" the replay runs both again."""
    import re

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.ops.pallas import common

    monkeypatch.setattr(common, "on_tpu", lambda: True)
    paddle.seed(0)
    cfg = models.qwen3_next_tiny(
        vocab_size=512, hidden_size=256, num_layers=2,
        full_attention_interval=2, num_attention_heads=4,
        num_key_value_heads=4, head_dim=128, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=128,
        linear_value_head_dim=128, max_position_embeddings=1024,
        lm_ce="blockwise", use_recompute=True, recompute_policy=policy)
    model = models.Qwen3NextForCausalLM(cfg).bfloat16()
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step, params, opt_state = models.create_train_step(model, opt)

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    ids = described(np.zeros((1, 1024), np.int32))
    text = step.lower(
        jax.tree.map(described, params), jax.tree.map(described, opt_state),
        described(jax.random.key(0)), ids, ids, 1e-3).compile().as_text()
    calls = collections.Counter(
        re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*tpu_custom_call", text))
    twice = 1 if policy == "flash_saveable" else 2
    assert {k: n for k, n in calls.items()
            if k.startswith(("gated_delta_", "flash_"))} == {
        "gated_delta_fwd": twice, "gated_delta_bwd": 1, "flash_fwd": twice,
        "flash_bwd": 1}


def test_a_matrix_update_is_no_epilogue_of_its_weight_gradient(
        one_chip, production_numerics):
    """The step of one 1024-wide SwiGLU block (bf16 weights, AdamW) compiled
    for the described chip: no fused computation holds both a
    ``convolution`` and AdamW's ``sqrt``. Until PR 35 XLA took each matrix's
    whole update into its weight gradient's convolution
    (``subtract_convert_fusion.N``), which ran at half the plain product's
    rate at 4096-wide matrices; ``Optimizer.apply_gradients`` now holds a
    matrix's gradient apart, and the norms' weights keep an update of their
    own either way."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import models
    from tools.wgrad_fusion_probe import fusions

    paddle.seed(0)
    cfg = models.LlamaConfig(
        vocab_size=512, hidden_size=1024, intermediate_size=2816,
        num_layers=1, num_heads=8, num_kv_heads=8,
        max_position_embeddings=256)
    model = models.LlamaForCausalLM(cfg).bfloat16()
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step, params, opt_state = models.create_train_step(model, opt)

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    ids = described(np.zeros((8, 256), np.int32))
    held = fusions(step.lower(
        jax.tree.map(described, params), jax.tree.map(described, opt_state),
        described(jax.random.key(0)), ids, ids, 1e-3).compile().as_text())
    products = {k for k, v in held.items() if v["holds"].get("convolution")}
    updates = {k for k, v in held.items() if v["holds"].get("sqrt")}
    matrices = sum(v.ndim >= 2 for v in params.values())
    assert len(products) >= 3 * 7 and len(updates) >= matrices
    assert not products & updates, {
        k: held[k]["out"] for k in products & updates}


# nemotron3-nano-train-s16384's Mamba-2 scan at the cell's widths (64 heads
# of 64 over 8 groups of state 128, chunk 128) and a sequence the chunk does
# not divide
@pytest.mark.parametrize("s", [2048, 1000])
def test_the_ssd_kernels_compile_for_v5e(s, one_chip, production_numerics):
    from paddle_tpu.ops.pallas import ssd as sd

    def arg(shape, dtype="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    def loss(x, dt, a, b, c):
        return sd.ssd(x, dt, a, b, c, 128, False).astype(jnp.float32).sum()
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        arg((1, s, 64, 64)), arg((1, s, 64), "float32"),
        arg((64,), "float32"), arg((1, s, 8, 128)),
        arg((1, s, 8, 128))).compile()
    text = compiled.as_text()
    for name in ("ssd_fwd", "ssd_bwd"):
        assert name in text, name
    # dt and G ride as lane-dense rows: the parked states (64 heads x 16
    # chunks x 32 KiB = 32 MiB at 2,048 tokens) are most of the temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 80 * 2 ** 20


@pytest.mark.parametrize("policy", ["flash_saveable", "full"])
def test_a_replayed_mamba_block_keeps_its_forward_kernels_results(
        policy, one_chip, production_numerics, monkeypatch):
    """A step of the Nemotron-H family (a Mamba-2 layer of 8 heads of 64
    over one group of state 128, and an attention layer of 4 heads of 128,
    without GQA so that it is a flash call at 1,024 tokens; layer bodies
    recomputed) compiled for the described chip: under the family's default
    each forward kernel is lowered once, the scan's output and chunk states
    and the flash call's output and statistics kept for the backward
    kernels; under "full" the replay runs both again."""
    import re

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.ops.pallas import common

    monkeypatch.setattr(common, "on_tpu", lambda: True)
    paddle.seed(0)
    cfg = models.nemotron_h_tiny(
        vocab_size=512, hidden_size=256, hybrid_override_pattern="M*",
        num_attention_heads=4, num_key_value_heads=4, head_dim=128,
        mamba_num_heads=8, mamba_head_dim=64, n_groups=1,
        ssm_state_size=128, chunk_size=128, lm_ce="blockwise",
        use_recompute=True, recompute_policy=policy)
    model = models.NemotronHForCausalLM(cfg).bfloat16()
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step, params, opt_state = models.create_train_step(model, opt)

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    ids = described(np.zeros((1, 1024), np.int32))
    text = step.lower(
        jax.tree.map(described, params), jax.tree.map(described, opt_state),
        described(jax.random.key(0)), ids, ids, 1e-3).compile().as_text()
    calls = collections.Counter(
        re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*tpu_custom_call", text))
    twice = 1 if policy == "flash_saveable" else 2
    got = {k: n for k, n in calls.items() if k.startswith(("ssd_", "flash_"))}
    assert got == {"ssd_fwd": twice, "ssd_bwd": 1, "flash_fwd": twice,
                   "flash_bwd": 1}
