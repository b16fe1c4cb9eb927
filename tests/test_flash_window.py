"""The window in the flash template (ops/pallas/flash_attention.py).

A windowed call is checked against plain masked attention, forward and the
three gradients, in interpret mode; its grid holds the band's blocks only;
its kernels carry other names. A call WITHOUT a window keeps what the
benchmark's readers find it by: the tile, grid, names, operand shapes and
VMEM limit at the two accepted benchmark cells' shapes are pinned here as
constants read off PR 26's commit. The whole jaxpr is pinned too, as of
PR 31, which changed the kernels' bodies (a tile is walked in sub-tiles).
"""
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional.flash_attention import _attention_xla
from paddle_tpu.ops.pallas import flash_attention as fa


def _plain(q, k, v, window):
    """softmax(q k^T / sqrt(d) under the mask) v, one head at a time."""
    b, s, hq, d = q.shape
    g = hq // k.shape[2]
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    mask = j <= i
    if window is not None:
        mask &= (i - j) < window
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, g, axis=2)) \
        / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(mask[None, None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(v, g, axis=2))


def _mk(b, s, hq, hk, d, seed):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (b, s, hq, d)),
            jax.random.normal(ks[1], (b, s, hk, d)),
            jax.random.normal(ks[2], (b, s, hk, d)),
            jax.random.normal(ks[3], (b, s, hq, d)))


def _flash(q, k, v, window, bq=None, bk=None):
    return fa.flash_attention_ext(
        q, k, v, None, jnp.zeros((1,), jnp.int32), None, None, True,
        1.0 / math.sqrt(q.shape[-1]), 0.0, bq, bk, True, window)


# (s, hq, hk, window, block): window smaller than, equal to and larger than
# a block; S not a multiple of the block; GQA groups 6 and 8
_CASES = [(256, 6, 1, 8, 128), (256, 8, 1, 128, 128), (300, 6, 1, 200, 128),
          (300, 8, 1, 64, 128), (384, 8, 2, 100, (128, 256)),
          (384, 6, 2, 300, (256, 128)), (200, 6, 1, 1000, 64)]


@pytest.mark.parametrize("s,hq,hk,window,block", _CASES)
def test_windowed_flash_matches_masked_plain_attention(s, hq, hk, window,
                                                       block):
    bq, bk = block if isinstance(block, tuple) else (block, block)
    q, k, v, do = _mk(1, s, hq, hk, 32, seed=s + window)
    def both(fn):
        def run(q, k, v):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(do)
        return jax.jit(run)(q, k, v)
    got = both(lambda *a: _flash(*a, window, bq, bk))
    want = both(lambda *a: _plain(*a, window))
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(a, b, atol=2e-4)


# the sub-tile a 512 x 512 tile is walked in: itself (one piece), and pieces
# that the band's two edges cross in one, both or neither of their sides
@pytest.mark.parametrize("sub", [(512, 512), (128, 128), (64, 256),
                                 (256, 128)], ids=lambda t: f"{t[0]}x{t[1]}")
@pytest.mark.parametrize("s,hq,hk,window", [
    (1200, 4, 1, 512),      # the cell's window, on a length it does not divide
    (1000, 2, 2, 200),      # narrower than a sub-tile's side or wider
    (700, 4, 2, 40)])
def test_windowed_sub_tiles_match_masked_plain_attention(s, hq, hk, window,
                                                         sub, monkeypatch):
    monkeypatch.setattr(
        fa, "_sub_tile",
        lambda kernel, bq, bk, *_: (min(sub[0], bq), min(sub[1], bk)))
    q, k, v, do = _mk(1, s, hq, hk, 32, seed=s + window)

    def both(fn):
        def run(q, k, v):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(do)
        return jax.jit(run)(q, k, v)
    got = both(lambda *a: _flash(*a, window, 512, 512))
    want = both(lambda *a: _plain(*a, window))
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(a, b, atol=2e-4)


def test_the_plans_tile_stops_at_twice_the_window():
    """512-wide bands take 1024 x 1024 tiles walked in pieces no longer than
    the window (the sweep on the chip, PERF.md "PR 31"; 512 x 512 while a
    tile was computed whole, "PR 28"); a narrow band takes small tiles."""
    def tile(fwd, dq, dkv, side=1024):
        return [(side, side, sub, sub) for sub in (fwd, dq, dkv)]

    def plan(window=None):
        return [t[:4] for t in fa.tile_plan(8192, 8192, 128, window=window)]
    assert plan(512) == plan() == plan(4096) == tile(512, 128, 256)
    assert plan(8) == tile(128, 128, 128, side=256)
    assert plan(200) == tile(256, 128, 256, side=512)
    # a 1024 tile under the window 512 meets the diagonal at its own
    # corner, and the band's lower edge in the tile to its left
    assert {t.kinds for t in fa.tile_plan(8192, 8192, 128, window=512)} == \
        {((0, None), (1024, None))}


def test_a_windowed_call_sweeps_the_band_only_and_says_so():
    """Names, the grid's extent and the ``flash::tile_plan`` attributes of a
    windowed call: S 1024, window 128, so 256 x 256 tiles walked in 128 x
    128: a query block sees two key blocks of four, a key block is seen by
    two query blocks; 128 query rows see two sub-tiles of eight."""
    from paddle_tpu.profiler import tracing

    q, k, v, _ = _mk(1, 1024, 2, 1, 32, seed=3)
    step = jax.jit(jax.grad(
        lambda q, k, v: _flash(q, k, v, 128).sum(), (0, 1, 2)))
    tracing.reset_tracing()
    tracing.enable_tracing()
    before = dict(fa.TILE_PLAN_TALLY)
    try:
        jax.block_until_ready(step(q, k, v))
        events = {e["args"]["kernel"]: e["args"]
                  for e in tracing.snapshot_events()
                  if e["name"] == "flash::tile_plan"}
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    assert set(events) == {"flash_win_fwd", "flash_win_bwd_dq",
                           "flash_win_bwd_dkv"}
    for name, a in events.items():
        assert (a["bq"], a["bk"], a["window"]) == (256, 256, 128)
        assert (a["sub_q"], a["sub_k"]) == (128, 128)
        key = (name, 256, 256, 128, 128)
        assert fa.TILE_PLAN_TALLY[key] == before.get(key, 0) + 1
        # 2 q heads x 4 outer blocks x 2 band blocks; the first query block
        # (the last key block) has one block in its band
        assert a["grid_steps"] == 2 * 4 * 2
        assert a["skipped_steps"] == 2 * 1
        assert a["band_skipped_steps"] == 2 * (4 * 4 - 4 * 2)
        # of the 8 x 8 sub-tiles 15 hold a visible score, each crossed by
        # the diagonal or the band's lower edge
        assert a["sub_tiles"] == a["sub_tiles_masked"] == 2 * 15
        assert a["sub_tiles_skipped"] == 2 * (8 * 8 - 15)
    calls = _pallas_calls(jax.grad(
        lambda q, k, v: _flash(q, k, v, 128).sum(), (0, 1, 2)), q, k, v)
    assert [(c["name"], c["grid"]) for c in calls] == [
        ("flash_win_fwd", (2, 4, 2)), ("flash_win_bwd_dq", (2, 4, 2)),
        ("flash_win_bwd_dkv", (1, 4, 2, 2))]


def test_the_xla_fallback_masks_the_same_window():
    q, k, v, _ = _mk(2, 96, 6, 1, 16, seed=5)
    got = _attention_xla(q, k, v, None, True, 0.25, 0.0, None, 8)
    np.testing.assert_allclose(got, _plain(q, k, v, 8), atol=2e-5)
    with pytest.raises(ValueError):
        _attention_xla(q, k, v, None, False, 0.25, 0.0, None, 8)


@pytest.mark.parametrize("force", [False, True], ids=["xla", "pallas"])
def test_window_through_the_functional_api(force):
    q, k, v, _ = _mk(1, 256, 6, 1, 32, seed=7)
    paddle.set_flags({"pallas_force_interpret": force})
    try:
        out = F.scaled_dot_product_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            is_causal=True, window=40)
        out2, _ = F.flash_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            causal=True, window=40)
    finally:
        paddle.set_flags({"pallas_force_interpret": False})
    np.testing.assert_allclose(out._data, _plain(q, k, v, 40), atol=2e-5)
    np.testing.assert_allclose(out2._data, _plain(q, k, v, 40), atol=2e-5)


def test_a_window_needs_causal_and_takes_no_bias_or_segments():
    q, k, v, _ = _mk(1, 128, 2, 2, 16, seed=9)
    seed = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError):
        fa.flash_attention_ext(q, k, v, None, seed, None, None, False, 0.25,
                               0.0, None, None, True, 8)
    with pytest.raises(ValueError):
        fa.flash_attention_ext(q, k, v, jnp.zeros((1, 2, 128, 128)), seed,
                               None, None, True, 0.25, 0.0, None, None, True,
                               8)


# -- a call without a window lowers as before --------------------------------

def _pallas_calls(fn, *args):
    out = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                gm = e.params["grid_mapping"]
                out.append({
                    "name": e.params["name"], "grid": tuple(gm.grid),
                    "blocks": tuple(
                        tuple(getattr(b, "block_size", b)
                              for b in bm.block_shape)
                        for bm in gm.block_mappings),
                    "operands": tuple(tuple(v.aval.shape) for v in e.invars),
                    "vmem_limit_bytes": e.params["compiler_params"][
                        "mosaic_tpu"].vmem_limit_bytes})
            for p in e.params.values():
                inner = getattr(p, "jaxpr", p)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    walk(inner)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


def _cell_grad(b, s, hq, hk, d, qk_dtype, v_dtype):
    args = (jax.ShapeDtypeStruct((b, s, hq, d), qk_dtype),
            jax.ShapeDtypeStruct((b, s, hk, d), qk_dtype),
            jax.ShapeDtypeStruct((b, s, hk, d), v_dtype))

    def loss(q, k, v):
        return fa.flash_attention_ext(
            q, k, v, None, jnp.zeros((1,), jnp.int32), None, None, True,
            d ** -0.5, 0.0, None, None, False).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2)), args


def _row(name, grid, d, q_rows, kv_rows, s, n_in, vmem):
    big, col = (1, 1024, d), (1, 1024, 1)
    blocks = {"flash_fwd": (big,) * 4 + (col,),
              "flash_bwd_dq": (big,) * 4 + (col, col, big),
              "flash_bwd_dkv": (big,) * 4 + (col, col, big, big)}[name]
    q3, kv3, c3 = (q_rows, s, d), (kv_rows, s, d), (q_rows, s, 1)
    operands = (q3, kv3, kv3) if n_in == 3 else (q3, kv3, kv3, q3, c3, c3)
    return {"name": name, "grid": grid, "blocks": blocks,
            "operands": operands, "vmem_limit_bytes": vmem}


# names, grids, blocks, operands and VMEM limits read off PR 26's commit
# (97959e4) with this file's _pallas_calls; the digests are PR 31's, whose
# kernels walk a tile in sub-tiles and are jitted on their own
_PINNED = {
    "gpt2s-train-s1024": (
        (32, 1024, 12, 12, 64, jnp.bfloat16, jnp.bfloat16),
        [_row("flash_fwd", (384, 1, 1), 64, 384, 384, 1024, 3, 25952256),
         _row("flash_bwd_dq", (384, 1, 1), 64, 384, 384, 1024, 6, 33030144),
         _row("flash_bwd_dkv", (384, 1, 1, 1), 64, 384, 384, 1024, 6,
              34603008)],
        "8005a5d08f1ae177"),
    "mistral7b-l2-train-s4096": (
        (4, 4096, 32, 8, 128, jnp.float32, jnp.bfloat16),
        [_row("flash_fwd", (128, 4, 4), 128, 128, 32, 4096, 3, 28311552),
         _row("flash_bwd_dq", (128, 4, 4), 128, 128, 32, 4096, 6, 36175872),
         _row("flash_bwd_dkv", (32, 4, 4, 4), 128, 128, 32, 4096, 6,
              38535168)],
        "930ee281d5c13e81"),
}


@pytest.mark.parametrize("cell", list(_PINNED))
def test_a_call_without_a_window_lowers_as_the_parent_did(cell, monkeypatch):
    # PR 33 names the forward's two residuals for fleet.recompute; a name is
    # the identity, and with it made so the text is the parent's
    import importlib
    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.distributed.fleet.recompute"),
        "checkpoint_name", lambda x, name: x)
    shape, rows, digest = _PINNED[cell]
    fn, args = _cell_grad(*shape)
    assert _pallas_calls(fn, *args) == rows
    # and the whole jaxpr, kernels' bodies included, letter for letter (as
    # printed under tests/conftest.py's settings by the installed jax: read
    # it off the parent again if either changes)
    text = str(jax.make_jaxpr(fn)(*args))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
