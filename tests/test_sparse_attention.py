"""ops/pallas/sparse_attention.py: the choice of blocks against a brute-force
loop over queries, the XLA path against a plain masked softmax, the three
kernels in interpret mode against the XLA path, and the promise that the
flash kernels' plan and route answer as before for the benchmark's other
cells."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import sparse_attention as sa

TINY = sa.SparseConfig(kernel_size=4, kernel_stride=2, block_size=8, topk=4,
                       init_blocks=1, window_size=16, dense_len=32)


def _qkv(seed, b, s, hq, hkv, d, dtype=jnp.float32):
    key = jax.random.key(seed)
    return tuple(
        jax.random.normal(jax.random.fold_in(key, i), (b, s, h, d),
                          jnp.float32).astype(dtype)
        for i, h in enumerate((hq, hkv, hkv)))


def brute_force_choice(q, k, sc):
    """Steps (1) to (5) of the rule, one query at a time in numpy float64:
    -> a list over kv heads of a list over positions of the chosen blocks, in
    the order forced first then by score, ties to the lower block."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    per = sc.block_size // sc.kernel_stride
    n_pool = (s - sc.kernel_size) // sc.kernel_stride + 1
    out = []
    for j in range(hkv):
        kbar = np.stack([k[i * sc.kernel_stride:i * sc.kernel_stride
                           + sc.kernel_size, j].mean(0)
                         for i in range(n_pool)])
        rows = []
        for t in range(s):
            vis = [i for i in range(n_pool)
                   if i * sc.kernel_stride + sc.kernel_size - 1 <= t]
            total = np.zeros(n_pool)
            for h in range(j * g, (j + 1) * g):
                if vis:
                    z = kbar[vis] @ q[t, h] / math.sqrt(d)
                    e = np.exp(z - z.max())
                    total[vis] += e / e.sum()
            own = t // sc.block_size
            scores = []
            for b in range(own + 1):
                lo, hi = max(per * b - 1, 0), min(per * b + per - 1,
                                                  n_pool - 1)
                forced = b < sc.init_blocks or b > own - sc.local_blocks
                scores.append((0 if forced else 1,
                               -float(total[lo:hi + 1].max()), b))
            rows.append([b for _, _, b in sorted(scores)[:sc.topk]])
        out.append(rows)
    return out


@pytest.mark.parametrize("seed,s", [(0, 64), (1, 96)])
def test_the_choice_matches_a_brute_force_loop(seed, s):
    q, k, _ = _qkv(seed, 1, s, 4, 2, 16)
    chosen = np.asarray(sa.select_blocks(q * 3, k, TINY))    # [1, 2, S, nb]
    want = brute_force_choice(np.asarray(q[0] * 3, np.float64),
                              np.asarray(k[0], np.float64), TINY)
    for j in range(2):
        for t in range(s):
            got = np.flatnonzero(chosen[0, j, t]).tolist()
            assert got == sorted(want[j][t]), (j, t)
            own = t // 8
            # causal at block level, the forced blocks among the chosen
            assert max(got) == own and 0 in got
            assert own - 1 in got or own == 0
            assert len(got) == min(4, own + 1)


def test_pooled_keys_never_look_past_the_query():
    """A pooled key is visible once its LAST token is: changing a key
    changes no choice of the queries before the pooled windows it is in
    end."""
    q, k, _ = _qkv(2, 1, 64, 4, 2, 16)
    base = np.asarray(sa.select_blocks(q, k, TINY))
    moved = np.asarray(sa.select_blocks(q, k.at[0, 41].add(50.0), TINY))
    # token 41 lies in the pooled windows [38, 42) and [40, 44): the first
    # ends at 41
    assert (base[:, :, :41] == moved[:, :, :41]).all()
    assert (base[:, :, 41:] != moved[:, :, 41:]).any()


def test_the_choice_is_named_in_the_compiled_program():
    """The choice stays XLA's: no kernel's name marks it, so its operations
    carry a scope of their own in ``op_name`` for a reader of a profile, and
    the scope's name holds none of the kernels' prefix, which the benchmark's
    readers count by."""
    q, k, _ = _qkv(2, 1, 64, 4, 2, 16)
    text = jax.jit(lambda a, b: sa.select_blocks(a, b, TINY)).lower(
        q, k).compile().as_text()
    assert 'sparse_select/' in text
    assert "sparse_attn_" not in text


def test_ties_go_to_the_lower_block_in_program_and_reference():
    """All scores equal (zero queries): the free choice is the lowest
    blocks, in ``select_blocks`` as in the benchmark's reference."""
    from benchmarks.reference import minicpm_sala as ref
    q, k, _ = _qkv(3, 1, 64, 4, 2, 16)
    q = jnp.zeros_like(q)
    chosen = np.asarray(sa.select_blocks(q, k, TINY))[0, 0]
    ri, rv = ref.chosen_blocks(q[0, :, :2].transpose(1, 0, 2), k[0, :, 0],
                               TINY._asdict())
    assert (chosen == np.asarray(ref._block_mask(ri, rv, 8))).all()
    # query 63 (block 7): forced 0, 6, 7 and the lowest free block, 1
    assert np.flatnonzero(chosen[63]).tolist() == [0, 1, 6, 7]


def _plain(q, k, v, chosen, block):
    """Masked softmax over all keys in float64 numpy."""
    b, s, hq, d = q.shape
    g = hq // k.shape[2]
    out = np.zeros((b, s, hq, d))
    for bi in range(b):
        for h in range(hq):
            for t in range(s):
                keys = [u for u in range(t + 1)
                        if chosen[bi, h // g, t, u // block]]
                z = k[bi, keys, h // g] @ q[bi, t, h] / math.sqrt(d)
                p = np.exp(z - z.max())
                out[bi, t, h] = (p / p.sum()) @ v[bi, keys, h // g]
    return out


def test_the_xla_path_is_a_masked_softmax_over_the_chosen_blocks():
    q, k, v = _qkv(4, 2, 64, 4, 2, 16)
    idx = sa.select_blocks(q, k, TINY)
    got = sa.sparse_attention_xla(q, k, v, idx, 0.25, 8)
    want = _plain(*(np.asarray(a, np.float64) for a in (q, k, v)),
                  np.asarray(idx), 8)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)


@pytest.mark.parametrize("s,sc,tiles", [
    (64, TINY, sa.SparseTiles(16, 16)),
    (128, TINY, sa.SparseTiles(32, 64)),
    (128, TINY, sa.SparseTiles(64, 32)),
    (512, sa.SparseConfig(topk=5, window_size=128, dense_len=256), None),
])
def test_the_kernels_match_the_xla_path(s, sc, tiles):
    q, k, v = _qkv(5, 1, s, 4, 2, 32)
    idx = sa.select_blocks(q, k, sc)
    scale = 1 / math.sqrt(32)
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)

    def loss(fn):
        return jax.value_and_grad(
            lambda a, b, c: jnp.sum(fn(a, b, c) * w), (0, 1, 2))(q, k, v)
    got, g_got = loss(lambda a, b, c: sa.sparse_attention(
        a, b, c, idx, scale, sc.block_size, tiles, True))
    want, g_want = loss(lambda a, b, c: sa.sparse_attention_xla(
        a, b, c, idx, scale, sc.block_size))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_a_tile_no_query_chose_from_is_flagged_dead():
    """Queries that choose blocks {0, own - 1, own} only leave the key tiles
    in between unflagged."""
    s, blk = 128, 8
    own = np.arange(s) // blk
    block = np.arange(s // blk)[None]
    chosen = (block == 0) | (block == own[:, None] - 1) \
        | (block == own[:, None])
    tiles = sa.SparseTiles(32, 32)
    words, flags = sa.pack_choice(jnp.asarray(chosen[None, None]), s, blk,
                                  tiles)
    flags = np.asarray(flags).reshape(4, 4)
    assert flags.tolist() == [[1, 0, 0, 0], [1, 1, 0, 0], [1, 1, 1, 0],
                              [1, 0, 1, 1]]
    assert words.shape == (1, s, 128)
    # query 100 (block 12, key tile 3, blocks 12..15): bits 0 of tile 3 and
    # bit 3 of tile 2 (block 11), bit 0 of tile 0
    assert np.asarray(words)[0, 100, :4].tolist() == [1, 0, 8, 1]


def test_mean_attended_keys_by_hand():
    sc = sa.SparseConfig()
    assert sa.mean_attended_keys(12288, sc) == pytest.approx(3392.5)
    assert sa.mean_attended_keys(8192, sc) == pytest.approx(4096.5)
    assert sa.sparse_tile_plan(12288, 64) == (1024, 1024)
    assert sa.sparse_tile_plan(64, 8) == (64, 64)


# the attention calls of the four cells the benchmark had before this one:
# (B, S, Hq, Hkv, D, window) -> the plan and route they get: the tiles read
# on the commit before this family came, with the one backward kernel on
# dkv's tile where dq and dkv were
_CELL_CALLS = {
    "gpt2s": (32, 1024, 12, 12, 64, None),
    "mistral": (4, 4096, 32, 8, 128, None),
    "laguna_full": (2, 8192, 48, 8, 128, None),
    "laguna_window": (2, 8192, 64, 8, 128, 512),
    "glm": (2, 8192, 20, 20, 256, None),
}
_CELL_PLANS = {
    "gpt2s": "fwd=1024x1024/128x128 bwd=1024x1024/256x256",
    "mistral": "fwd=1024x1024/512x512 bwd=1024x1024/256x256",
    "laguna_full": "fwd=1024x1024/512x512 bwd=1024x1024/256x256",
    "laguna_window": "fwd=1024x1024/512x512 bwd=1024x1024/256x256",
    "glm": "fwd=1024x1024/512x512 bwd=1024x1024/256x256",
}


@pytest.mark.parametrize("cell", sorted(_CELL_CALLS))
def test_flash_plan_and_route_of_the_other_cells_are_unchanged(cell):
    b, s, hq, hkv, d, window = _CELL_CALLS[cell]
    plan = fa.tile_plan(s, s, d, 2, 2, 2, causal=True, window=window,
                        rep=hq // hkv)
    got = " ".join(f"{n}={t.bq}x{t.bk}/{t.sub_q}x{t.sub_k}"
                   for n, t in plan.lowered().items())
    assert got == _CELL_PLANS[cell]
    q = jax.ShapeDtypeStruct((b, s, hq, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16)
    route = fa.attention_route(
        q, k, None, dropout_rate=0.0, has_key=False, causal=True,
        window=window, meshed=False, on_tpu=True, force_interpret=False)
    assert (route.impl, route.rule) == (
        "kernel", "window" if window else "default")
