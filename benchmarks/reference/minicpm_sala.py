"""Plain reference of the MiniCPM-SALA decoder (openbmb, ``model_type``
"minicpm_sala"; ``huggingface.co/openbmb/MiniCPM-SALA`` ``config.json``),
written from the config's keys and, where the config is silent, from the
family's published conventions (each such item is ``assumed`` in the
configuration's file). float32 ``jax.numpy``, no kernels, no chunked scan, no
gather of blocks. Nothing here imports the program.

Model (the MiniCPM convention of ``scale_emb``, ``scale_depth``,
``dim_model_base``): ``h = scale_emb x embed(ids)``; each layer ``h = h + a x
Mixer(RMSNorm(h))``, ``h = h + a x MLP(RMSNorm(h))`` with ``a = scale_depth /
sqrt(residual_scale_layers)`` (the PUBLISHED depth, 32, however many layers
are held); ``MLP(x) = down(silu(gate x) * up x)``; ``logits = head(RMSNorm(h)
/ (hidden_size / dim_model_base))``; no biases; table and head untied.
``mup_denominator`` has no term in the forward pass.

``minicpm4`` mixer (``mixer_types[l]``; InfLLM-V2's block-sparse attention):
``q = RMSNorm_D(Wq x)``, ``k = RMSNorm_D(Wk x)`` (one gain of D each, shared
by the heads), ``v = Wv x``, no rotary embedding, scale ``1/sqrt(D)``,
``num_attention_heads`` query heads over ``num_key_value_heads`` kv heads.
With ``sparse_config`` = {kernel_size 32, kernel_stride 16, block_size 64,
topk 64, init_blocks 1, window_size 2048, dense_len 8192}:

- ``S <= dense_len``: plain causal softmax attention.
- ``S > dense_len``, for each kv head: (1) pooled keys ``kbar_j = mean(k[16 j
  : 16 j + 32])``, ``j = 0 .. (S - 32) / 16``; (2) for each query head of the
  group ``p_t = softmax_j(q_t . kbar_j / sqrt(D))`` over the pooled keys whose
  last token ``16 j + 31 <= t`` (none visible: all zero); (3) ``P_t`` = the
  sum of ``p_t`` over the group's heads; (4) block score ``s_t[b] = max(P_t[4
  b - 1 .. 4 b + 3])`` (max-pool 5, stride 4, padding 1); (5) among the
  blocks ``b <= t // 64``, block 0 (``init_blocks``) and the ``window_size /
  block_size`` = 32 blocks that end at the query's own are always taken, and
  the best-scoring others fill up to ``topk`` = 64 IN ALL (ties: the lower
  block first); a query that sees 64 blocks or fewer takes all; (6) ``o_t`` =
  softmax over the keys ``s <= t`` of the chosen blocks of ``q_t . k_s /
  sqrt(D)`` times ``v_s``, one choice for the whole group; steps (1) to (5)
  carry no gradient and are float32 at highest precision whatever ``math``
  is: they decide a top-k. (7) ``y = Wo (o * sigmoid(Wg x))``, Wg: hidden ->
  heads x D, elementwise.

``lightning-attn`` mixer (Lightning Attention-2, Qin et al. 2024): ``q, k =
rope(RMSNorm_D(W x))`` (theta ``rope_theta``, the whole head, pairs (x[2i],
x[2i+1])), ``v = Wv x``; ``o_t = (1/sqrt(D)) sum_{s <= t} lambda_h^(t - s)
(q_t . k_s) v_s``, no normaliser, ``lambda_h = exp(-slope_h (1 - l / (L - 1)
+ 1e-5))``, ``slope_h = 2^(-8 (h + 1) / heads)``, ``l`` the published index
of the layer and ``L = residual_scale_layers`` the published depth; ``y = Wo
(RMSNorm(o) * sigmoid(Wg x))`` with the norm over ALL the heads' outputs side
by side (one gain of heads x D, as Lightning Attention-2's public code norms
its output; a norm over each head alone divides the first token's output,
``(q_0 . k_0) v_0``, by its own size, and where ``q_0 . k_0`` is near zero the
gradient there is as large as ``1 / sqrt(eps)``: PERF.md section 6, PR 34).
Computed as the quadratic masked form, one head and one block of queries at
a time.

Matrices are stored [in, out]. Queries are walked in blocks under
``jax.checkpoint`` so that nothing of size S x S is alive at once. The
vocabulary is whatever ``vocab_size`` says: a slice is a smaller vocabulary.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
_HIGHEST = jax.lax.Precision.HIGHEST


def _mixers(v: dict) -> list:
    return list(v["mixer_types"][:v["num_hidden_layers"]])


def sparse_config(v: dict) -> dict:
    return v["sparse_config"]


def mean_attended_keys(seq: int, sc: dict) -> float:
    """The exact mean over positions of the keys a query attends under the
    rule above: all ``t + 1`` where it sees ``topk`` blocks or fewer (or the
    sequence is dense), else ``topk - 1`` whole blocks and its own so far."""
    if seq <= sc["dense_len"]:
        return (seq + 1) / 2.0
    t = np.arange(seq, dtype=np.int64)
    blk, topk = sc["block_size"], sc["topk"]
    seen = t // blk + 1
    return float(np.where(seen <= topk, t + 1,
                          (topk - 1) * blk + t % blk + 1).mean())


def mean_visible_pooled(seq: int, sc: dict) -> float:
    """The mean over positions of the pooled keys a query scores."""
    if seq <= sc["dense_len"]:
        return 0.0
    t = np.arange(seq, dtype=np.int64)
    n = np.maximum((t - (sc["kernel_size"] - 1)) // sc["kernel_stride"] + 1, 0)
    return float(n.mean())


def dims(v: dict) -> dict:
    """The sizes ``harness/flops.py::train_flops_per_token`` needs, such that
    it counts what one token's step requires here and no more. It computes
    ``6 (layers x layer_matmul_params + vocab x hidden) + layers x 6 S heads
    D``; ``heads`` is 0 here (no layer attends at causal half density), and
    ``layer_matmul_params`` is the MEAN over the layers of the parameters a
    token multiplies (mixer projections, gate, MLP) plus

    - for a sparse layer ``2 Hq D nbar``, which the 6 turns into ``12 Hq D
      nbar`` FLOPs a token forward and backward (``nbar`` =
      ``mean_attended_keys``), and the forward-only scoring ``Hq D pbar / 3``
      (``2 Hq D`` FLOPs a visible pooled key, ``pbar`` =
      ``mean_visible_pooled``);
    - for a lightning layer ``2 H D D``: the recurrence's state update and
      read-out, ``12 H D^2`` FLOPs a token forward and backward.

    A dense masked sweep or an intra-chunk product is charged its time and
    credited only this."""
    h, inner = v["hidden_size"], v["intermediate_size"]
    hq, hkv, d = v["num_attention_heads"], v["num_key_value_heads"], \
        v["head_dim"]
    lh, ld = v["lightning_nh"], v["lightning_head_dim"]
    seq, sc = v["sequence_length"], sparse_config(v)
    mlp = 3 * h * inner
    sparse = 3 * h * hq * d + 2 * h * hkv * d + mlp \
        + 2 * hq * d * mean_attended_keys(seq, sc) \
        + hq * d * mean_visible_pooled(seq, sc) / 3.0
    lightning = 4 * h * lh * ld + h * v["lightning_nkv"] * ld + mlp \
        + 2 * lh * ld * ld
    kinds = _mixers(v)
    total = sum(sparse if k == SPARSE else lightning for k in kinds)
    return {"hidden": h, "layers": len(kinds), "heads": 0, "kv_heads": hkv,
            "head_dim": d, "vocab": v["vocab_size"],
            "layer_matmul_params": total / len(kinds)}


def param_shapes(v: dict) -> dict:
    h, inner, vocab = v["hidden_size"], v["intermediate_size"], v["vocab_size"]
    hq, hkv, d = v["num_attention_heads"], v["num_key_value_heads"], \
        v["head_dim"]
    lh, lkv, ld = v["lightning_nh"], v["lightning_nkv"], \
        v["lightning_head_dim"]
    if lkv != lh:
        raise ValueError("this reference writes lightning attention with as "
                         "many key heads as query heads")
    out = {"embed": ((vocab, h), "normal")}
    for i, kind in enumerate(_mixers(v)):
        b = f"layers.{i}."
        out[b + "input_norm.weight"] = ((h,), "ones")
        if kind == SPARSE:
            nq, nkv, hd = hq, hkv, d
        else:
            nq, nkv, hd = lh, lkv, ld
        out[b + "q.weight"] = ((h, nq * hd), "normal")
        out[b + "k.weight"] = ((h, nkv * hd), "normal")
        out[b + "v.weight"] = ((h, nkv * hd), "normal")
        out[b + "q_norm.weight"] = ((hd,), "ones")
        out[b + "k_norm.weight"] = ((hd,), "ones")
        if kind == LIGHTNING:
            out[b + "o_norm.weight"] = ((nq * hd,), "ones")
        out[b + "g.weight"] = ((h, nq * hd), "normal")
        out[b + "o.weight"] = ((nq * hd, h), "normal")
        out[b + "post_norm.weight"] = ((h,), "ones")
        out[b + "gate.weight"] = ((h, inner), "normal")
        out[b + "up.weight"] = ((h, inner), "normal")
        out[b + "down.weight"] = ((inner, h), "normal")
    out["norm.weight"] = ((h,), "ones")
    out["head.weight"] = ((h, vocab), "normal")
    return out


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _rope(x, theta):
    """x: [B, S, H, D]; rotate pairs (2i, 2i+1)."""
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def output_gate(y, w, math):
    """sigmoid(y Wg): the elementwise gate of a mixer's output."""
    return jax.nn.sigmoid(math.einsum("bsh,hk->bsk", y, w))


def _query_block(s: int) -> int:
    """Queries walked at a time: the largest of 256, 128, ... that divides."""
    b = 256
    while s % b:
        b //= 2
    return b


# -- the minicpm4 mixer -------------------------------------------------------

def pooled_keys(k, sc: dict):
    """k [S, D] -> [n_pool, D]: means of ``kernel_size`` keys every
    ``kernel_stride``."""
    size, stride = sc["kernel_size"], sc["kernel_stride"]
    n = (k.shape[0] - size) // stride + 1
    idx = jnp.arange(n)[:, None] * stride + jnp.arange(size)[None]
    return jnp.mean(k[idx], axis=1)


def block_scores(q, kbar, t, sc: dict):
    """Steps (2) to (4) for the queries ``q`` [G, T, D] at positions ``t``
    [T] of one kv group: [T, n_blocks] float32."""
    size, stride, blk = sc["kernel_size"], sc["kernel_stride"], \
        sc["block_size"]
    per = blk // stride                                   # pooled keys a block
    d, n_pool = q.shape[-1], kbar.shape[0]
    z = jnp.einsum("gtd,jd->gtj", q, kbar, precision=_HIGHEST) \
        / jnp.sqrt(jnp.float32(d))
    last = jnp.arange(n_pool) * stride + size - 1
    vis = last[None, :] <= t[:, None]                      # [T, n_pool]
    z = jnp.where(vis[None], z, -jnp.inf)
    m = jnp.max(z, axis=-1, keepdims=True)
    e = jnp.where(vis[None], jnp.exp(z - jnp.where(jnp.isfinite(m), m, 0.0)),
                  0.0)
    den = jnp.sum(e, axis=-1, keepdims=True)
    p = jnp.sum(e / jnp.where(den > 0, den, 1.0), axis=0)  # [T, n_pool]
    n_blocks = sc["_n_blocks"]
    width = per * n_blocks + 1
    # index i + 1 holds P[i]; the window of block b is [per b, per b + per]
    padded = jnp.zeros((p.shape[0], width), p.dtype)
    padded = padded.at[:, 1:1 + min(n_pool, width - 1)].set(
        p[:, :width - 1])
    return jnp.max(jnp.stack(
        [padded[:, o:o + per * (n_blocks - 1) + 1:per]
         for o in range(per + 1)]), axis=0)


def choose_blocks(score, t, sc: dict):
    """Step (5): ``score`` [T, n_blocks] -> (idx [T, K] int32, valid [T, K])
    with ``K = min(topk, n_blocks)``; forced blocks first by an infinite
    score, invisible blocks never."""
    blk, n_blocks = sc["block_size"], score.shape[1]
    own = (t // blk)[:, None]
    b = jnp.arange(n_blocks)[None]
    local = sc["window_size"] // blk
    forced = (b < sc["init_blocks"]) | ((b > own - local) & (b <= own))
    ranked = jnp.where(b > own, -jnp.inf, jnp.where(forced, jnp.inf, score))
    vals, idx = jax.lax.top_k(ranked, min(sc["topk"], n_blocks))
    return idx.astype(jnp.int32), vals > -jnp.inf


def chosen_blocks(q, k, sc: dict):
    """Steps (1) to (5) for one kv group: q [G, S, D], k [S, D] -> (idx [S,
    K], valid [S, K])."""
    s = q.shape[1]
    sc = dict(sc, _n_blocks=s // sc["block_size"])
    kbar = pooled_keys(k, sc)
    qb = _query_block(s)

    def one(args):
        qs, ts = args
        return choose_blocks(block_scores(qs, kbar, ts, sc), ts, sc)
    t = jnp.arange(s).reshape(s // qb, qb)
    qs = jnp.moveaxis(q.reshape(q.shape[0], s // qb, qb, -1), 1, 0)
    idx, valid = jax.lax.map(one, (qs, t))
    return idx.reshape(s, -1), valid.reshape(s, -1)


def _block_mask(idx, valid, n_blocks):
    """[T, K] choices -> [T, n_blocks] bool."""
    hit = (idx[..., None] == jnp.arange(n_blocks)) & valid[..., None]
    return jnp.any(hit, axis=-2)


def _sparse_group(q, k, val, sc, math):
    """One kv group: q [G, S, D], k and val [S, D] -> [G, S, D]."""
    g, s, d = q.shape
    dense = s <= sc["dense_len"]
    if not dense:
        if s % sc["block_size"]:
            raise ValueError("sequence no multiple of block_size")
        idx, valid = chosen_blocks(jax.lax.stop_gradient(q),
                                   jax.lax.stop_gradient(k), sc)
    qb = _query_block(s)
    n_blocks = s // sc["block_size"]
    key_block = jnp.arange(s) // sc["block_size"]

    def rows(args):
        qs, ts, ix, ok = args
        z = math.einsum("gtd,sd->gts", qs, k) / jnp.sqrt(jnp.float32(d))
        see = jnp.arange(s)[None] <= ts[:, None]
        if not dense:
            see = see & _block_mask(ix, ok, n_blocks)[:, key_block]
        z = jnp.where(see[None], z, -jnp.inf)
        return math.einsum("gts,sd->gtd", jax.nn.softmax(z, -1), val)

    t = jnp.arange(s).reshape(s // qb, qb)
    qs = jnp.moveaxis(q.reshape(g, s // qb, qb, d), 1, 0)
    if dense:
        ix = ok = jnp.zeros((s // qb, qb, 1), jnp.int32)
    else:
        ix, ok = idx.reshape(s // qb, qb, -1), valid.reshape(s // qb, qb, -1)
    out = jax.lax.map(jax.checkpoint(rows), (qs, t, ix, ok))
    return jnp.moveaxis(out, 0, 1).reshape(g, s, d)


def _sparse_mixer(y, lp, v, math):
    b, s, _ = y.shape
    hq, hkv, d = v["num_attention_heads"], v["num_key_value_heads"], \
        v["head_dim"]
    eps, sc = v["rms_norm_eps"], sparse_config(v)
    q = _rms_norm(math.einsum("bsh,hk->bsk", y, lp["q.weight"])
                  .reshape(b, s, hq, d), lp["q_norm.weight"], eps)
    k = _rms_norm(math.einsum("bsh,hk->bsk", y, lp["k.weight"])
                  .reshape(b, s, hkv, d), lp["k_norm.weight"], eps)
    val = math.einsum("bsh,hk->bsk", y, lp["v.weight"]).reshape(b, s, hkv, d)
    # [B, Hkv, G, S, D] and [B, Hkv, S, D]
    qg = q.reshape(b, s, hkv, hq // hkv, d).transpose(0, 2, 3, 1, 4)
    kg, vg = k.transpose(0, 2, 1, 3), val.transpose(0, 2, 1, 3)
    out = jnp.stack([jnp.stack([
        _sparse_group(qg[i, j], kg[i, j], vg[i, j], sc, math)
        for j in range(hkv)]) for i in range(b)])          # [B,Hkv,G,S,D]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, hq * d)
    return math.einsum("bsk,kh->bsh",
                       out * output_gate(y, lp["g.weight"], math),
                       lp["o.weight"])


# -- the lightning mixer ------------------------------------------------------

def decay_rates(v: dict, layer: int):
    """``-log(lambda_h)`` of every head of published layer ``layer``,
    float64 numpy: ``slope_h (1 - l / (L - 1) + 1e-5)``."""
    heads, depth = v["lightning_nh"], v["residual_scale_layers"]
    slope = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    return slope * (1.0 - layer / (depth - 1) + 1e-5)


def _lightning_head(q, k, val, rate, math):
    """One head: q, k, val [S, D], ``rate`` = -log(lambda) -> [S, D]."""
    s, d = q.shape
    qb = _query_block(s)

    def rows(args):
        qs, ts = args
        z = math.einsum("td,sd->ts", qs, k) / jnp.sqrt(jnp.float32(d))
        gap = (ts[:, None] - jnp.arange(s)[None]).astype(jnp.float32)
        w = jnp.where(gap >= 0, jnp.exp(-rate * jnp.maximum(gap, 0.0)), 0.0)
        return math.einsum("ts,sd->td", z * w, val)

    t = jnp.arange(s).reshape(s // qb, qb)
    out = jax.lax.map(jax.checkpoint(rows), (q.reshape(s // qb, qb, d), t))
    return out.reshape(s, d)


def _lightning_mixer(y, lp, v, math, layer):
    b, s, _ = y.shape
    heads, d = v["lightning_nh"], v["lightning_head_dim"]
    eps, theta = v["rms_norm_eps"], float(v["rope_theta"])

    def proj(name):
        return math.einsum("bsh,hk->bsk", y, lp[name]).reshape(b, s, heads, d)
    q = _rope(_rms_norm(proj("q.weight"), lp["q_norm.weight"], eps), theta)
    k = _rope(_rms_norm(proj("k.weight"), lp["k_norm.weight"], eps), theta)
    val = proj("v.weight")
    rates = jnp.asarray(decay_rates(v, layer), jnp.float32)

    def head(args):
        qh, kh, vh, rate = args          # [B, S, D] each
        return jnp.stack([_lightning_head(qh[i], kh[i], vh[i], rate, math)
                          for i in range(b)])
    out = jax.lax.map(head, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                             jnp.moveaxis(val, 2, 0), rates))
    out = _rms_norm(jnp.moveaxis(out, 0, 2).reshape(b, s, heads * d),
                    lp["o_norm.weight"], eps)
    return math.einsum("bsk,kh->bsh",
                       out * output_gate(y, lp["g.weight"], math),
                       lp["o.weight"])


# -- the model ----------------------------------------------------------------

def _layer(x, lp, v, math, layer, kind):
    eps = v["rms_norm_eps"]
    a = v["scale_depth"] / float(np.sqrt(v["residual_scale_layers"]))
    y = _rms_norm(x, lp["input_norm.weight"], eps)
    mix = _sparse_mixer(y, lp, v, math) if kind == SPARSE \
        else _lightning_mixer(y, lp, v, math, layer)
    x = x + a * mix
    y = _rms_norm(x, lp["post_norm.weight"], eps)
    gate = math.einsum("bsh,hi->bsi", y, lp["gate.weight"])
    up = math.einsum("bsh,hi->bsi", y, lp["up.weight"])
    return x + a * math.einsum("bsi,ih->bsh", jax.nn.silu(gate) * up,
                               lp["down.weight"])


def hidden_states(params, ids, v: dict, math):
    """The residual stream after the last layer, before the final norm."""
    p = {k: a.astype(jnp.float32) for k, a in params.items()}
    x = v["scale_emb"] * p["embed"][ids]
    for i, kind in enumerate(_mixers(v)):
        pre = f"layers.{i}."
        lp = {k[len(pre):]: a for k, a in p.items() if k.startswith(pre)}
        x = jax.checkpoint(
            lambda xx, ll, i=i, kind=kind: _layer(xx, ll, v, math, i, kind))(
                x, lp)
    return x, p


def layer_choices(params, ids, v: dict, math) -> dict:
    """layer index -> the blocks each query of each kv head chose, [B, Hkv,
    S, blocks] bool, for every ``minicpm4`` layer of a sequence past
    ``dense_len``: what ``tools/readings_sala.py`` counts the program's
    choices against."""
    p = {k: a.astype(jnp.float32) for k, a in params.items()}
    x = v["scale_emb"] * p["embed"][ids]
    b, s = ids.shape
    hq, hkv, d = v["num_attention_heads"], v["num_key_value_heads"], \
        v["head_dim"]
    eps, sc = v["rms_norm_eps"], sparse_config(v)
    out = {}
    for i, kind in enumerate(_mixers(v)):
        pre = f"layers.{i}."
        lp = {k[len(pre):]: a for k, a in p.items() if k.startswith(pre)}
        if kind == SPARSE and s > sc["dense_len"]:
            y = _rms_norm(x, lp["input_norm.weight"], eps)
            q = _rms_norm(math.einsum("bsh,hk->bsk", y, lp["q.weight"])
                          .reshape(b, s, hkv, hq // hkv, d),
                          lp["q_norm.weight"], eps)
            k = _rms_norm(math.einsum("bsh,hk->bsk", y, lp["k.weight"])
                          .reshape(b, s, hkv, d), lp["k_norm.weight"], eps)
            rows = []
            for bi in range(b):
                for j in range(hkv):
                    idx, ok = chosen_blocks(q[bi, :, j].transpose(1, 0, 2),
                                            k[bi, :, j], sc)
                    rows.append(_block_mask(idx, ok, s // sc["block_size"]))
            out[i] = jnp.stack(rows).reshape(b, hkv, s, -1)
        x = _layer(x, lp, v, math, i, kind)
    return out


def token_losses(params, ids, labels, v: dict, math):
    """Cross entropy of every token, [B, S] float32."""
    x, p = hidden_states(params, ids, v, math)
    x = _rms_norm(x, p["norm.weight"], v["rms_norm_eps"]) \
        / (v["hidden_size"] / v["dim_model_base"])
    logits = math.einsum("bsh,hv->bsv", x, p["head.weight"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - picked
