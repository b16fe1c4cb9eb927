"""Plain reference of the GLM MoE "lite" decoder (zai-org, ``model_type``
"glm4_moe_lite"; ``huggingface.co/zai-org/GLM-4.7-Flash`` ``config.json``),
written from the config's keys: token embedding, pre-RMSNorm blocks of latent
attention and a SwiGLU or sparse FFN, a final RMSNorm, an untied head, and one
multi-token-prediction module in the loss. float32 ``jax.numpy``, no kernels,
no sort, no grouped product. Nothing here imports the program.

A layer, input x [S, H], ``u = RMSNorm(x)`` (eps ``rms_norm_eps``, no biases):

- Attention (every layer, the MTP module's too). ``cq = RMSNorm(u Wqa)``
  (``q_lora_rank`` wide); ``q = cq Wqb``, ``num_attention_heads`` heads of
  ``qk_nope_head_dim + qk_rope_head_dim``, each ``[q_nope; q_rope]``.
  ``[ckv; k_rope] = u Wkva`` (``kv_lora_rank`` and ``qk_rope_head_dim`` wide);
  ``ckv <- RMSNorm(ckv)``; ``[k_nope_h; v_h] = ckv Wkvb`` for each head
  (``qk_nope_head_dim`` and ``v_head_dim`` wide). ``q_rope_h`` and the ONE
  ``k_rope`` a token are rotated by ``pos * theta^(-2i / rope)`` over pairs
  (x[2i], x[2i+1]) (the Hugging Face port rotates halves: the same model under
  a fixed permutation of columns); ``rope_scaling`` is null, so nothing
  scales the frequencies or the scores. ``k_h = [k_nope_h; k_rope]``,
  ``o_h = softmax_causal(q_h k_h^T / sqrt(nope + rope)) v_h``,
  ``out = concat_h(o_h) Wo``. Scores are explicit, under a plain mask.
- FFN on ``t = RMSNorm(h)``. The first ``first_k_dense_replace`` layers:
  SwiGLU at ``intermediate_size``. Every later one, and the MTP module's:
  ``s = sigmoid(t Wr)`` over ALL ``of`` experts (float32 at highest precision
  whatever ``math`` is: it decides a top-k); the chosen ``T`` are the
  ``num_experts_per_tok`` largest of ``s + b`` (``n_group = topk_group = 1``:
  no group step), ``b`` the layer's frozen correction bias; ``w_e = s_e /
  sum_T s`` (``norm_topk_prob``), ``b`` taking no part; ``y =
  SwiGLU_shared(t) + routed_scaling_factor * sum_{e in T} w_e SwiGLU_e(t)``,
  both at ``moe_intermediate_size``.

After the ``num_hidden_layers`` layers and the final norm, with ``h_t`` the
normed output and ``labels[t] = id_{t+1}``: the main logits are ``h_t Wh``;
the MTP module computes ``z_t = [RMSNorm_e(Emb(labels[t])); RMSNorm_h(h_t)]
Weh``, one sparse layer of its own on z, ``RMSNorm_s``, and the SAME ``Wh``:
its logits at t predict ``labels[t + 1]``; the last position has no target.
``loss = mean_t CE_main + mtp_loss_weight * mean_{t < S-1} CE_mtp``.
``token_losses`` returns, per token, ``CE_main[t] + mtp_loss_weight * S /
(S - 1) * CE_mtp[t] [t < S - 1]``, whose mean over all the batch's tokens is
that loss (``reference/train.py::follow`` sums rows and divides by the count).

The chip's share: ``expert_share`` = {first, held, of} (absent: all held).
The router is ``of`` wide, ``w_e`` is normalised over all the chosen, and the
sum runs over the chosen experts with ``first <= e < first + held`` only,
expert by expert under a plain mask; the shared expert is whole. What the
absent experts would add is left out. The vocabulary is whatever
``vocab_size`` says: a slice is a smaller vocabulary.

**The correction bias is data, not a leaf**: ``score_bias(values, layer)``
makes it, ``normal(0, score_bias.scale)`` from ``score_bias.seed`` of the
configuration's file and the layer's index, rounded to the storage dtype. It
does not follow ``--seed``: the runner hands a family's ``token_losses`` no
seed and compares trained leaves only, and the bias is no trained leaf.

Matrices are stored [in, out]; expert matrices are stacked [held, in, out].
Attention walks one head at a time and the experts one at a time, under
``jax.checkpoint``, so that nothing larger than a few S x S is alive at once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
MTP = "mtp."


def _share(v: dict):
    s = v.get("expert_share") or {"first": 0, "held": v["n_routed_experts"],
                                  "of": v["n_routed_experts"]}
    return s["first"], s["held"], s["of"]


def _sparse(v: dict) -> list:
    """Per main layer: does it carry the experts?"""
    return [i >= v["first_k_dense_replace"]
            for i in range(v["num_hidden_layers"])]


def _attention_params(v: dict) -> int:
    h, heads = v["hidden_size"], v["num_attention_heads"]
    dn, dr, dv = v["qk_nope_head_dim"], v["qk_rope_head_dim"], v["v_head_dim"]
    return (h * v["q_lora_rank"] + v["q_lora_rank"] * heads * (dn + dr)
            + h * (v["kv_lora_rank"] + dr)
            + v["kv_lora_rank"] * heads * (dn + dv) + heads * dv * h)


def dims(v: dict) -> dict:
    """The sizes ``harness/flops.py::train_flops_per_token`` needs, such that
    it counts what one token's step requires here and no more. That function
    computes ``6 (layers x layer_matmul_params + vocab x hidden) + layers x
    6 S heads D``, so:

    - ``layers`` counts every attention call of a step: the main layers and
      the MTP module's one, each causal at ``heads`` heads of ``nope + rope``
      (= ``v_head_dim``): the scores' product and the values' are as wide.
    - ``layer_matmul_params`` is the MEAN over those of the parameters a
      token multiplies: the five latent projections; then the dense MLP's
      3 H I, or the router's H x of, the shared expert's 3 H f and, in
      expectation, ``k x held / of`` routed experts of 3 H f each (half an
      expert at top-4 with 8 of 64 held). The MTP module adds ``2 H x H``
      (``Weh``) and the head's SECOND pass, vocab x hidden, folded in here
      since the function counts the head once.
    """
    h = v["hidden_size"]
    _, held, of = _share(v)
    f = v["moe_intermediate_size"]
    sparse = h * of + 3 * h * f * v["n_shared_experts"] \
        + (v["num_experts_per_tok"] * held / of) * 3 * h * f
    attn = _attention_params(v)
    total = sum(attn + (sparse if s else 3 * h * v["intermediate_size"])
                for s in _sparse(v))
    n = v["num_hidden_layers"]
    if v["num_nextn_predict_layers"]:
        total += attn + sparse + 2 * h * h + v["vocab_size"] * h
        n += 1
    return {"hidden": h, "layers": n, "heads": v["num_attention_heads"],
            "kv_heads": v["num_attention_heads"],
            "head_dim": v["qk_nope_head_dim"] + v["qk_rope_head_dim"],
            "vocab": v["vocab_size"], "layer_matmul_params": total / n}


def _layer_shapes(v: dict, sparse: bool) -> dict:
    h, heads = v["hidden_size"], v["num_attention_heads"]
    dn, dr, dv = v["qk_nope_head_dim"], v["qk_rope_head_dim"], v["v_head_dim"]
    rq, rkv = v["q_lora_rank"], v["kv_lora_rank"]
    _, held, of = _share(v)
    f, inner = v["moe_intermediate_size"], v["intermediate_size"]
    fs = f * v["n_shared_experts"]
    out = {"input_norm.weight": ((h,), "ones"),
           "q_a.weight": ((h, rq), "normal"),
           "q_a_norm.weight": ((rq,), "ones"),
           "q_b.weight": ((rq, heads * (dn + dr)), "normal"),
           "kv_a.weight": ((h, rkv + dr), "normal"),
           "kv_a_norm.weight": ((rkv,), "ones"),
           "kv_b.weight": ((rkv, heads * (dn + dv)), "normal"),
           "o.weight": ((heads * dv, h), "normal"),
           "post_norm.weight": ((h,), "ones")}
    if sparse:
        out.update({"router.weight": ((h, of), "normal"),
                    "experts.gate": ((held, h, f), "normal"),
                    "experts.up": ((held, h, f), "normal"),
                    "experts.down": ((held, f, h), "normal"),
                    "shared.gate.weight": ((h, fs), "normal"),
                    "shared.up.weight": ((h, fs), "normal"),
                    "shared.down.weight": ((fs, h), "normal")})
    else:
        out.update({"gate.weight": ((h, inner), "normal"),
                    "up.weight": ((h, inner), "normal"),
                    "down.weight": ((inner, h), "normal")})
    return out


def param_shapes(v: dict) -> dict:
    """Every TRAINED leaf. The correction biases are not among them
    (``score_bias``)."""
    h, vocab = v["hidden_size"], v["vocab_size"]
    out = {"embed": ((vocab, h), "normal")}
    for i, sparse in enumerate(_sparse(v)):
        for k, s in _layer_shapes(v, sparse).items():
            out[f"layers.{i}.{k}"] = s
    out["norm.weight"] = ((h,), "ones")
    out["head.weight"] = ((h, vocab), "normal")
    if v["num_nextn_predict_layers"]:
        out[MTP + "enorm.weight"] = ((h,), "ones")
        out[MTP + "hnorm.weight"] = ((h,), "ones")
        out[MTP + "eh.weight"] = ((2 * h, h), "normal")
        for k, s in _layer_shapes(v, True).items():
            out[MTP + "layer." + k] = s
        out[MTP + "norm.weight"] = ((h,), "ones")
    return out


def score_bias(v: dict, layer: int) -> np.ndarray:
    """The frozen correction bias of sparse layer ``layer`` (the MTP
    module's layer is ``num_hidden_layers``), [of] float32 holding values of
    the storage dtype. (The benchmark's fault tools put zeros in its
    place.)"""
    _, _, of = _share(v)
    sb = v["score_bias"]
    b = np.random.default_rng([int(sb["seed"]), int(layer)]).normal(
        0.0, float(sb["scale"]), of).astype(np.float32)
    return b.astype(jnp.dtype(v["dtype"])).astype(np.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _rope(x, theta: float):
    """x: [B, S, ..., R]; rotate the pairs (2i, 2i+1) of the last axis by
    ``pos * theta^(-2i / R)``."""
    s, r = x.shape[1], x.shape[-1]
    inv = float(theta) ** (-2.0 * np.arange(r // 2, dtype=np.float64) / r)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None]
    shape = (1, s) + (1,) * (x.ndim - 3) + (r // 2,)
    cos = jnp.asarray(np.cos(ang), jnp.float32).reshape(shape)
    sin = jnp.asarray(np.sin(ang), jnp.float32).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def visible(s: int):
    """[S, S] bool: key j visible to query i."""
    return jnp.asarray(np.arange(s)[None, :] <= np.arange(s)[:, None])


def qkv(u, lp, v, math):
    """(q [B,S,H,nope+rope], k_nope [B,S,H,nope], the one rotated k_rope
    [B,S,rope], values [B,S,H,v]) of the normed input u."""
    b, s, _ = u.shape
    heads = v["num_attention_heads"]
    dn, dr, dv = v["qk_nope_head_dim"], v["qk_rope_head_dim"], v["v_head_dim"]
    eps, theta = v["rms_norm_eps"], v["rope_theta"]
    cq = _rms_norm(math.einsum("bsh,hr->bsr", u, lp["q_a.weight"]),
                   lp["q_a_norm.weight"], eps)
    q = math.einsum("bsr,rk->bsk", cq, lp["q_b.weight"]).reshape(
        b, s, heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], axis=-1)
    kva = math.einsum("bsh,hr->bsr", u, lp["kv_a.weight"])
    rkv = v["kv_lora_rank"]
    ckv = _rms_norm(kva[..., :rkv], lp["kv_a_norm.weight"], eps)
    k_rope = _rope(kva[..., rkv:], theta)
    kv = math.einsum("bsr,rk->bsk", ckv, lp["kv_b.weight"]).reshape(
        b, s, heads, dn + dv)
    return q, kv[..., :dn], k_rope, kv[..., dn:]


def _attention(u, lp, v, math):
    b, s, _ = u.shape
    heads, dv = v["num_attention_heads"], v["v_head_dim"]
    q, k_nope, k_rope, val = qkv(u, lp, v, math)
    mask = visible(s)
    scale = 1.0 / np.sqrt(float(q.shape[-1]))

    def head(args):
        qh, kn, vh = args           # [B,S,nope+rope], [B,S,nope], [B,S,v]
        kh = jnp.concatenate([kn, k_rope], axis=-1)
        sc = math.einsum("bqd,bkd->bqk", qh, kh) * scale
        sc = jnp.where(mask[None], sc, -jnp.inf)
        return math.einsum("bqk,bkd->bqd", jax.nn.softmax(sc, -1), vh)

    att = jax.lax.map(jax.checkpoint(head), tuple(
        jnp.moveaxis(a, 2, 0) for a in (q, k_nope, val)))
    att = jnp.moveaxis(att, 0, 2).reshape(b, s, heads * dv)
    return math.einsum("bsk,kh->bsh", att, lp["o.weight"])


def _swiglu(t, gate, up, down, math):
    a = jax.nn.silu(math.einsum("bsh,hi->bsi", t, gate)) \
        * math.einsum("bsh,hi->bsi", t, up)
    return math.einsum("bsi,ih->bsh", a, down)


def route(scores, k: int, bias):
    """scores [..., E], bias [E] -> weights [..., E]: the k largest of
    ``scores + bias`` are chosen; each gets its SCORE over the chosen
    scores' sum, zero elsewhere. (The benchmark's fault tools put a wrong
    one in its place.)"""
    _, idx = jax.lax.top_k(scores + bias, k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    w = top / jnp.sum(top, axis=-1, keepdims=True)
    hot = jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.dtype)
    return jnp.einsum("...k,...ke->...e", w, hot, precision=_HIGHEST)


def router_scores(t, wr):
    return jax.nn.sigmoid(jnp.einsum(
        "bsh,he->bse", t.astype(jnp.float32), wr.astype(jnp.float32),
        precision=_HIGHEST))


def _experts(t, lp, v, math, layer: int):
    first, held, _ = _share(v)
    w = route(router_scores(t, lp["router.weight"]),
              v["num_experts_per_tok"],
              jnp.asarray(score_bias(v, layer)))[..., first:first + held]

    def one(acc, xs):
        we, gate, up, down = xs     # [B,S], [H,f], [H,f], [f,H]
        return acc + we[..., None] * _swiglu(t, gate, up, down, math), None

    routed, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(t),
        (jnp.moveaxis(w, -1, 0), lp["experts.gate"], lp["experts.up"],
         lp["experts.down"]))
    shared = _swiglu(t, lp["shared.gate.weight"], lp["shared.up.weight"],
                     lp["shared.down.weight"], math)
    return shared + v["routed_scaling_factor"] * routed


def _layer(x, lp, layer, v, math):
    """One block; ``layer`` is its index (the MTP module's layer is
    ``num_hidden_layers``), which says whether it is sparse and which
    correction bias it holds."""
    eps = v["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, lp["input_norm.weight"], eps), lp, v,
                       math)
    t = _rms_norm(x, lp["post_norm.weight"], eps)
    if layer >= v["first_k_dense_replace"]:
        return x + _experts(t, lp, v, math, layer)
    return x + _swiglu(t, lp["gate.weight"], lp["up.weight"],
                       lp["down.weight"], math)


def _sub(p, pre):
    return {k[len(pre):]: a for k, a in p.items() if k.startswith(pre)}


def _float32(params, ids, v):
    if ids.shape[1] != v["sequence_length"]:
        raise ValueError(f"sequence length {ids.shape[1]}: the configuration "
                         f"states {v['sequence_length']}")
    return {k: a.astype(jnp.float32) for k, a in params.items()}


def _main(p, ids, v, math):
    """The main model's output after its final norm, [B, S, H]."""
    x = p["embed"][ids]
    for i in range(v["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda xx, ll, i=i: _layer(xx, ll, i, v, math))(
                x, _sub(p, f"layers.{i}."))
    return _rms_norm(x, p["norm.weight"], v["rms_norm_eps"])


def _mtp_input(p, h, next_ids, v, math):
    """z = [RMSNorm_e(Emb(next)); RMSNorm_h(h)] Weh, [B, S, H]."""
    eps = v["rms_norm_eps"]
    z = jnp.concatenate(
        [_rms_norm(p["embed"][next_ids], p[MTP + "enorm.weight"], eps),
         _rms_norm(h, p[MTP + "hnorm.weight"], eps)], axis=-1)
    return math.einsum("bsk,kh->bsh", z, p[MTP + "eh.weight"])


def _mtp(p, h, next_ids, v, math):
    """The MTP module's output before the head, [B, S, H]."""
    z = jax.checkpoint(lambda zz, ll: _layer(
        zz, ll, v["num_hidden_layers"], v, math))(
            _mtp_input(p, h, next_ids, v, math), _sub(p, MTP + "layer."))
    return _rms_norm(z, p[MTP + "norm.weight"], v["rms_norm_eps"])


def _ce(h, head, targets, math):
    logits = math.einsum("bsh,hv->bsv", h, head)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked


def logits(params, ids, labels, v: dict, math):
    """(main logits, MTP logits), each [B, S, vocab]: position t of the
    first predicts ``labels[t]``, of the second ``labels[t + 1]``."""
    p = _float32(params, ids, v)
    h = _main(p, ids, v, math)
    return (math.einsum("bsh,hv->bsv", h, p["head.weight"]),
            math.einsum("bsh,hv->bsv", _mtp(p, h, labels, v, math),
                        p["head.weight"]))


def token_losses(params, ids, labels, v: dict, math):
    """[B, S] float32 whose mean over the batch's tokens is the loss (the
    module docstring says how the MTP term is spread over the tokens)."""
    p = _float32(params, ids, v)
    h = _main(p, ids, v, math)
    ce = jax.checkpoint(lambda hh, w: _ce(hh, w, labels, math))
    out = ce(h, p["head.weight"])
    weight = v["mtp_loss_weight"] if v["num_nextn_predict_layers"] else 0.0
    if not weight:      # the module trains by this term alone
        return out
    s = ids.shape[1]
    targets = jnp.concatenate([labels[:, 1:], labels[:, :1]], axis=1)
    ce = jax.checkpoint(lambda hh, w: _ce(hh, w, targets, math))
    extra = ce(_mtp(p, h, labels, v, math), p["head.weight"])
    has_target = jnp.arange(s)[None, :] < s - 1
    return out + weight * (s / (s - 1.0)) * jnp.where(has_target, extra, 0.0)


def chosen_experts(params, ids, v: dict, math) -> dict:
    """layer index -> the experts each token chose there, [B, S, k] int32,
    sorted: what the program's own choice is counted against. The MTP
    module's layer (``num_hidden_layers``) is fed the ids' own successors."""
    p = _float32(params, ids, v)
    eps, k, out = v["rms_norm_eps"], v["num_experts_per_tok"], {}

    def chosen(x, lp, layer):
        h = x + _attention(_rms_norm(x, lp["input_norm.weight"], eps), lp, v,
                           math)
        s = router_scores(_rms_norm(h, lp["post_norm.weight"], eps),
                          lp["router.weight"])
        return jnp.sort(jax.lax.top_k(
            s + jnp.asarray(score_bias(v, layer)), k)[1], -1)

    x = p["embed"][ids]
    for i, sparse in enumerate(_sparse(v)):
        lp = _sub(p, f"layers.{i}.")
        if sparse:
            out[i] = chosen(x, lp, i)
        x = _layer(x, lp, i, v, math)
    if v["num_nextn_predict_layers"]:
        h = _rms_norm(x, p["norm.weight"], eps)
        nxt = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], 1)
        n = v["num_hidden_layers"]
        out[n] = chosen(_mtp_input(p, h, nxt, v, math),
                        _sub(p, MTP + "layer."), n)
    return out
