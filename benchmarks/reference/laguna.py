"""Plain reference of the Laguna decoder (poolside, ``model_type`` "laguna";
``huggingface.co/poolside/Laguna-XS.2`` ``config.json``), written from the
config's keys: token embedding, pre-RMSNorm blocks, a final RMSNorm, an untied
head, token-mean cross entropy and no balance term. float32 ``jax.numpy``, no
kernels, no sort, no grouped product. Nothing here imports the program.

Layer ``l``, input x [S, H], ``u = RMSNorm(x)``:

- Attention with ``num_attention_heads_per_layer[l]`` query heads over
  ``num_key_value_heads`` kv heads of ``head_dim``; head h reads kv head
  ``h // (heads / kv_heads)``. Rotary embedding on q and k: a
  ``full_attention`` layer rotates the first ``partial_rotary_factor`` of
  each head with YaRN frequencies (``yarn_inv_freq``) and multiplies cos and
  sin by ``attention_factor``; a ``sliding_attention`` layer rotates the
  whole head with ``theta ** (-2i / D)``. Pairs are (x[2i], x[2i+1]) (the
  Hugging Face port rotates halves: the same model under a fixed permutation
  of q's and k's columns). Key j is visible to query i when ``j <= i`` and,
  on a sliding layer, ``i - j < sliding_window``. Each head's output is
  scaled by its gate, ``sigmoid(u Wg)[h]``, before the output projection.
- FFN on ``t = RMSNorm(h)``: a ``dense`` layer is SwiGLU at
  ``intermediate_size``; a ``sparse`` layer is ``SwiGLU_shared(t) +
  moe_routed_scaling_factor * sum_{e in T} w_e SwiGLU_e(t)`` with
  ``s = sigmoid(t Wr)`` over ALL the experts (the router is float32 at
  highest precision whatever ``math`` is: it decides a top-k), ``T`` the
  ``num_experts_per_tok`` largest and ``w_e = s_e / sum_T s``.

The chip's share: ``expert_share`` = {first, held, of} (absent: all held).
The router is ``of`` wide, ``w_e`` is normalised over all the chosen, and the
sum runs over the chosen experts with ``first <= e < first + held`` only,
expert by expert under a plain mask; the shared expert is whole. What the
absent experts would add is left out. The vocabulary is whatever
``vocab_size`` says: a slice is a smaller vocabulary.

Matrices are stored [in, out]; expert matrices are stacked [held, in, out].
Attention walks one query head at a time and the experts one at a time, under
``jax.checkpoint``, so that nothing larger than a few S x S is alive at once
(a kv group's 8 heads at S 8192 would be 2 GiB a copy, and the backward pass
holds four).
"""
from __future__ import annotations

import math as _m

import jax
import jax.numpy as jnp
import numpy as np

FULL, SLIDING, SPARSE = "full_attention", "sliding_attention", "sparse"
_HIGHEST = jax.lax.Precision.HIGHEST


def _share(v: dict):
    s = v.get("expert_share") or {"first": 0, "held": v["num_experts"],
                                  "of": v["num_experts"]}
    return s["first"], s["held"], s["of"]


def _layers(v: dict):
    """(attention kind, query heads, sparse?) of each layer that is run."""
    n = v["num_hidden_layers"]
    return list(zip(v["layer_types"][:n],
                    v["num_attention_heads_per_layer"][:n],
                    [m == SPARSE for m in v["mlp_layer_types"][:n]]))


def dims(v: dict) -> dict:
    """The sizes ``harness/flops.py::train_flops_per_token`` needs, such that
    it counts what one token's step requires here and no more. That function
    computes ``6 (layers x layer_matmul_params + vocab x hidden) + layers x
    6 S heads D``, and has no window term, so:

    - ``layer_matmul_params`` is the MEAN over the layers of the parameters
      a token multiplies: that layer's q, k, v, o and gate; then the dense
      MLP's 3 H I, or the router's H x of, the shared expert's 3 H fs and, in
      expectation, ``k x held / of`` routed experts of 3 H f each (one expert
      at top-8 with 32 of 256 held). A sliding layer adds ``2 heads D n``
      there, which the 6 turns into its band's ``12 heads D n`` FLOPs a
      token, with ``n = W - W (W - 1) / (2 S)`` the keys a query sees on
      average over a sequence of ``S = sequence_length``.
    - ``heads`` is the full layers' query heads summed over ALL the layers'
      count, so that ``layers x 6 S heads D`` is the full layers' causal
      attention alone.
    """
    h, d, hkv = v["hidden_size"], v["head_dim"], v["num_key_value_heads"]
    first, held, of = _share(v)
    k, f = v["num_experts_per_tok"], v["moe_intermediate_size"]
    w, s = v["sliding_window"], v["sequence_length"]
    band = w - w * (w - 1) / (2.0 * s) if w < s else (s + 1) / 2.0
    layers = _layers(v)
    total, full_heads = 0.0, 0
    for kind, hq, sparse in layers:
        total += 2 * h * hq * d + 2 * h * hkv * d + h * hq
        if kind == SLIDING:
            total += 2 * hq * d * band
        else:
            full_heads += hq
        if sparse:
            total += h * of + 3 * h * v["shared_expert_intermediate_size"] \
                + (k * held / of) * 3 * h * f
        else:
            total += 3 * h * v["intermediate_size"]
    n = len(layers)
    return {"hidden": h, "layers": n, "heads": full_heads / n,
            "kv_heads": hkv, "head_dim": d, "vocab": v["vocab_size"],
            "layer_matmul_params": total / n}


def param_shapes(v: dict) -> dict:
    h, d, hkv = v["hidden_size"], v["head_dim"], v["num_key_value_heads"]
    _, held, of = _share(v)
    f, fs = v["moe_intermediate_size"], v["shared_expert_intermediate_size"]
    inner, vocab = v["intermediate_size"], v["vocab_size"]
    out = {"embed": ((vocab, h), "normal")}
    for i, (_, hq, sparse) in enumerate(_layers(v)):
        b = f"layers.{i}."
        out[b + "input_norm.weight"] = ((h,), "ones")
        out[b + "q.weight"] = ((h, hq * d), "normal")
        out[b + "k.weight"] = ((h, hkv * d), "normal")
        out[b + "v.weight"] = ((h, hkv * d), "normal")
        out[b + "o.weight"] = ((hq * d, h), "normal")
        if v.get("gating", True):
            out[b + "g.weight"] = ((h, hq), "normal")
        out[b + "post_norm.weight"] = ((h,), "ones")
        if sparse:
            out[b + "router.weight"] = ((h, of), "normal")
            out[b + "experts.gate"] = ((held, h, f), "normal")
            out[b + "experts.up"] = ((held, h, f), "normal")
            out[b + "experts.down"] = ((held, f, h), "normal")
            out[b + "shared.gate.weight"] = ((h, fs), "normal")
            out[b + "shared.up.weight"] = ((h, fs), "normal")
            out[b + "shared.down.weight"] = ((fs, h), "normal")
        else:
            out[b + "gate.weight"] = ((h, inner), "normal")
            out[b + "up.weight"] = ((h, inner), "normal")
            out[b + "down.weight"] = ((inner, h), "normal")
    out["norm.weight"] = ((h,), "ones")
    out["head.weight"] = ((h, vocab), "normal")
    return out


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def yarn_inv_freq(rot: int, p: dict) -> np.ndarray:
    """``inv_extra_i = base^(-2i/rot)``, ``inv_inter_i = inv_extra_i /
    factor``, ``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``
    with ``c(n) = rot ln(L / (2 pi n)) / (2 ln base)`` clipped to [0, rot -
    1], ``ramp_i = clip((i - low) / (high - low), 0, 1)``, ``inv_i =
    inv_inter_i ramp_i + inv_extra_i (1 - ramp_i)``, i = 0 .. rot/2 - 1."""
    base, factor = float(p["rope_theta"]), float(p["factor"])
    length = p["original_max_position_embeddings"]
    i = np.arange(rot // 2, dtype=np.float64)
    extra = base ** (-2.0 * i / rot)

    def c(n):
        return rot * _m.log(length / (2 * _m.pi * n)) / (2 * _m.log(base))
    low = min(max(_m.floor(c(p["beta_fast"])), 0), rot - 1)
    high = min(max(_m.ceil(c(p["beta_slow"])), 0), rot - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / factor) * ramp + extra * (1.0 - ramp)


def rope_inv_freq(head_dim: int, p: dict):
    """(inverse frequencies [rot / 2], the factor on cos and sin)."""
    rot = int(head_dim * p.get("partial_rotary_factor", 1))
    if p.get("rope_type", "default") == "yarn":
        return yarn_inv_freq(rot, p), float(p.get("attention_factor") or 1.0)
    i = np.arange(rot // 2, dtype=np.float64)
    return float(p["rope_theta"]) ** (-2.0 * i / rot), 1.0


def _rope(x, inv, scale):
    """x: [B, S, H, D]; rotate the pairs (2i, 2i+1) of the first
    ``2 len(inv)`` dims by ``pos * inv_i``; the rest pass."""
    s, rot = x.shape[1], 2 * len(inv)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang) * scale, jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang) * scale, jnp.float32)[None, :, None]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                       axis=-1).reshape(x.shape[:-1] + (rot,))
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def visible(s: int, window=None):
    """[S, S] bool: key j visible to query i."""
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    mask = j <= i
    if window is not None:
        mask &= (i - j) < window
    return jnp.asarray(mask)


def _attention(u, lp, kind, hq, v, math):
    b, s, _ = u.shape
    hkv, d = v["num_key_value_heads"], v["head_dim"]
    g = hq // hkv
    inv, scale = rope_inv_freq(d, v["rope_parameters"][kind])
    q = _rope(math.einsum("bsh,hk->bsk", u, lp["q.weight"])
              .reshape(b, s, hq, d), inv, scale)
    k = _rope(math.einsum("bsh,hk->bsk", u, lp["k.weight"])
              .reshape(b, s, hkv, d), inv, scale)
    val = math.einsum("bsh,hk->bsk", u, lp["v.weight"]).reshape(b, s, hkv, d)
    mask = visible(s, v["sliding_window"] if kind == SLIDING else None)

    def head(args):
        qh, kv = args               # [B,S,D], the index of its kv head
        kh, vh = (jax.lax.dynamic_index_in_dim(a, kv, 2, keepdims=False)
                  for a in (k, val))
        sc = math.einsum("bqd,bkd->bqk", qh, kh) / jnp.sqrt(float(d))
        sc = jnp.where(mask[None], sc, -jnp.inf)
        return math.einsum("bqk,bkd->bqd", jax.nn.softmax(sc, -1), vh)

    att = jax.lax.map(jax.checkpoint(head),
                      (jnp.moveaxis(q, 2, 0), jnp.arange(hq) // g))
    att = jnp.moveaxis(att, 0, 2)
    if "g.weight" in lp:
        gate = jax.nn.sigmoid(math.einsum("bsh,hn->bsn", u, lp["g.weight"]))
        att = att * gate[..., None]
    return math.einsum("bsk,kh->bsh", att.reshape(b, s, hq * d),
                       lp["o.weight"])


def _swiglu(t, gate, up, down, math):
    a = jax.nn.silu(math.einsum("bsh,hi->bsi", t, gate)) \
        * math.einsum("bsh,hi->bsi", t, up)
    return math.einsum("bsi,ih->bsh", a, down)


def route(scores, k: int):
    """scores [..., E] -> weights [..., E]: each of the k largest scores over
    their sum, zero elsewhere. (The benchmark's fault tools put a wrong one
    in its place.)"""
    top, idx = jax.lax.top_k(scores, k)
    w = top / jnp.sum(top, axis=-1, keepdims=True)
    hot = jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.dtype)
    return jnp.einsum("...k,...ke->...e", w, hot, precision=_HIGHEST)


def router_scores(t, wr):
    return jax.nn.sigmoid(jnp.einsum(
        "bsh,he->bse", t.astype(jnp.float32), wr.astype(jnp.float32),
        precision=_HIGHEST))


def _experts(t, lp, v, math):
    first, held, _ = _share(v)
    w = route(router_scores(t, lp["router.weight"]),
              v["num_experts_per_tok"])[..., first:first + held]

    def one(acc, xs):
        we, gate, up, down = xs     # [B,S], [H,f], [H,f], [f,H]
        return acc + we[..., None] * _swiglu(t, gate, up, down, math), None

    routed, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(t),
        (jnp.moveaxis(w, -1, 0), lp["experts.gate"], lp["experts.up"],
         lp["experts.down"]))
    shared = _swiglu(t, lp["shared.gate.weight"], lp["shared.up.weight"],
                     lp["shared.down.weight"], math)
    return shared + v["moe_routed_scaling_factor"] * routed


def _layer(x, lp, spec, v, math):
    kind, hq, sparse = spec
    eps = v["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, lp["input_norm.weight"], eps), lp, kind,
                       hq, v, math)
    t = _rms_norm(x, lp["post_norm.weight"], eps)
    if sparse:
        return x + _experts(t, lp, v, math)
    return x + _swiglu(t, lp["gate.weight"], lp["up.weight"],
                       lp["down.weight"], math)


def _layer_params(p, i):
    pre = f"layers.{i}."
    return {k[len(pre):]: a for k, a in p.items() if k.startswith(pre)}


def _embed(params, ids, v):
    if ids.shape[1] != v["sequence_length"]:
        raise ValueError(f"sequence length {ids.shape[1]}: the configuration "
                         f"states {v['sequence_length']}")
    p = {k: a.astype(jnp.float32) for k, a in params.items()}
    return p["embed"][ids], p


def token_losses(params, ids, labels, v: dict, math):
    """Cross entropy of every token, [B, S] float32."""
    x, p = _embed(params, ids, v)
    for i, spec in enumerate(_layers(v)):
        x = jax.checkpoint(
            lambda xx, ll, spec=spec: _layer(xx, ll, spec, v, math))(
                x, _layer_params(p, i))
    x = _rms_norm(x, p["norm.weight"], v["rms_norm_eps"])
    logits = math.einsum("bsh,hv->bsv", x, p["head.weight"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - picked


def chosen_experts(params, ids, v: dict, math) -> dict:
    """layer index -> the experts each token chose there, [B, S, k] int32,
    sorted: what the program's own choice is counted against."""
    x, p = _embed(params, ids, v)
    eps, out = v["rms_norm_eps"], {}
    for i, spec in enumerate(_layers(v)):
        lp = _layer_params(p, i)
        if spec[2]:
            h = x + _attention(_rms_norm(x, lp["input_norm.weight"], eps),
                               lp, spec[0], spec[1], v, math)
            s = router_scores(_rms_norm(h, lp["post_norm.weight"], eps),
                              lp["router.weight"])
            out[i] = jnp.sort(
                jax.lax.top_k(s, v["num_experts_per_tok"])[1], -1)
        x = _layer(x, lp, spec, v, math)
    return out
