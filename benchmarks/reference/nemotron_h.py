"""Plain reference of the Nemotron-H decoder (NVIDIA, ``model_type``
"nemotron_h"; ``huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``
``config.json``), written from the config's keys and Nemotron-H's public
modeling code; where the config is silent the configuration's file lists what
was set as ``assumed``. float32 ``jax.numpy`` under highest matmul precision,
no kernels, no chunked form of the scan, no sort, no grouped product.
Nothing here imports the program.

Each layer is ONE residual sublayer, ``h = h + Mixer(RMSNorm(h))``, the mixer
picked by the layer's character of ``hybrid_override_pattern``; a final
RMSNorm and an untied head; token-mean cross entropy, no balance term. Norm
gains multiply (``x w``, w initialised to 1).

**Mamba-2** (``M``; H heads of P = ``mamba_head_dim``, G = ``n_groups``
groups of N = ``ssm_state_size``, I = H P): ``in_proj`` gives [z I, xBC (I +
2 G N), dt H]; xBC through a causal depthwise convolution of width
``conv_kernel`` with a bias (``y_t = sum_j w[:, j] xBC_(t - K + 1 + j) +
b``) and SiLU, split into x [H, P], B [G, N], C [G, N]; head h reads group
``h // (H / G)``. ``dt = softplus(dt + dt_bias + softplus^-1(dt0))``, ``A =
-exp(A_log + log A0)`` with (A0, dt0) per head from ``ssm_decay_init``.
Then, TOKEN BY TOKEN, a float32 state S [P, N] a head::

    S_t = exp(dt_t A) S_(t-1) + (dt_t x_t) B_t^T
    y_t = S_t C_t + D x_t

(a ``lax.scan`` over tokens inside a scan over blocks of ``chunk_size``
tokens, each block under ``jax.checkpoint``). Then the gate BEFORE a
grouped RMSNorm, ``w RMSNorm_groups(y silu(z))`` over ``n_groups`` groups of
I / G channels, and ``out_proj``.

**Experts** (``E``): ``s = sigmoid(t Wr)`` over ALL ``of`` experts in
float32 (it decides a top-k: highest precision whatever ``math`` is), the
``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` chosen,
weighted by their ``s`` over their sum times ``routed_scaling_factor``; the
chip's share (``expert_share`` {first, held, of}) sums the chosen experts it
holds, one expert at a time under a plain mask, each ``down(relu(up t)^2)``;
the shared expert, the same form, whole.

**Attention** (``*``): causal softmax attention, ``num_attention_heads``
over ``num_key_value_heads`` of ``head_dim``, scale ``1 / sqrt(head_dim)``,
NO rotary embedding, no bias. One head and one block of queries at a time.

The functions the readings tool puts a fault or the control in place of are
``carry``, ``group_of``, ``head_decay``, ``skip``, ``gated_norm``,
``expert_act`` and ``state_precision``. Matrices are stored [in, out];
expert matrices [held, in, out].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import ssd_work

MAMBA, MOE, ATTENTION = "M", "E", "*"
_HIGHEST = jax.lax.Precision.HIGHEST
# queries of one attention block
_QUERY_BLOCK = 2048
# rows of the head's logits at a time
_HEAD_ROWS = 4096


def _share(v: dict):
    s = v.get("expert_share") or {"first": 0, "held": v["n_routed_experts"],
                                  "of": v["n_routed_experts"]}
    return s["first"], s["held"], s["of"]


def layer_kinds(v: dict) -> list:
    return list(v["hybrid_override_pattern"])


def _mamba_sizes(v: dict):
    h, p = v["mamba_num_heads"], v["mamba_head_dim"]
    g, n = v["n_groups"], v["ssm_state_size"]
    inner = h * p
    return h, p, g, n, inner, inner + 2 * g * n


def dims(v: dict) -> dict:
    """The sizes ``harness/flops.py::train_flops_per_token`` needs, such that
    it counts what one token's step requires here and no more. That function
    computes ``6 (layers x layer_matmul_params + vocab x hidden) + layers x
    6 S heads D``, so:

    - ``layer_matmul_params`` is the MEAN over the layers of the parameters
      a token multiplies: a Mamba-2 layer's ``in_proj`` and ``out_proj``
      plus a sixth of the scan's ``12 H P N`` FLOPs a token
      (``harness/ssd_work.py``: the state's update and its read by C,
      forward and backward); an expert layer's router ``H x of``, shared
      expert ``2 H fs`` and, in expectation, ``k x held / of`` routed
      experts of ``2 H f`` each; an attention layer's q, k, v and o;
    - ``heads`` is the attention layers' query heads over ALL the layers'
      count, so that ``layers x 6 S heads D`` is their causal attention
      alone.

    No intra-chunk product of the chunked form, convolution or norm is
    credited."""
    hid = v["hidden_size"]
    _, held, of = _share(v)
    hq, hkv, d = v["num_attention_heads"], v["num_key_value_heads"], \
        v["head_dim"]
    h, _, _, _, inner, conv = _mamba_sizes(v)
    f, fs = v["moe_intermediate_size"], \
        v["moe_shared_expert_intermediate_size"]
    mamba = hid * (inner + conv + h) + inner * hid \
        + ssd_work.scan_flops_per_token(v) / 6
    moe = hid * of + 2 * hid * fs \
        + (v["num_experts_per_tok"] * held / of) * 2 * hid * f
    attention = hid * hq * d + 2 * hid * hkv * d + hq * d * hid
    per = {MAMBA: mamba, MOE: moe, ATTENTION: attention}
    kinds = layer_kinds(v)
    total = sum(per[k] for k in kinds)
    return {"hidden": hid, "layers": len(kinds),
            "heads": hq * kinds.count(ATTENTION) / len(kinds),
            "kv_heads": hkv, "head_dim": d, "vocab": v["vocab_size"],
            "layer_matmul_params": total / len(kinds)}


def param_shapes(v: dict) -> dict:
    hid, vocab = v["hidden_size"], v["vocab_size"]
    _, held, of = _share(v)
    hq, hkv, d = v["num_attention_heads"], v["num_key_value_heads"], \
        v["head_dim"]
    h, _, _, _, inner, conv = _mamba_sizes(v)
    f, fs = v["moe_intermediate_size"], \
        v["moe_shared_expert_intermediate_size"]
    out = {"embed": ((vocab, hid), "normal")}
    for i, kind in enumerate(layer_kinds(v)):
        b = f"layers.{i}."
        out[b + "norm.weight"] = ((hid,), "ones")
        if kind == MAMBA:
            out[b + "in.weight"] = ((hid, inner + conv + h), "normal")
            out[b + "conv.weight"] = ((conv, v["conv_kernel"]), "ones")
            out[b + "conv.bias"] = ((conv,), "normal")
            out[b + "A_log"] = ((h,), "zeros")
            out[b + "dt_bias"] = ((h,), "zeros")
            out[b + "D"] = ((h,), "ones")
            out[b + "gnorm.weight"] = ((inner,), "ones")
            out[b + "out.weight"] = ((inner, hid), "normal")
        elif kind == MOE:
            out[b + "router.weight"] = ((hid, of), "normal")
            out[b + "experts.up"] = ((held, hid, f), "normal")
            out[b + "experts.down"] = ((held, f, hid), "normal")
            out[b + "shared.up.weight"] = ((hid, fs), "normal")
            out[b + "shared.down.weight"] = ((fs, hid), "normal")
        else:
            out[b + "q.weight"] = ((hid, hq * d), "normal")
            out[b + "k.weight"] = ((hid, hkv * d), "normal")
            out[b + "v.weight"] = ((hid, hkv * d), "normal")
            out[b + "o.weight"] = ((hq * d, hid), "normal")
    out["norm.weight"] = ((hid,), "ones")
    out["head.weight"] = ((hid, vocab), "normal")
    return out


def score_bias(v: dict, layer: int) -> np.ndarray:
    """The frozen correction bias of expert layer ``layer``, [of] float32
    holding values of the storage dtype: normal(0, ``score_bias.scale``)
    from numpy's ``default_rng([score_bias.seed, layer])``."""
    _, _, of = _share(v)
    sb = v["score_bias"]
    b = np.random.default_rng([int(sb["seed"]), int(layer)]).normal(
        0.0, float(sb["scale"]), of).astype(np.float32)
    return b.astype(jnp.dtype(v["dtype"])).astype(np.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


# -- the pieces a fault or the control is put in place of ---------------------

def carry(state):
    """The state handed from one block of tokens to the next."""
    return state


def group_of(heads: int, groups: int):
    """The group of B and C each head reads, [heads] int."""
    return np.arange(heads) // (heads // groups)


def head_decay(a_log, dt_bias, v: dict):
    """(rate A [H] > 0, shift [H]) with ``dt = softplus(dt_raw + shift)``
    and a token's decay ``exp(-A dt)``: the leaves plus the fixed offsets of
    ``ssm_decay_init``."""
    init = v["ssm_decay_init"]
    a0 = np.asarray(init["A"], np.float64)
    dt0 = np.asarray(init["dt"], np.float64)
    return (jnp.exp(a_log + jnp.asarray(np.log(a0), jnp.float32)),
            dt_bias + jnp.asarray(np.log(np.expm1(dt0)), jnp.float32))


def skip(x, d):
    """The skip term ``D x`` a head, x [B, S, H, P]."""
    return d[:, None] * x


def gated_norm(y, z, w, groups: int, eps: float):
    """``w RMSNorm(y silu(z))`` over ``groups`` groups of channels: the gate
    before the norm."""
    v = y * jax.nn.silu(z)
    shape = v.shape
    v = v.reshape(shape[:-1] + (groups, -1))
    v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True) + eps)
    return v.reshape(shape) * w


def expert_act(a):
    """The experts' (routed and shared) activation."""
    return jnp.square(jax.nn.relu(a))


def state_precision(st):
    """The scan's state after each token: float32 as it stands."""
    return st


# -- Mamba-2 ------------------------------------------------------------------

def ssd(x, dt, rate, b, c, block: int):
    """x [Bt, S, H, P], dt [Bt, S, H], rate [H] (> 0), b and c [Bt, S, H, N]
    (each head's group already taken) -> y [Bt, S, H, P]: the recurrence
    token by token, float32."""
    bt, s, h, p = x.shape
    n = b.shape[-1]
    pad = (-s) % block

    def blocks(z):
        z = jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))
        z = jnp.moveaxis(z, 1, 0)
        return z.reshape((-1, block) + z.shape[1:])

    def token(st, z):
        # products and sums of float32 elements: no matmul, so no
        # precision setting enters the recurrence
        xt, dtt, bt_, ct = z                 # [Bt, H, P] / [Bt, H] / [Bt, H, N]
        st = jnp.exp(-dtt * rate)[..., None, None] * st \
            + (dtt[..., None] * xt)[..., :, None] * bt_[..., None, :]
        st = state_precision(st)
        return st, jnp.sum(st * ct[..., None, :], axis=-1)

    def run(st, zs):
        return jax.lax.scan(token, carry(st), zs, unroll=8)

    _, out = jax.lax.scan(jax.checkpoint(run),
                          jnp.zeros((bt, h, p, n), jnp.float32),
                          tuple(blocks(z) for z in (x, dt, b, c)))
    out = out.reshape((-1,) + out.shape[2:])[:s]
    return jnp.moveaxis(out, 0, 1)


def causal_conv(x, w, bias):
    """x [B, S, C], w [C, K], bias [C]: ``y_t = sum_j w[:, j] x_(t - K + 1 +
    j) + bias``."""
    k, s = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + s] * w[:, j] for j in range(k)) + bias


def _mamba(u, lp, v, math):
    bt, s, _ = u.shape
    h, p, g, n, inner, conv = _mamba_sizes(v)
    proj = math.einsum("bsh,hn->bsn", u, lp["in.weight"])
    z = proj[..., :inner]
    xbc = jax.nn.silu(causal_conv(proj[..., inner:inner + conv],
                                  lp["conv.weight"], lp["conv.bias"]))
    x = xbc[..., :inner].reshape(bt, s, h, p)
    heads = group_of(h, g)
    b = xbc[..., inner:inner + g * n].reshape(bt, s, g, n)[:, :, heads]
    c = xbc[..., inner + g * n:].reshape(bt, s, g, n)[:, :, heads]
    rate, shift = head_decay(lp["A_log"], lp["dt_bias"], v)
    dt = jax.nn.softplus(proj[..., inner + conv:] + shift)
    y = ssd(x, dt, rate, b, c, v["chunk_size"]) + skip(x, lp["D"])
    y = gated_norm(y.reshape(bt, s, inner), z, lp["gnorm.weight"], g,
                   v["layer_norm_epsilon"])
    return math.einsum("bsk,kh->bsh", y, lp["out.weight"])


# -- attention ----------------------------------------------------------------

def _attention(u, lp, v, math):
    bt, s, _ = u.shape
    hq, hkv, d = v["num_attention_heads"], v["num_key_value_heads"], \
        v["head_dim"]
    q = math.einsum("bsh,hk->bsk", u, lp["q.weight"]).reshape(bt, s, hq, d)
    k = math.einsum("bsh,hk->bsk", u, lp["k.weight"]).reshape(bt, s, hkv, d)
    val = math.einsum("bsh,hk->bsk", u, lp["v.weight"]).reshape(bt, s, hkv,
                                                                 d)
    qb = min(_QUERY_BLOCK, s)
    n_blocks = -(-s // qb)
    keys = jnp.arange(s)

    def head(args):
        qh, kv = args                    # [B, S, D], its kv head
        kh, vh = (jax.lax.dynamic_index_in_dim(a, kv, 2, keepdims=False)
                  for a in (k, val))
        qpad = jnp.pad(qh, ((0, 0), (0, n_blocks * qb - s), (0, 0)))

        def rows(i):
            qi = jax.lax.dynamic_slice_in_dim(qpad, i * qb, qb, axis=1)
            pos = i * qb + jnp.arange(qb)
            sc = math.einsum("bqd,bkd->bqk", qi, kh) / np.float32(np.sqrt(d))
            sc = jnp.where((keys[None, :] <= pos[:, None])[None], sc,
                           -jnp.inf)
            return math.einsum("bqk,bkd->bqd", jax.nn.softmax(sc, -1), vh)
        out = jax.lax.map(jax.checkpoint(rows), jnp.arange(n_blocks))
        return jnp.moveaxis(out, 0, 1).reshape(bt, n_blocks * qb, d)[:, :s]

    att = jax.lax.map(jax.checkpoint(head),
                      (jnp.moveaxis(q, 2, 0), jnp.arange(hq) // (hq // hkv)))
    return math.einsum("bsk,kh->bsh", jnp.moveaxis(att, 0, 2).reshape(
        bt, s, hq * d), lp["o.weight"])


# -- experts ------------------------------------------------------------------

def _mlp(t, up, down, math):
    return math.einsum("bsi,ih->bsh",
                       expert_act(math.einsum("bsh,hi->bsi", t, up)), down)


def route(t, wr, bias, k: int, scale: float):
    """Each token's weight on every expert [B, S, of]: the k largest of
    ``sigmoid(t wr) + bias`` chosen, weighted by their sigmoid over its sum
    times ``scale``, zero elsewhere."""
    s = jax.nn.sigmoid(jnp.einsum("bsh,he->bse", t.astype(jnp.float32),
                                  wr.astype(jnp.float32), precision=_HIGHEST))
    _, idx = jax.lax.top_k(s + jnp.asarray(bias), k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    w = top / jnp.sum(top, axis=-1, keepdims=True) * np.float32(scale)
    hot = jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype)
    return jnp.einsum("...k,...ke->...e", w, hot, precision=_HIGHEST)


def _experts(t, lp, v, math, layer: int):
    first, held, _ = _share(v)
    w = route(t, lp["router.weight"], score_bias(v, layer),
              v["num_experts_per_tok"],
              v["routed_scaling_factor"])[..., first:first + held]

    def one(acc, xs):
        we, up, down = xs
        return acc + we[..., None] * _mlp(t, up, down, math), None

    routed, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(t),
        (jnp.moveaxis(w, -1, 0), lp["experts.up"], lp["experts.down"]))
    return _mlp(t, lp["shared.up.weight"], lp["shared.down.weight"], math) \
        + routed


# -- the model ----------------------------------------------------------------

def _layer(x, lp, kind, v, math, index):
    u = _rms_norm(x, lp["norm.weight"], v["layer_norm_epsilon"])
    if kind == MAMBA:
        return x + _mamba(u, lp, v, math)
    if kind == MOE:
        return x + _experts(u, lp, v, math, index)
    return x + _attention(u, lp, v, math)


def _layer_params(p, i):
    pre = f"layers.{i}."
    return {k[len(pre):]: a for k, a in p.items() if k.startswith(pre)}


def chosen_experts(params, ids, v: dict, math) -> dict:
    """layer index -> the experts each token chose there, [B, S, k] int32,
    sorted: what the program's own choice is counted against."""
    p = {k: a.astype(jnp.float32) for k, a in params.items()}
    x, out = p["embed"][ids], {}
    for i, kind in enumerate(layer_kinds(v)):
        lp = _layer_params(p, i)
        if kind == MOE:
            t = _rms_norm(x, lp["norm.weight"], v["layer_norm_epsilon"])
            s = jax.nn.sigmoid(jnp.einsum(
                "bsh,he->bse", t, lp["router.weight"], precision=_HIGHEST))
            out[i] = jnp.sort(jax.lax.top_k(
                s + jnp.asarray(score_bias(v, i)),
                v["num_experts_per_tok"])[1], -1)
        x = jax.checkpoint(
            lambda xx, ll, kind=kind, i=i: _layer(xx, ll, kind, v, math, i))(
                x, lp)
    return out


def token_losses(params, ids, labels, v: dict, math):
    """Cross entropy of every token, [B, S] float32."""
    if ids.shape[1] != v["sequence_length"]:
        raise ValueError(f"sequence length {ids.shape[1]}: the configuration "
                         f"states {v['sequence_length']}")
    p = {k: a.astype(jnp.float32) for k, a in params.items()}
    x = p["embed"][ids]
    for i, kind in enumerate(layer_kinds(v)):
        x = jax.checkpoint(
            lambda xx, ll, kind=kind, i=i: _layer(xx, ll, kind, v, math, i))(
                x, _layer_params(p, i))
    x = _rms_norm(x, p["norm.weight"], v["layer_norm_epsilon"])
    b, s, h = x.shape
    rows = min(_HEAD_ROWS, s)
    pad = (-s) % rows
    xr = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(b, -1, rows, h)
    lr = jnp.pad(labels, ((0, 0), (0, pad))).reshape(b, -1, rows)

    def block(args):
        xb, lb = args                   # [B, rows, H], [B, rows]
        logits = math.einsum("bsh,hv->bsv", xb, p["head.weight"])
        picked = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked
    out = jax.lax.map(jax.checkpoint(block),
                      (jnp.moveaxis(xr, 1, 0), jnp.moveaxis(lr, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, -1)[:, :s]
