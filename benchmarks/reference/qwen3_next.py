"""Plain reference of the Qwen3-Next decoder (Qwen, ``model_type``
"qwen3_next"; ``huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct``
``config.json``), written from the config's keys and Qwen3-Next's public
modeling code; where the config is silent the configuration's file lists what
was set as ``assumed``. float32 ``jax.numpy`` under highest matmul precision,
no kernels, no chunked form of the recurrence, no sort, no grouped product.
Nothing here imports the program.

Each layer: ``h = h + Mixer(RMSNorm(h))``, ``h = h + MoE(RMSNorm(h))``; a
final RMSNorm and an untied head; token-mean cross entropy, no balance term.
Norm gains multiply (``x w``, w initialised to 1). Layer i's mixer is full
attention when ``(i + 1) % full_attention_interval == 0``, else Gated
DeltaNet.

**Gated DeltaNet** (``linear_*`` keys; Hk key heads of dk, Hv value heads of
dv, ``r = Hv / Hk``): ``in_proj_qkvz`` laid out per key head as [q dk, k dk,
v r dv, z r dv], ``in_proj_ba`` per key head as [b r, a r]; (q, k, v) side by
side through a causal depthwise convolution of width
``linear_conv_kernel_dim`` (``y_t = sum_j w[:, j] x_(t - K + 1 + j)``, no
bias) and SiLU; q and k L2-normalised (eps 1e-6), q times ``1 / sqrt(dk)``;
value head h reads key head ``h // r``. ``beta = sigmoid(b)``, ``g =
-exp(A_log + log A0) softplus(a + dt_bias + softplus^-1(dt0))`` with (A0,
dt0) per head from ``linear_decay_init``. Then, TOKEN BY TOKEN, a float32
state S [dk, dv] a value head::

    S_t = exp(g_t) S_(t-1)
    S_t = S_t + k_t (beta_t (v_t - S_t^T k_t))^T
    o_t = S_t^T q_t

(a ``lax.scan`` over tokens inside a scan over blocks of ``BLOCK`` tokens,
each block under ``jax.checkpoint``). Then ``RMSNorm_dv(o) w silu(z)`` a
value head and ``out_proj``.

**Gated attention**: ``q_proj`` gives [query D, gate D] per head; q and k
pass an RMSNorm over D; rotary embedding on the first
``partial_rotary_factor`` of each head, ``theta ** (-2i / rot)``, pairs
(x[2i], x[2i+1]); causal softmax attention, scale ``1 / sqrt(D)``, query
head h over kv head ``h // (Hq / Hkv)``; the output times ``sigmoid(gate)``
before ``o_proj``. One head and one block of queries at a time.

**Experts**: ``p = softmax(t Wr)`` over ALL ``num_experts`` in float32 (it
decides a top-k: highest precision whatever ``math`` is), the
``num_experts_per_tok`` largest chosen and renormalised; the chip's share
(``expert_share`` {first, held, of}) sums the chosen experts it holds, one
expert at a time under a plain mask; the shared SwiGLU expert, whole, times
``sigmoid(t W_sg)``.

The functions the readings tool puts a fault in place of are
``carry``, ``write_strength``, ``head_decay``, ``output_gate``,
``shared_gate`` and ``router_probs``. Matrices are stored [in, out]; expert
matrices [held, in, out].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

FULL, LINEAR = "full_attention", "linear_attention"
_HIGHEST = jax.lax.Precision.HIGHEST
# tokens of the recurrence between the checkpoints of its state (the
# kernels' chunk, so that a state dropped at each block's start is the fault
# of a chunked scan that carries nothing across its chunks)
BLOCK = 128
# queries of one attention block
_QUERY_BLOCK = 2048
# rows of the head's logits at a time
_HEAD_ROWS = 4096


def _share(v: dict):
    s = v.get("expert_share") or {"first": 0, "held": v["num_experts"],
                                  "of": v["num_experts"]}
    return s["first"], s["held"], s["of"]


def layer_kinds(v: dict) -> list:
    n = v["full_attention_interval"]
    return [FULL if (i + 1) % n == 0 else LINEAR
            for i in range(v["num_hidden_layers"])]


def dims(v: dict) -> dict:
    """The sizes ``harness/flops.py::train_flops_per_token`` needs, such that
    it counts what one token's step requires here and no more. That function
    computes ``6 (layers x layer_matmul_params + vocab x hidden) + layers x
    6 S heads D``, so:

    - ``layer_matmul_params`` is the MEAN over the layers of the parameters
      a token multiplies: a DeltaNet layer's ``in_proj_qkvz``,
      ``in_proj_ba`` and ``out_proj`` plus ``3 Hv dk dv``, which the 6 turns
      into the recurrence's ``18 Hv dk dv`` FLOPs a token (the state update
      and the two reads of the state, forward and backward); a full layer's
      q (with its gate), k, v and o; every layer's router ``H x of``, shared
      expert ``3 H fs`` and gate ``H``, and in expectation ``k x held / of``
      routed experts of ``3 H f`` each;
    - ``heads`` is the full layers' query heads over ALL the layers' count,
      so that ``layers x 6 S heads D`` is the full layers' causal attention
      alone.

    No intra-chunk product of the chunked form is credited."""
    h = v["hidden_size"]
    first, held, of = _share(v)
    hq, hkv, d = v["num_attention_heads"], v["num_key_value_heads"], \
        v["head_dim"]
    hk, hv = v["linear_num_key_heads"], v["linear_num_value_heads"]
    dk, dv = v["linear_key_head_dim"], v["linear_value_head_dim"]
    f, fs = v["moe_intermediate_size"], v["shared_expert_intermediate_size"]
    moe = h * of + 3 * h * fs + h \
        + (v["num_experts_per_tok"] * held / of) * 3 * h * f
    linear = h * (2 * hk * dk + 2 * hv * dv) + h * 2 * hv + hv * dv * h \
        + 3 * hv * dk * dv
    full = h * 2 * hq * d + 2 * h * hkv * d + hq * d * h
    kinds = layer_kinds(v)
    total = sum(full if k == FULL else linear for k in kinds) \
        + len(kinds) * moe
    full_heads = hq * sum(1 for k in kinds if k == FULL)
    return {"hidden": h, "layers": len(kinds), "heads": full_heads
            / len(kinds), "kv_heads": hkv, "head_dim": d,
            "vocab": v["vocab_size"],
            "layer_matmul_params": total / len(kinds)}


def param_shapes(v: dict) -> dict:
    h, vocab = v["hidden_size"], v["vocab_size"]
    _, held, of = _share(v)
    hq, hkv, d = v["num_attention_heads"], v["num_key_value_heads"], \
        v["head_dim"]
    hk, hv = v["linear_num_key_heads"], v["linear_num_value_heads"]
    dk, dv = v["linear_key_head_dim"], v["linear_value_head_dim"]
    f, fs = v["moe_intermediate_size"], v["shared_expert_intermediate_size"]
    out = {"embed": ((vocab, h), "normal")}
    for i, kind in enumerate(layer_kinds(v)):
        b = f"layers.{i}."
        out[b + "input_norm.weight"] = ((h,), "ones")
        if kind == FULL:
            out[b + "q.weight"] = ((h, hq * 2 * d), "normal")
            out[b + "k.weight"] = ((h, hkv * d), "normal")
            out[b + "v.weight"] = ((h, hkv * d), "normal")
            out[b + "q_norm.weight"] = ((d,), "ones")
            out[b + "k_norm.weight"] = ((d,), "ones")
            out[b + "o.weight"] = ((hq * d, h), "normal")
        else:
            out[b + "qkvz.weight"] = ((h, 2 * hk * dk + 2 * hv * dv),
                                      "normal")
            out[b + "ba.weight"] = ((h, 2 * hv), "normal")
            out[b + "conv.weight"] = ((2 * hk * dk + hv * dv,
                                       v["linear_conv_kernel_dim"]), "normal")
            out[b + "A_log"] = ((hv,), "zeros")
            out[b + "dt_bias"] = ((hv,), "zeros")
            out[b + "gnorm.weight"] = ((dv,), "ones")
            out[b + "out.weight"] = ((hv * dv, h), "normal")
        out[b + "post_norm.weight"] = ((h,), "ones")
        out[b + "router.weight"] = ((h, of), "normal")
        out[b + "experts.gate"] = ((held, h, f), "normal")
        out[b + "experts.up"] = ((held, h, f), "normal")
        out[b + "experts.down"] = ((held, f, h), "normal")
        out[b + "shared.gate.weight"] = ((h, fs), "normal")
        out[b + "shared.up.weight"] = ((h, fs), "normal")
        out[b + "shared.down.weight"] = ((fs, h), "normal")
        out[b + "shared_gate.weight"] = ((h, 1), "normal")
    out["norm.weight"] = ((h,), "ones")
    out["head.weight"] = ((h, vocab), "normal")
    return out


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


# -- the pieces a fault is put in place of ------------------------------------

def carry(state):
    """The state handed from one block of tokens to the next."""
    return state


def write_strength(b):
    """beta from the projection's b."""
    return jax.nn.sigmoid(b)


def head_decay(a_log, dt_bias, v: dict):
    """(rate factor A [Hv], shift [Hv]) with ``g = -A softplus(a + shift)``:
    the leaves plus the fixed offsets of ``linear_decay_init``."""
    init = v["linear_decay_init"]
    a0 = np.asarray(init["A"], np.float64)
    dt0 = np.asarray(init["dt"], np.float64)
    return (jnp.exp(a_log + jnp.asarray(np.log(a0), jnp.float32)),
            dt_bias + jnp.asarray(np.log(np.expm1(dt0)), jnp.float32))


def output_gate(z):
    """The DeltaNet output's gate, from z."""
    return jax.nn.silu(z)


def shared_gate(t, w, math):
    """The shared expert's gate a token, [B, S, 1]."""
    return jax.nn.sigmoid(math.einsum("bsh,ho->bso", t, w))


def router_probs(logits):
    """Scores over all the experts, float32."""
    return jax.nn.softmax(logits, axis=-1)


# -- Gated DeltaNet -----------------------------------------------------------

def delta_rule(q, k, v, g, beta):
    """q, k, v [B, S, H, d] (q and k per value head), g and beta [B, S, H]
    -> o [B, S, H, dv]: the recurrence token by token, float32."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-s) % BLOCK

    def blocks(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((-1, BLOCK) + x.shape[1:])

    def token(st, x):
        # products and sums of float32 elements: no matmul, so no
        # precision setting enters the recurrence
        qt, kt, vt, gt, bt = x                 # [B, H, d] / [B, H]
        st = jnp.exp(gt)[..., None, None] * st
        mem = jnp.sum(st * kt[..., :, None], axis=-2)
        st = st + kt[..., :, None] * (bt[..., None] * (vt - mem))[..., None, :]
        return st, jnp.sum(st * qt[..., :, None], axis=-2)

    def block(st, xs):
        return jax.lax.scan(token, carry(st), xs, unroll=8)

    _, out = jax.lax.scan(jax.checkpoint(block),
                          jnp.zeros((b, h, dk, dv), jnp.float32),
                          tuple(blocks(x) for x in (q, k, v, g, beta)))
    out = out.reshape((-1,) + out.shape[2:])[:s]
    return jnp.moveaxis(out, 0, 1)


def causal_conv(x, w):
    """x [B, S, C], w [C, K]: ``y_t = sum_j w[:, j] x_(t - K + 1 + j)``."""
    k, s = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + s] * w[:, j] for j in range(k))


def _deltanet(u, lp, v, math):
    b, s, _ = u.shape
    hk, hv = v["linear_num_key_heads"], v["linear_num_value_heads"]
    dk, dv = v["linear_key_head_dim"], v["linear_value_head_dim"]
    r = hv // hk
    x = math.einsum("bsh,hn->bsn", u, lp["qkvz.weight"]).reshape(
        b, s, hk, 2 * dk + 2 * r * dv)
    z = x[..., 2 * dk + r * dv:].reshape(b, s, hv, dv)
    mixed = jnp.concatenate([x[..., :dk].reshape(b, s, hk * dk),
                             x[..., dk:2 * dk].reshape(b, s, hk * dk),
                             x[..., 2 * dk:2 * dk + r * dv].reshape(
                                 b, s, hv * dv)], axis=-1)
    mixed = jax.nn.silu(causal_conv(mixed, lp["conv.weight"]))
    q = mixed[..., :hk * dk].reshape(b, s, hk, dk)
    k = mixed[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk)
    val = mixed[..., 2 * hk * dk:].reshape(b, s, hv, dv)
    q = jnp.repeat(_l2norm(q) / np.float32(np.sqrt(dk)), r, axis=2)
    k = jnp.repeat(_l2norm(k), r, axis=2)
    ba = math.einsum("bsh,hn->bsn", u, lp["ba.weight"]).reshape(b, s, hk,
                                                                 2 * r)
    beta = write_strength(ba[..., :r].reshape(b, s, hv))
    rate, shift = head_decay(lp["A_log"], lp["dt_bias"], v)
    g = -rate * jax.nn.softplus(ba[..., r:].reshape(b, s, hv) + shift)
    o = delta_rule(q, k, val, g, beta)
    o = _rms_norm(o, lp["gnorm.weight"], v["rms_norm_eps"]) * output_gate(z)
    return math.einsum("bsk,kh->bsh", o.reshape(b, s, hv * dv),
                       lp["out.weight"])


# -- gated attention ----------------------------------------------------------

def _rope(x, rot, theta):
    """x [B, S, H, D]: the pairs (2i, 2i+1) of the first ``rot`` dims turned
    by ``pos * theta ** (-2i / rot)``; the rest pass."""
    s = x.shape[1]
    inv = float(theta) ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                       axis=-1).reshape(x.shape[:-1] + (rot,))
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def _attention(u, lp, v, math):
    b, s, _ = u.shape
    hq, hkv, d = v["num_attention_heads"], v["num_key_value_heads"], \
        v["head_dim"]
    eps, rot = v["rms_norm_eps"], int(d * v["partial_rotary_factor"])
    qg = math.einsum("bsh,hk->bsk", u, lp["q.weight"]).reshape(b, s, hq,
                                                                2 * d)
    q = _rope(_rms_norm(qg[..., :d], lp["q_norm.weight"], eps), rot,
              v["rope_theta"])
    k = _rope(_rms_norm(math.einsum("bsh,hk->bsk", u, lp["k.weight"])
                        .reshape(b, s, hkv, d), lp["k_norm.weight"], eps),
              rot, v["rope_theta"])
    val = math.einsum("bsh,hk->bsk", u, lp["v.weight"]).reshape(b, s, hkv, d)
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
        return jnp.moveaxis(out, 0, 1).reshape(b, n_blocks * qb, d)[:, :s]

    att = jax.lax.map(jax.checkpoint(head),
                      (jnp.moveaxis(q, 2, 0), jnp.arange(hq) // (hq // hkv)))
    att = jnp.moveaxis(att, 0, 2) * jax.nn.sigmoid(qg[..., d:])
    return math.einsum("bsk,kh->bsh", att.reshape(b, s, hq * d),
                       lp["o.weight"])


# -- experts ------------------------------------------------------------------

def _swiglu(t, gate, up, down, math):
    a = jax.nn.silu(math.einsum("bsh,hi->bsi", t, gate)) \
        * math.einsum("bsh,hi->bsi", t, up)
    return math.einsum("bsi,ih->bsh", a, down)


def route(t, wr, k: int):
    """Each token's weight on every expert [B, S, of]: the k largest of
    ``router_probs`` over their sum, zero elsewhere."""
    p = router_probs(jnp.einsum("bsh,he->bse", t.astype(jnp.float32),
                                wr.astype(jnp.float32), precision=_HIGHEST))
    top, idx = jax.lax.top_k(p, k)
    w = top / jnp.sum(top, axis=-1, keepdims=True)
    hot = jax.nn.one_hot(idx, p.shape[-1], dtype=p.dtype)
    return jnp.einsum("...k,...ke->...e", w, hot, precision=_HIGHEST)


def _experts(t, lp, v, math):
    first, held, _ = _share(v)
    w = route(t, lp["router.weight"],
              v["num_experts_per_tok"])[..., first:first + held]

    def one(acc, xs):
        we, gate, up, down = xs
        return acc + we[..., None] * _swiglu(t, gate, up, down, math), None

    routed, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(t),
        (jnp.moveaxis(w, -1, 0), lp["experts.gate"], lp["experts.up"],
         lp["experts.down"]))
    shared = _swiglu(t, lp["shared.gate.weight"], lp["shared.up.weight"],
                     lp["shared.down.weight"], math) \
        * shared_gate(t, lp["shared_gate.weight"], math)
    return shared + routed


# -- the model ----------------------------------------------------------------

def _layer(x, lp, kind, v, math):
    eps = v["rms_norm_eps"]
    u = _rms_norm(x, lp["input_norm.weight"], eps)
    x = x + (_attention(u, lp, v, math) if kind == FULL
             else _deltanet(u, lp, v, math))
    return x + _experts(_rms_norm(x, lp["post_norm.weight"], eps), lp, v,
                        math)


def _layer_params(p, i):
    pre = f"layers.{i}."
    return {k[len(pre):]: a for k, a in p.items() if k.startswith(pre)}


def token_losses(params, ids, labels, v: dict, math):
    """Cross entropy of every token, [B, S] float32."""
    if ids.shape[1] != v["sequence_length"]:
        raise ValueError(f"sequence length {ids.shape[1]}: the configuration "
                         f"states {v['sequence_length']}")
    p = {k: a.astype(jnp.float32) for k, a in params.items()}
    x = p["embed"][ids]
    for i, kind in enumerate(layer_kinds(v)):
        x = jax.checkpoint(
            lambda xx, ll, kind=kind: _layer(xx, ll, kind, v, math))(
                x, _layer_params(p, i))
    x = _rms_norm(x, p["norm.weight"], v["rms_norm_eps"])
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
