"""Plain reference of the dense decoder block that Llama and Mistral share
(Touvron et al. 2023; Jiang et al. 2023, "Mistral 7B", and the layout of
``huggingface.co/mistralai/Mistral-7B-v0.1``): token embedding, pre-RMSNorm
blocks of causal grouped-query attention with rotary position embedding and a
SwiGLU MLP, no biases, a final RMSNorm, an untied head, token-mean cross
entropy. float32 ``jax.numpy``, no kernels.

Rotary embedding rotates the pairs (x[2i], x[2i+1]) by the angle
``pos * theta**(-2i/D)``, as the RoFormer paper and Mistral's own reference
code do (the Hugging Face port permutes the head dimension to rotate halves:
the same model under a fixed permutation of q's and k's columns). Attention is
full causal: ``sliding_window`` is honoured only as "not shorter than the
sequence", and a sequence longer than it is an error. Matrices are stored
[in, out]. The kv heads are walked one group at a time so that nothing larger
than (Hq/Hkv) x S x S is alive at once. Nothing here imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dims(v: dict) -> dict:
    """The sizes ``harness/flops.py`` needs. A layer's matmul parameters:
    q and out 2 H (Hq D), k and v 2 H (Hkv D), gate, up and down 3 H I."""
    h, inner = v["hidden_size"], v["intermediate_size"]
    hq, hkv = v["num_attention_heads"], v["num_key_value_heads"]
    d = v.get("head_dim") or h // hq
    return {"hidden": h, "layers": v["num_hidden_layers"], "heads": hq,
            "kv_heads": hkv, "head_dim": d, "vocab": v["vocab_size"],
            "layer_matmul_params": (2 * h * hq * d + 2 * h * hkv * d
                                    + 3 * h * inner)}


def param_shapes(v: dict) -> dict:
    h, inner, vocab = v["hidden_size"], v["intermediate_size"], v["vocab_size"]
    hq, hkv = v["num_attention_heads"], v["num_key_value_heads"]
    d = v.get("head_dim") or h // hq
    out = {"embed": ((vocab, h), "normal")}
    for i in range(v["num_hidden_layers"]):
        b = f"layers.{i}."
        out[b + "input_norm.weight"] = ((h,), "ones")
        out[b + "q.weight"] = ((h, hq * d), "normal")
        out[b + "k.weight"] = ((h, hkv * d), "normal")
        out[b + "v.weight"] = ((h, hkv * d), "normal")
        out[b + "o.weight"] = ((hq * d, h), "normal")
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


def _layer(x, lp, v, math):
    b, s, h = x.shape
    hq, hkv = v["num_attention_heads"], v["num_key_value_heads"]
    d = v.get("head_dim") or h // hq
    g = hq // hkv
    eps, theta = v["rms_norm_eps"], float(v["rope_theta"])
    y = _rms_norm(x, lp["input_norm.weight"], eps)
    q = _rope(math.einsum("bsh,hk->bsk", y, lp["q.weight"])
              .reshape(b, s, hq, d), theta)
    k = _rope(math.einsum("bsh,hk->bsk", y, lp["k.weight"])
              .reshape(b, s, hkv, d), theta)
    val = math.einsum("bsh,hk->bsk", y, lp["v.weight"]).reshape(b, s, hkv, d)
    mask = jnp.tril(jnp.ones((s, s), bool))

    def group(args):
        qg, kg, vg = args           # [B,S,g,D], [B,S,D], [B,S,D]
        sc = math.einsum("bqgd,bkd->bgqk", qg, kg) / jnp.sqrt(float(d))
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        return math.einsum("bgqk,bkd->bqgd", jax.nn.softmax(sc, -1), vg)

    qg = jnp.moveaxis(q.reshape(b, s, hkv, g, d), 2, 0)
    att = jax.lax.map(jax.checkpoint(group),
                      (qg, jnp.moveaxis(k, 2, 0), jnp.moveaxis(val, 2, 0)))
    att = jnp.moveaxis(att, 0, 2).reshape(b, s, hq * d)
    x = x + math.einsum("bsk,kh->bsh", att, lp["o.weight"])
    y = _rms_norm(x, lp["post_norm.weight"], eps)
    gate = math.einsum("bsh,hi->bsi", y, lp["gate.weight"])
    up = math.einsum("bsh,hi->bsi", y, lp["up.weight"])
    return x + math.einsum("bsi,ih->bsh", jax.nn.silu(gate) * up,
                           lp["down.weight"])


def token_losses(params, ids, labels, v: dict, math):
    """Cross entropy of every token, [B, S] float32."""
    if ids.shape[1] > (v.get("sliding_window") or ids.shape[1]):
        raise ValueError("sequence longer than sliding_window: this "
                         "reference computes full causal attention")
    p = {k: a.astype(jnp.float32) for k, a in params.items()}
    x = p["embed"][ids]
    for i in range(v["num_hidden_layers"]):
        pre = f"layers.{i}."
        lp = {k[len(pre):]: a for k, a in p.items() if k.startswith(pre)}
        x = jax.checkpoint(lambda xx, ll: _layer(xx, ll, v, math))(x, lp)
    x = _rms_norm(x, p["norm.weight"], v["rms_norm_eps"])
    logits = math.einsum("bsh,hv->bsv", x, p["head.weight"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - picked
