"""Plain reference of the GPT-2 block (Radford et al. 2019; the layout of
``huggingface.co/openai-community/gpt2``): learned token and position
embeddings, pre-LayerNorm blocks of causal multi-head attention and a GELU
MLP with biases everywhere, a final LayerNorm, the head tied to the token
embedding, token-mean cross entropy. float32 ``jax.numpy``, no kernels.

Departures from the published model, each stated by the configuration's file:
q, k and v are three matrices where the published ``c_attn`` is one fused
(the same mathematics); ``activation_function`` is read from the file
(``gelu`` is the exact erf form, ``gelu_new`` the published tanh form); the
embedding has ``padded_vocab_size`` rows, and the softmax runs over all of
them. Nothing here imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dims(v: dict) -> dict:
    """The sizes ``harness/flops.py`` needs. A layer's matmul parameters:
    q, k, v, out 4 H^2 and the two MLP matrices 2 H I."""
    h = v["n_embd"]
    inner = v.get("n_inner") or 4 * h
    return {"hidden": h, "layers": v["n_layer"], "heads": v["n_head"],
            "kv_heads": v["n_head"], "head_dim": h // v["n_head"],
            "vocab": v["padded_vocab_size"],
            "layer_matmul_params": 4 * h * h + 2 * h * inner}


def param_shapes(v: dict) -> dict:
    """name -> (shape, kind); kind is normal | ones | zeros."""
    h, p, vocab = v["n_embd"], v["n_positions"], v["padded_vocab_size"]
    inner = v.get("n_inner") or 4 * h
    out = {"wte": ((vocab, h), "normal"), "wpe": ((p, h), "normal")}
    for i in range(v["n_layer"]):
        b = f"h.{i}."
        out[b + "ln_1.weight"] = ((h,), "ones")
        out[b + "ln_1.bias"] = ((h,), "zeros")
        for n in ("q", "k", "v", "o"):
            out[b + f"attn.{n}.weight"] = ((h, h), "normal")
            out[b + f"attn.{n}.bias"] = ((h,), "zeros")
        out[b + "ln_2.weight"] = ((h,), "ones")
        out[b + "ln_2.bias"] = ((h,), "zeros")
        out[b + "mlp.fc.weight"] = ((h, inner), "normal")
        out[b + "mlp.fc.bias"] = ((inner,), "zeros")
        out[b + "mlp.proj.weight"] = ((inner, h), "normal")
        out[b + "mlp.proj.bias"] = ((h,), "zeros")
    out["ln_f.weight"] = ((h,), "ones")
    out["ln_f.bias"] = ((h,), "zeros")
    return out


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu(x, kind):
    if kind == "gelu":
        return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0)))
    if kind == "gelu_new":
        return 0.5 * x * (1.0 + jnp.tanh(
            jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))
    raise ValueError(f"activation_function {kind!r}")


def _block(x, lp, v, math):
    b, s, h = x.shape
    nh = v["n_head"]
    d = h // nh
    eps = v["layer_norm_epsilon"]

    def lin(t, name):
        return math.einsum("bsh,hk->bsk", t, lp[name + ".weight"]) \
            + lp[name + ".bias"]
    y = _layer_norm(x, lp["ln_1.weight"], lp["ln_1.bias"], eps)
    q = lin(y, "attn.q").reshape(b, s, nh, d)
    k = lin(y, "attn.k").reshape(b, s, nh, d)
    val = lin(y, "attn.v").reshape(b, s, nh, d)
    scores = math.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = math.einsum("bhqk,bkhd->bqhd", probs, val).reshape(b, s, h)
    x = x + lin(att, "attn.o")
    y = _layer_norm(x, lp["ln_2.weight"], lp["ln_2.bias"], eps)
    y = _gelu(lin(y, "mlp.fc"), v["activation_function"])
    return x + lin(y, "mlp.proj")


def token_losses(params, ids, labels, v: dict, math):
    """Cross entropy of every token, [B, S] float32. Each block is
    rematerialised in the backward pass, so that a block of rows fits."""
    p = {k: a.astype(jnp.float32) for k, a in params.items()}
    s = ids.shape[1]
    x = p["wte"][ids] + p["wpe"][jnp.arange(s)][None]
    for i in range(v["n_layer"]):
        pre = f"h.{i}."
        lp = {k[len(pre):]: a for k, a in p.items() if k.startswith(pre)}
        x = jax.checkpoint(lambda xx, ll: _block(xx, ll, v, math))(x, lp)
    x = _layer_norm(x, p["ln_f.weight"], p["ln_f.bias"],
                    v["layer_norm_epsilon"])
    logits = math.einsum("bsh,vh->bsv", x, p["wte"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - picked
