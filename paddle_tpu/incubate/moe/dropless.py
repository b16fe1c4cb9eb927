"""DroplessMoE: top-k SwiGLU experts with no capacity, over a grouped matmul.

The expert layer of today's fine-grained mixtures (hundreds of narrow SwiGLU
experts, sigmoid scores, the chosen k renormalised and scaled, a shared
expert beside them), as one chip of an expert-parallel deployment runs it:
the layer is told which experts it holds, ``(first, held)`` of
``num_experts``. It routes every token over ALL the experts, keeps each
chosen expert's weight as normalised over all k chosen, and computes the
part of the result that its own experts give: the assignments that land on
a held expert are sorted by expert into one buffer, two grouped matrix
products (gate and up in one, then down; ``ops/pallas/grouped_matmul.py``)
run over the held experts, and the rows are combined back by weight. What
the absent experts would add is left out, and nothing stands in for the
other chips or their exchange. No token is dropped and there is no
capacity: the buffers take any routing, and the grouped products skip the
tiles that hold no rows. With ``held == num_experts`` it is the whole layer.

``MoELayer`` (capacity gates over dense one-hot dispatch) keeps its place
beside it.
"""
from __future__ import annotations

import collections
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...core.dispatch import run_op
from ...nn.initializer import Normal
from ...nn.layer.layers import Layer
from ...ops.pallas.grouped_matmul import (ROW_TILE, gmm_tiles, group_layout,
                                          grouped_matmul, padded_rows)

__all__ = ["DroplessMoE", "MOE_PLAN_TALLY"]

# one count per lowered expert layer, by (held, of, top_k, tokens, row
# tile): trace time only, nothing a step (as flash's TILE_PLAN_TALLY)
MOE_PLAN_TALLY: collections.Counter = collections.Counter()
_HIGHEST = jax.lax.Precision.HIGHEST


def _float0(a):
    return np.zeros(a.shape, jax.dtypes.float0)


@jax.custom_vjp
def _dispatch(x, row_tok, dest, here):
    """x [T, d] -> [rows, d]: row r holds token ``row_tok[r]`` (a padding
    row holds token 0: finite, and never combined). The backward is a gather
    too, since ``dest`` [T, k] is the rows' inverse: a token's gradient is
    the sum over its assignments ``here`` of their rows'."""
    return x[row_tok]


def _dispatch_fwd(x, row_tok, dest, here):
    return x[row_tok], (row_tok, dest, here)


def _dispatch_bwd(res, dxp):
    row_tok, dest, here = res
    dx = _gather_sum(dxp, dest, here, None)
    return dx.astype(dxp.dtype), _float0(row_tok), _float0(dest), \
        _float0(here)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _gather_sum(yp, dest, here, w):
    """sum_j [here[t, j]] w[t, j] yp[dest[t, j]] in float32, one gather an
    assignment slot, so that nothing of size [T, k, d] is alive. ``where``
    and not a product with zero: the rows of dead tiles are never written."""
    last = yp.shape[0] - 1
    out = jnp.zeros((dest.shape[0], yp.shape[1]), jnp.float32)
    for j in range(dest.shape[1]):
        rows = yp[jnp.minimum(dest[:, j], last)].astype(jnp.float32)
        if w is not None:
            rows = rows * w[:, j, None]
        out = out + jnp.where(here[:, j, None], rows, 0.0)
    return out


@jax.custom_vjp
def _combine(yp, w, row_tok, row_w_src, dest, here):
    """yp [rows, d], w [T, k] float32 -> [T, d] float32: each token's rows
    weighted and summed. ``row_w_src`` [rows] indexes ``w`` flattened (T * k
    for a padding row, whose gradient is zero)."""
    return _gather_sum(yp, dest, here, w)


def _combine_fwd(yp, w, row_tok, row_w_src, dest, here):
    return _gather_sum(yp, dest, here, w), (yp, w, row_tok, row_w_src, dest,
                                            here)


def _combine_bwd(res, dout):
    """Row by row, not slot by slot: one gather of the cotangent's rows (in
    yp's dtype, as the grouped product takes it) serves the rows' gradient
    and, dotted with yp, each row's share of its weight's gradient, which a
    scalar gather hands back to the slots. A padding row's weight is zero;
    a dead tile's rows are garbage that no slot reads."""
    yp, w, row_tok, row_w_src, dest, here = res
    row_w = jnp.concatenate([w.reshape(-1), jnp.zeros((1,), w.dtype)]
                            )[row_w_src]
    g = dout.astype(yp.dtype)[row_tok].astype(jnp.float32)
    dyp = (g * row_w[:, None]).astype(yp.dtype)
    row_dw = jnp.sum(g * yp.astype(jnp.float32), axis=-1)
    dw = jnp.where(here, row_dw[jnp.minimum(dest, yp.shape[0] - 1)], 0.0)
    return dyp, dw.astype(w.dtype), _float0(row_tok), _float0(row_w_src), \
        _float0(dest), _float0(here)


_combine.defvjp(_combine_fwd, _combine_bwd)


class DroplessMoE(Layer):
    """Top-k SwiGLU experts, dropless, on the chip that holds ``held`` of
    them.

    Args:
        d_model, d_expert: token and expert widths.
        num_experts: the router's width: all the experts of the layer.
        top_k: experts chosen per token.
        held: ``(first, count)``: this chip holds experts ``first .. first +
            count - 1``; None holds them all.
        shared: a layer applied to every token and added (the shared
            expert: the dense models' SwiGLU), or None.
        routed_scale: factor on the routed sum.
        row_tile: rows of a grouped product's tile; each held expert's rows
            are padded to a multiple of it.
        score_bias: the layer holds ``e_score_correction_bias``
            [num_experts], a buffer and no parameter (zeros until a
            checkpoint or the caller fills it; no gradient, no optimizer
            state): the k experts are chosen by ``score + bias`` and
            weighted by their scores alone ("noaux_tc"). The rule that
            moves the bias from the routing loads during training is not
            here.

    The router's scores are a sigmoid of ``router_input @ router_weight`` in
    float32; the k largest are chosen and their scores normalised to sum to
    one over the chosen k. ``forward(x, router_input=None)``: ``router_input``
    is x in float32 where the caller has it (a top-k is a comparison: an
    input rounded to bfloat16 flips the choice of tokens whose k-th and
    k+1-th scores are close). After a call ``self.expert_load`` holds the
    assignments each held expert got, [held] int32, and
    ``self.expert_choice`` the experts each token chose, [T, k] int32.
    """

    def __init__(self, d_model: int, d_expert: int, num_experts: int,
                 top_k: int, held: Optional[Tuple[int, int]] = None,
                 shared: Optional[Layer] = None, routed_scale: float = 1.0,
                 init_std: float = 0.02, row_tile: int = ROW_TILE,
                 score_bias: bool = False):
        super().__init__()
        first, count = held if held is not None else (0, num_experts)
        if not (0 <= first and count >= 1 and first + count <= num_experts):
            raise ValueError(f"held {held!r} is not a range of the "
                             f"{num_experts} experts")
        if top_k > num_experts:
            raise ValueError("top_k exceeds num_experts")
        self.d_model, self.d_expert = d_model, d_expert
        self.num_experts, self.top_k = num_experts, top_k
        self.first, self.held = first, count
        self.routed_scale = float(routed_scale)
        self.row_tile = row_tile
        init = Normal(0.0, init_std)
        self.router_weight = self.create_parameter(
            [d_model, num_experts], default_initializer=init)
        self.gate_proj = self.create_parameter(
            [count, d_model, d_expert], default_initializer=init)
        self.up_proj = self.create_parameter(
            [count, d_model, d_expert], default_initializer=init)
        self.down_proj = self.create_parameter(
            [count, d_expert, d_model], default_initializer=init)
        self.shared = shared
        if score_bias:
            from ...core.tensor import Tensor
            self.register_buffer("e_score_correction_bias", Tensor(
                jnp.zeros((num_experts,), jnp.float32)))
        else:
            self.e_score_correction_bias = None
        self.expert_load = self.expert_choice = None

    def route(self, x32, router_weight, bias=None):
        """(chosen experts [T, k] int32, their weights [T, k] float32, the
        routed scale not yet applied) of tokens x32 [T, d]. With ``bias``
        [num_experts] the choice is by ``score + bias``; the weights are the
        chosen experts' scores over their sum, the bias taking no part."""
        logits = jnp.dot(x32.astype(jnp.float32),
                         router_weight.astype(jnp.float32),
                         precision=_HIGHEST)
        scores = jax.nn.sigmoid(logits)
        if bias is None:
            top_s, top_i = jax.lax.top_k(scores, self.top_k)
        else:
            _, top_i = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                     self.top_k)
            top_s = jnp.take_along_axis(scores, top_i, axis=-1)
        return top_i.astype(jnp.int32), \
            top_s / jnp.sum(top_s, axis=-1, keepdims=True)

    def _plan(self, tokens: int):
        from ...profiler.tracing import trace_event
        k, tm = self.top_k, self.row_tile
        d, f = self.d_model, self.d_expert
        MOE_PLAN_TALLY[(self.held, self.num_experts, k, tokens, tm)] += 1
        trace_event(
            "moe::plan", cat="kernel", held=self.held, of=self.num_experts,
            first=self.first, top_k=k, tokens=tokens,
            expected_rows=tokens * k * self.held // self.num_experts,
            buffer_rows=padded_rows(tokens * k, self.held, tm), row_tile=tm,
            score_bias=self.e_score_correction_bias is not None,
            gate_up_tile="%dx%d" % gmm_tiles(d, 2 * f),
            down_tile="%dx%d" % gmm_tiles(f, d))

    def forward(self, x, router_input=None):
        """x: [..., d_model]. Returns the shared expert's output plus
        ``routed_scale`` times the held experts' part of the routed sum."""
        shape = x.shape
        t = int(np.prod(shape[:-1]))
        k, f, tm = self.top_k, self.d_expert, self.row_tile
        first, held = self.first, self.held
        self._plan(t)

        def fn(xt, x32, wr, wg, wu, wd, *bias):
            tok = xt.reshape(t, self.d_model)
            top_i, w = self.route(x32.reshape(t, self.d_model), wr, *bias)
            lay = group_layout(top_i.reshape(-1), first, held, tm)
            rows = lay.row_src.shape[0]
            row_tok = jnp.where(lay.row_src < t * k, lay.row_src // k, 0)
            dest = lay.dest.reshape(t, k)
            here = dest < rows
            xp = _dispatch(tok, row_tok, dest, here)
            gu = grouped_matmul(xp, jnp.concatenate([wg, wu], axis=-1),
                                lay.tile_group, lay.n_tiles)
            act = (jax.nn.silu(gu[:, :f].astype(jnp.float32))
                   * gu[:, f:].astype(jnp.float32)).astype(xt.dtype)
            yp = grouped_matmul(act, wd, lay.tile_group, lay.n_tiles)
            routed = _combine(yp, w, row_tok, lay.row_src, dest, here)
            return (routed * self.routed_scale).reshape(shape), lay.sizes, \
                top_i

        bias = self.e_score_correction_bias
        routed, load, choice = run_op(
            "moe_dropless", fn,
            (x, x if router_input is None else router_input,
             self.router_weight, self.gate_proj, self.up_proj,
             self.down_proj) + (() if bias is None else (bias,)),
            num_nondiff_outputs=2)
        self.expert_load, self.expert_choice = load, choice
        if self.shared is None:
            return routed.astype(x.dtype)
        return (self.shared(x).astype("float32") + routed).astype(x.dtype)
