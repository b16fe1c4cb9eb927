"""Activation recomputation (gradient checkpointing).

Capability parity with the reference (reference: fleet/recompute/
recompute.py — RecomputeFunction PyLayer with RNG-state replay :108,
recompute() API :404, recompute_sequential :542, offload variant
recompute_hybrid.py).

TPU-native: on the functional/jit path this is ``jax.checkpoint`` — XLA
rematerializes inside one program (strictly better than the reference's
replay machinery). On the imperative tape path we implement true
recompute-on-backward: forward runs under no_grad saving only inputs +
RNG (seed, offset); backward replays the forward with the restored RNG
state to rebuild the vjp — the reference's RNG-replay contract.
"""
from __future__ import annotations

import collections
from typing import Sequence

import jax
from jax.ad_checkpoint import checkpoint_name

from ....core import random as _random
from ....core.autograd import TapeNode, is_tape_active, no_grad, tape_paused
from ....core.tensor import Tensor

__all__ = ["recompute", "recompute_sequential", "checkpoint", "keep",
           "RECOMPUTE_PLAN_TALLY"]

# blocks lowered through ``_remat_functional`` by (policy, bytes kept):
# trace time only, nothing a step (as flash's TILE_PLAN_TALLY)
RECOMPUTE_PLAN_TALLY: collections.Counter = collections.Counter()


def _any_traced(args) -> bool:
    for a in args:
        if isinstance(a, Tensor) and isinstance(a._data, jax.core.Tracer):
            return True
    return False


# a policy is None (replay everything), an attribute of
# jax.checkpoint_policies, or the tuple of names ``keep`` gave that the
# backward pass keeps (save_only_these_names)
_POLICIES = {
    None: None, "full": None, "nothing_saveable": None,
    # selective remat: save matmul/dot outputs, recompute only cheap
    # elementwise work — ~0 extra matmul FLOPs vs full remat's +1 forward
    # (the fwd FLOPs are ~2/6 of a train step, so full per-layer remat
    # costs ~33% throughput; selective costs ~0 at higher memory).
    # "selective" is an alias of dots_saveable — NOT the
    # no-batch-dims variant, which re-runs every batched matmul
    # (attention BMMs) and forfeits exactly the FLOPs this exists to keep
    "dots_saveable": "dots_saveable",
    "selective": "dots_saveable",
    "dots_with_no_batch_dims_saveable": "dots_with_no_batch_dims_saveable",
    "everything_saveable": "everything_saveable",
    # "full" with one thing kept: a flash call's output and statistics, so
    # the replay re-runs the projections (the backward kernels need q, k,
    # v) but not the forward kernel, whose second run would write the same
    # bits. A call costs B x S x Hq x D x itemsize + 4 x B x Hq x S bytes.
    # Rule of thumb: a kept byte of ``out`` saves as many FLOPs as a query
    # sees keys (causal: 4 x S/2 x D FLOPs a row of 2 D bytes; S at full
    # attention, the window under one), a matmul output's byte as many as
    # a quarter of the contracted width: past a few thousand keys the
    # forward kernel is the dearest thing in the block to replay. The names
    # are given in ops/pallas/flash_attention.py::_fa_fwd: ``out`` [B, S,
    # Hq, D] and the softmax statistics ``lse`` [B x Hq, S] float32. The
    # gated delta rule's forward kernel is spared the same way (names given
    # in ops/pallas/gated_delta.py::_gdn_fwd): its output ``gdn_out`` [B, S,
    # Hv, dv] in the activations' dtype and the chunk states it parks for
    # the backward kernel, ``gdn_states`` [B x Hv, chunks, dk, dv] float32,
    # B x S x Hv x dv x itemsize + 4 x B x Hv x chunks x dk x dv bytes a
    # call: at S 16384, 32 value heads of 128 in bfloat16, 128 MiB + 256 MiB
    # a layer against a forward kernel of 15.02 ms on a TPU v5e (PERF.md).
    # Mamba-2's SSD scan likewise (ops/pallas/ssd.py::_ssd_fwd): ``ssd_out``
    # [B, S, H, P] and ``ssd_states`` [B x H, chunks, P, N] float32, 128 MiB
    # + 256 MiB a layer at S 16384, 64 heads of 64, state 128.
    # A block that makes none of these calls names nothing and lowers as
    # under "full".
    "flash_saveable": ("flash_out", "flash_lse", "gdn_out", "gdn_states",
                       "ssd_out", "ssd_states"),
    # a block whose mixer is attention over chosen key blocks
    # (F.block_sparse_attention): what is dear and small there is the choice,
    # ``sparse_choice`` [B, Hkv, S, blocks] bool (a byte a block, token and kv
    # head against a softmax over every pooled key for every query head),
    # and, as for a flash call, the sweep's ``sparse_out`` and
    # ``sparse_lse``; below ``dense_len`` the mixer IS a flash call, so what
    # "flash_saveable" keeps is kept too. A linear-attention mixer's chunk
    # states are residuals of its own custom_vjp inside the replay and are
    # not named:
    # its forward kernel takes 1.1 ms a layer at S 12288 against the sparse
    # forward sweep's 10.3 ms, and its states are 100 MB a layer (PERF.md,
    # PR 34).
    "sala_saveable": ("sparse_choice", "sparse_out", "sparse_lse"),
}
_POLICIES["sala_saveable"] += _POLICIES["flash_saveable"]


def _resolve_policy(policy):
    if callable(policy):
        return policy
    if policy not in _POLICIES:
        raise ValueError(
            f"unknown recompute policy {policy!r}; one of "
            f"{sorted(k for k in _POLICIES if isinstance(k, str))}")
    entry = _POLICIES[policy]
    if isinstance(entry, tuple):
        return jax.checkpoint_policies.save_only_these_names(*entry)
    return getattr(jax.checkpoint_policies, entry) if entry else None


# the residuals named inside each block being traced (innermost last):
# [name, bytes] pairs, read by ``_remat_functional`` for its plan; beside
# them the names each block's policy keeps
_NAMED = []
_KEEPS = []


def replayed(name) -> bool:
    """Whether the backward pass of the block being traced replays the value
    that ``keep`` names ``name``: inside a block whose policy does not keep
    it by name. Outside any block nothing is replayed."""
    return bool(_KEEPS) and name not in _KEEPS[-1]


def keep(x, name):
    """Name ``x`` for the policies that keep by name (``_POLICIES``), and
    tell the block being lowered, if one is, what it would hold. Outside a
    ``jax.checkpoint`` with such a policy the name is the identity and
    lowers to nothing."""
    if _NAMED:
        _NAMED[-1].append((name, x.size * x.dtype.itemsize))
    return checkpoint_name(x, name)


def _kept_names(policy) -> tuple:
    """The names a policy keeps by name (none for a callable one)."""
    entry = None if callable(policy) else _POLICIES.get(policy)
    return entry if isinstance(entry, tuple) else ()


def _record_plan(policy, named):
    """One ``recompute::plan`` event and one count in RECOMPUTE_PLAN_TALLY
    for a block lowered through ``_remat_functional``: the policy, the
    names it keeps and the bytes they hold in this block (from the shapes
    of what ``keep`` named inside; a callable policy keeps what only it
    knows: None)."""
    from ....profiler.tracing import trace_event
    names = _kept_names(policy)
    if callable(policy):
        label, kept = getattr(policy, "__name__", "callable"), None
    else:
        label = policy or "full"
        kept = sum(b for n, b in named if n in names)
    trace_event("recompute::plan", cat="model", policy=label,
                names=",".join(names), kept_bytes=kept,
                named_values=len(named))
    RECOMPUTE_PLAN_TALLY[(label, kept)] += 1


def _remat_functional(function, args, kwargs, policy=None):
    """Functional/jit path: route the call through ``jax.checkpoint`` so XLA
    rematerializes the segment's activations on the backward pass. Layer
    parameters are closed-over tracers — they stay residuals (params are
    live for the optimizer anyway); only the explicit activation args bound
    the remat segment. ``policy`` selects WHAT to save (reference
    recompute saves everything-at-boundaries; 'dots_saveable'/'selective'
    keep matmul outputs so the backward re-runs only elementwise work;
    'flash_saveable' replays everything but the flash forward kernel, whose
    output and statistics it keeps, and the gated delta rule's forward
    kernel, whose output and chunk states it keeps; 'sala_saveable'
    likewise for a block of chosen-block attention, and keeps the choice).
    Leaves a ``recompute::plan`` event."""
    tensor_idx = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
    arrays = [args[i]._data for i in tensor_idx]
    sg = [args[i].stop_gradient for i in tensor_idx]
    meta, named = {}, []
    keeps = _kept_names(policy)

    def pure(*arrs):
        call = list(args)
        for j, i in enumerate(tensor_idx):
            call[i] = Tensor(arrs[j], stop_gradient=sg[j])
        # only while the block's own body is traced: the custom_vjp rules
        # of what is inside are traced later, outside this frame
        _NAMED.append(named)
        _KEEPS.append(keeps)
        try:
            out = function(*call, **kwargs)
        finally:
            _NAMED.pop()
            _KEEPS.pop()
        single = not isinstance(out, (tuple, list))
        meta["single"] = single
        outs = (out,) if single else tuple(out)
        meta["is_tensor"] = [isinstance(o, Tensor) for o in outs]
        return tuple(o._data if isinstance(o, Tensor) else o for o in outs)

    pol = _resolve_policy(policy)
    res = (jax.checkpoint(pure, policy=pol) if pol is not None
           else jax.checkpoint(pure))(*arrays)
    _record_plan(policy, named)
    outs = [Tensor(r, stop_gradient=False) if t else r
            for r, t in zip(res, meta["is_tensor"])]
    return outs[0] if meta["single"] else tuple(outs)


def recompute(function, *args, **kwargs):
    """paddle.distributed.fleet.utils.recompute parity. ``use_reentrant``
    accepted and ignored (single behavior). ``policy`` (jit path only)
    picks the jax.checkpoint saveable policy (``_POLICIES``): "full", the
    reference's, replays the whole segment; "dots_saveable" / "selective"
    keep matmul outputs; "flash_saveable" is "full" with each flash call's
    ``out`` and ``lse`` kept (B x S x Hq x D x itemsize + 4 x B x Hq x S
    bytes a call), so the forward kernel is not run a second time: worth
    it from a few thousand keys on, since a byte of ``out`` saves as many
    FLOPs as a query sees keys; it keeps the gated delta rule's forward
    output and chunk states likewise. The eager tape path always replays the
    whole segment (the reference behavior)."""
    kwargs.pop("use_reentrant", None)
    preserve_rng = kwargs.pop("preserve_rng_state", True)
    policy = kwargs.pop("policy", None)

    if not is_tape_active():
        if _any_traced(args):
            # under a jit/vjp trace (create_train_step, DistModel, the
            # pipeline chunk programs): real gradient checkpointing
            return _remat_functional(function, args, kwargs, policy)
        # plain eager no-grad call: recompute has nothing to save
        return function(*args, **kwargs)

    # record RNG state so dropout masks replay identically (reference
    # RecomputeFunction: CUDA seed/offset capture; here (seed, offset))
    gen_state = _random.default_generator.peek_state() if preserve_rng else None

    tensor_args = [a for a in args if isinstance(a, Tensor)]
    diff_inputs = [t for t in tensor_args if not t.stop_gradient]

    with no_grad():
        outputs = function(*args, **kwargs)
    single = not isinstance(outputs, (tuple, list))
    out_list = (outputs,) if single else tuple(outputs)

    if not diff_inputs:
        return outputs

    def vjp_fn(cts):
        # replay forward WITH grad tracking on detached inputs
        if gen_state is not None:
            saved = _random.default_generator.peek_state()
            _random.default_generator.set_state(gen_state)
        try:
            detached = []
            mapping = {}
            for a in args:
                if isinstance(a, Tensor) and not a.stop_gradient:
                    d = Tensor(a._data, stop_gradient=False)
                    mapping[id(a)] = d
                    detached.append(d)
                elif isinstance(a, Tensor):
                    detached.append(a.detach())
                else:
                    detached.append(a)
            replay = function(*detached, **kwargs)
            rlist = (replay,) if not isinstance(replay, (tuple, list)) \
                else tuple(replay)
            from ....core.autograd import _run_backward
            targets = [mapping[id(t)] for t in diff_inputs]
            # accumulate_leaf=True: parameters captured by the function get
            # their grads accumulated here (reference RecomputeFunction's
            # backward does the same via its replayed graph)
            tg = _run_backward(list(rlist),
                               [Tensor(c, stop_gradient=True) for c in cts],
                               retain_graph=False, targets=targets,
                               accumulate_leaf=True)
            return tuple(tg.get(id(t), None) if tg.get(id(t)) is None
                         else tg[id(t)]._data
                         if isinstance(tg.get(id(t)), Tensor) else tg[id(t)]
                         for t in targets)
        finally:
            if gen_state is not None:
                _random.default_generator.set_state(saved)

    node = TapeNode("recompute", diff_inputs, vjp_fn,
                    [jax.ShapeDtypeStruct(o._data.shape, o._data.dtype)
                     for o in out_list])
    wrapped = []
    for i, o in enumerate(out_list):
        t = Tensor(o._data, stop_gradient=False)
        t._node = node
        t._out_idx = i
        wrapped.append(t)
    return wrapped[0] if single else tuple(wrapped)


def recompute_sequential(ctx, functions, *args, **kwargs):
    """Recompute a Sequential in segments (reference :542)."""
    segments = ctx.get("segments", 1) if isinstance(ctx, dict) else 1
    layers = list(functions)
    n = len(layers)
    per = max(n // segments, 1)
    out = args[0]
    i = 0
    while i < n:
        chunk = layers[i:i + per]

        def seg(x, _chunk=chunk):
            for l in _chunk:
                x = l(x)
            return x
        out = recompute(seg, out)
        i += per
    return out


def checkpoint(function):
    """Functional-path decorator: jax.checkpoint for jitted training
    (XLA remat — the TPU answer to recompute_hybrid offload)."""
    return jax.checkpoint(function)
