"""Optimizers (parity: python/paddle/optimizer/ — Optimizer base, SGD,
Momentum, Adagrad, RMSProp, Adam, AdamW, Lamb + the fused multi-tensor adamw
kernel capability, reference paddle/phi/kernels/gpu/adamw_kernel.cu).

TPU-native design: each optimizer defines a pure ``_update(param, grad,
state, lr) -> (new_param, new_state)`` rule. The eager ``step()`` applies it
per-parameter (the reference's dygraph path); the functional
``apply_gradients(params, grads, states, lr)`` maps the same rule over a
pytree inside ONE jitted XLA program — that is the fused multi-tensor path:
XLA fuses the whole update sweep into a handful of kernels, which is what
the reference's multi_tensor_adam achieves by hand.
"""
from __future__ import annotations

import collections
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..nn.clip import ClipGradBase
from .lr import LRScheduler

__all__ = ["Optimizer", "SGD", "Momentum", "Adagrad", "RMSProp", "Adam", "Rprop", "LBFGS",
           "AdamW", "Adamax", "Lamb", "Adadelta", "UPDATE_PLAN_TALLY"]

# calls of ``apply_gradients`` by (leaves with a gradient, leaves whose
# gradient is held apart, their parameters, their gradients' bytes): trace
# time only, nothing a step (as flash's TILE_PLAN_TALLY)
UPDATE_PLAN_TALLY: collections.Counter = collections.Counter()


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=False):
        self._lr = learning_rate
        self._parameter_list = list(parameters) if parameters is not None else None
        self._weight_decay = weight_decay
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._states: Dict[int, Dict[str, jnp.ndarray]] = {}
        self._master_weights: Dict[int, jnp.ndarray] = {}
        self._step_count = 0
        self._param_groups = None
        if parameters and isinstance(parameters[0], dict):
            self._param_groups = parameters
            self._parameter_list = [p for g in parameters for p in g["params"]]

    # -- lr ------------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return float(self._lr())
        return float(self._lr)

    def set_lr(self, value):
        self._lr = value

    @property
    def _learning_rate(self):
        return self._lr

    # -- state ---------------------------------------------------------------
    def _state_for(self, p: Tensor) -> Dict[str, jnp.ndarray]:
        key = id(p)
        if key not in self._states:
            self._states[key] = self._init_state(p)
        return self._states[key]

    def _init_state(self, p: Tensor) -> Dict[str, jnp.ndarray]:
        return {}

    def _update(self, param, grad, state, lr):
        raise NotImplementedError

    def _decoupled_weight_decay(self) -> bool:
        return False

    # -- the eager step (parity: optimizer.step() in dygraph) ----------------
    def _decay_of(self, p) -> float:
        """Per-param weight-decay coefficient (AdamW overrides to honor
        apply_decay_param_fun)."""
        del p
        return self._wd_coeff() if self._weight_decay else 0.0

    def step(self):
        params = self._parameter_list
        if params is None:
            raise ValueError("optimizer created without parameters")
        params_grads = [(p, p.grad) for p in params
                        if not p.stop_gradient and p.grad is not None]
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        lr = self.get_lr()
        self._step_count += 1
        if self._try_fused_step(params_grads, lr):
            return
        decoupled = self._decoupled_weight_decay()
        for p, g in params_grads:
            garr = g._data.astype(jnp.float32)
            parr = p._data
            decay = self._decay_of(p)
            # L2 regularization (coupled) unless the rule decouples it
            if decay and not decoupled:
                garr = garr + decay * parr.astype(jnp.float32)
            state = self._state_for(p)
            wd = decay if decoupled else 0.0
            use_master = self._multi_precision and parr.dtype != jnp.float32
            if use_master:
                mw = self._master_weights.setdefault(
                    id(p), parr.astype(jnp.float32))
                new_mw, new_state = self._update(mw, garr, state, lr, wd=wd)
                self._master_weights[id(p)] = new_mw
                p._data = new_mw.astype(parr.dtype)
            else:
                new_p, new_state = self._update(parr.astype(jnp.float32),
                                                garr, state, lr, wd=wd)
                p._data = new_p.astype(parr.dtype)
            self._states[id(p)] = new_state

    # -- fused eager step ---------------------------------------------------
    def _fused_decays(self, params_grads):
        """Per-param (coupled_wd, decoupled_wd) pairs for the fused path."""
        decoupled = self._decoupled_weight_decay()
        return tuple(
            ((0.0, self._decay_of(p)) if decoupled
             else (self._decay_of(p), 0.0)) for p, _ in params_grads)

    def _try_fused_step(self, params_grads, lr) -> bool:
        """One jitted XLA program updating EVERY parameter — the TPU-native
        analog of the reference's fused multi-tensor optimizer kernels
        (_append_optimize_multi_tensor_op / fused adamw). Falls back to the
        per-param loop for master-weight (multi-precision) training.
        Params living on different device sets (pipeline-stage sub-meshes)
        are updated by one fused program per device set — a single XLA
        program cannot span disjoint meshes."""
        from ..core import flags as _flags
        if (not _flags.get_flag("use_fused_optimizer") or not params_grads
                or self._multi_precision):
            return False

        def devset(p):
            sh = getattr(p._data, "sharding", None)
            ds = getattr(sh, "device_set", None)
            return frozenset(d.id for d in ds) if ds else frozenset()

        groups = {}
        for pg in params_grads:
            groups.setdefault(devset(pg[0]), []).append(pg)
        if len(groups) > 1:
            return all(self._fused_step_group(g, lr)
                       for g in groups.values())
        return self._fused_step_group(params_grads, lr)

    def _fused_step_group(self, params_grads, lr) -> bool:
        decays = self._fused_decays(params_grads)
        key = (tuple(id(p) for p, _ in params_grads), decays,
               tuple(str(p._data.dtype) for p, _ in params_grads))
        states = [self._state_for(p) for p, _ in params_grads]
        cache = getattr(self, "_fused_cache", None)
        if cache is None:
            cache = self._fused_cache = {}
        fused_fn = cache.get(key)
        if fused_fn is None:
            n = len(params_grads)

            def fused(parrs, garrs, sts, lr_arr):
                new_p, new_s = [], []
                for i in range(n):
                    parr = parrs[i].astype(jnp.float32)
                    garr = garrs[i].astype(jnp.float32)
                    cwd, dwd = decays[i]
                    if cwd:
                        garr = garr + cwd * parr
                    np_, ns_ = self._update(parr, garr, sts[i], lr_arr,
                                            wd=dwd)
                    new_p.append(np_.astype(parrs[i].dtype))
                    new_s.append(ns_)
                return new_p, new_s

            # donate the old optimizer-state buffers: XLA aliases them into
            # the outputs (moments dominate Adam-state memory). Params are
            # NOT donated — user-held detach()/state_dict views share those
            # buffers and must stay readable after the step.
            fused_fn = cache[key] = jax.jit(fused, donate_argnums=(2,))
        new_p, new_s = fused_fn(
            [p._data for p, _ in params_grads],
            [g._data for _, g in params_grads],
            states, jnp.asarray(lr, jnp.float32))
        for (p, _), np_, ns_ in zip(params_grads, new_p, new_s):
            p._data = np_
            self._states[id(p)] = ns_
        return True

    def clear_grad(self, set_to_zero=True):
        if self._parameter_list:
            for p in self._parameter_list:
                p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        from ..static import Program, TrainNode, Variable
        if isinstance(loss, Variable):
            # static mode: append the backward + update step to the loss's
            # program (parity: append_backward + the optimizer ops)
            loss.program.train_node = TrainNode(loss, self)
            loss.program._version += 1
            return None, None
        loss.backward()
        self.step()
        self.clear_grad()
        return None, None

    # -- functional path (jit): same rule, one XLA program -------------------
    def init_state_tree(self, params: Dict[str, jnp.ndarray]):
        """Build the optimizer state pytree for a {name: array} param dict."""
        class _P:  # shim exposing ._data/.shape for _init_state
            def __init__(self, a):
                self._data = a
        return {k: self._init_state(_P(v)) for k, v in params.items()}

    def apply_gradients(self, params: Dict[str, jnp.ndarray],
                        grads: Dict[str, jnp.ndarray], states, lr,
                        wd_mask: Optional[Dict[str, bool]] = None):
        """Pure functional update over {name: array} dicts — call inside
        jax.jit. ``wd_mask[name]=False`` skips weight decay (bias/norm
        params), mirroring AdamW.apply_decay_param_fun.

        A matrix's gradient (a leaf of 2+ dimensions) is held apart from
        its update by an ``optimization_barrier`` of its own: the backward
        pass's product writes the gradient as the value the program's types
        say it is, and the update runs behind it as an elementwise pass over
        the parameter and its state. Without it XLA takes the whole update
        into the weight gradient's convolution as an epilogue, which ran at
        half the plain product's rate at 4096-wide matrices (PERF.md,
        "PR 35"). One barrier a leaf, never one over the tree: that would
        keep every gradient alive until the last one is there. One
        ``optimizer::plan`` event and one count in ``UPDATE_PLAN_TALLY`` a
        call say how many leaves were held apart."""
        from ..profiler.tracing import trace_event
        new_params, new_states = {}, {}
        wd = self._wd_coeff()
        leaves, held = 0, []
        for k, p in params.items():
            g = grads[k]
            if g is None:
                new_params[k], new_states[k] = p, states[k]
                continue
            leaves += 1
            if g.ndim >= 2:
                g = jax.lax.optimization_barrier(g)
                held.append(g)
            g = g.astype(jnp.float32)
            p32 = p.astype(jnp.float32)
            decay = wd if (wd_mask is None or wd_mask.get(k, True)) else 0.0
            if decay and not self._decoupled_weight_decay():
                g = g + decay * p32
            np_, ns_ = self._update(p32, g, states[k], lr,
                                    wd=decay if self._decoupled_weight_decay() else 0.0)
            new_params[k] = np_.astype(p.dtype)
            new_states[k] = ns_
        plan = (leaves, len(held), sum(g.size for g in held),
                sum(g.size * g.dtype.itemsize for g in held))
        trace_event("optimizer::plan", cat="model", **dict(zip(
            ("leaves", "held", "held_params", "held_bytes"), plan)))
        UPDATE_PLAN_TALLY[plan] += 1
        return new_params, new_states

    def _wd_coeff(self) -> float:
        if isinstance(self._weight_decay, float):
            return self._weight_decay
        return getattr(self._weight_decay, "_coeff", 0.0) if self._weight_decay else 0.0

    # -- checkpoint ----------------------------------------------------------
    def state_dict(self):
        out = {"step": self._step_count}
        if isinstance(self._lr, LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        if self._parameter_list:
            for i, p in enumerate(self._parameter_list):
                st = self._states.get(id(p))
                if st:
                    for k, v in st.items():
                        # snapshot: the fused step donates state buffers to
                        # XLA, so returning aliases would leave the captured
                        # state_dict unreadable after the next step()
                        out[f"{p.name or i}.{k}"] = Tensor(jnp.copy(v))
        return out

    def set_state_dict(self, state_dict):
        self._step_count = state_dict.get("step", 0)
        if isinstance(self._lr, LRScheduler) and "LR_Scheduler" in state_dict:
            self._lr.set_state_dict(state_dict["LR_Scheduler"])
        if self._parameter_list:
            for i, p in enumerate(self._parameter_list):
                st = self._state_for(p)
                for k in list(st.keys()):
                    key = f"{p.name or i}.{k}"
                    if key in state_dict:
                        v = state_dict[key]
                        st[k] = v._data if isinstance(v, Tensor) else jnp.asarray(v)


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)

    def _update(self, param, grad, state, lr, wd=0.0):
        return param - lr * grad, state


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _init_state(self, p):
        return {"velocity": jnp.zeros_like(p._data, dtype=jnp.float32)}

    def _update(self, param, grad, state, lr, wd=0.0):
        v = self._momentum * state["velocity"] + grad
        if self._nesterov:
            upd = grad + self._momentum * v
        else:
            upd = v
        return param - lr * upd, {"velocity": v}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _init_state(self, p):
        return {"moment": jnp.full_like(p._data, self._init_acc, dtype=jnp.float32)}

    def _update(self, param, grad, state, lr, wd=0.0):
        m = state["moment"] + jnp.square(grad)
        return param - lr * grad / (jnp.sqrt(m) + self._epsilon), {"moment": m}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum
        self._centered = centered

    def _init_state(self, p):
        s = {"mean_square": jnp.zeros_like(p._data, dtype=jnp.float32),
             "momentum": jnp.zeros_like(p._data, dtype=jnp.float32)}
        if self._centered:
            s["mean_grad"] = jnp.zeros_like(p._data, dtype=jnp.float32)
        return s

    def _update(self, param, grad, state, lr, wd=0.0):
        ms = self._rho * state["mean_square"] + (1 - self._rho) * jnp.square(grad)
        out_state = {"mean_square": ms}
        if self._centered:
            mg = self._rho * state["mean_grad"] + (1 - self._rho) * grad
            denom = jnp.sqrt(ms - jnp.square(mg) + self._epsilon)
            out_state["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + self._epsilon)
        mom = self._momentum * state["momentum"] + lr * grad / denom
        out_state["momentum"] = mom
        return param - mom, out_state


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 use_multi_tensor=False, amsgrad=False, name=None, *,
                 moment_dtype=None):
        # moment_dtype is keyword-only: it is this framework's extension,
        # and inserting it positionally would shift ``name`` off its
        # reference-API position
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._amsgrad = amsgrad
        # STORAGE dtype of moment1/moment2 (beta pows stay f32, and all
        # moment arithmetic runs in f32 regardless): bf16 halves the
        # optimizer-state HBM — the dominant static cost at billions of
        # params (8 bytes/param f32 -> 4). Parity: the reference's
        # master-weight/multi_precision family trades precision of the
        # stored copy for memory the same way.
        self._moment_dtype = moment_dtype or jnp.float32

    def _init_state(self, p):
        s = {"moment1": jnp.zeros_like(p._data, dtype=self._moment_dtype),
             "moment2": jnp.zeros_like(p._data, dtype=self._moment_dtype),
             "beta1_pow": jnp.ones((), jnp.float32),
             "beta2_pow": jnp.ones((), jnp.float32)}
        if self._amsgrad:
            # f32 regardless of moment_dtype: re-quantizing the running
            # max to bf16 can round DOWN below the true max, breaking
            # AMSGrad's monotone-denominator guarantee
            s["moment2_max"] = jnp.zeros_like(p._data, dtype=jnp.float32)
        return s

    def _update(self, param, grad, state, lr, wd=0.0):
        b1, b2 = self._beta1, self._beta2
        md = self._moment_dtype
        m1 = b1 * state["moment1"].astype(jnp.float32) + (1 - b1) * grad
        m2 = (b2 * state["moment2"].astype(jnp.float32)
              + (1 - b2) * jnp.square(grad))
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        mhat = m1 / (1 - b1p)
        if self._amsgrad:
            m2max = jnp.maximum(state["moment2_max"], m2)
            vhat = m2max / (1 - b2p)
        else:
            vhat = m2 / (1 - b2p)
        if wd:
            param = param * (1.0 - lr * wd)
        new_param = param - lr * mhat / (jnp.sqrt(vhat) + self._epsilon)
        out = {"moment1": m1.astype(md), "moment2": m2.astype(md),
               "beta1_pow": b1p, "beta2_pow": b2p}
        if self._amsgrad:
            out["moment2_max"] = m2max
        return new_param, out


class AdamW(Adam):
    """Decoupled weight decay (parity: paddle.optimizer.AdamW with
    apply_decay_param_fun; kernel parity: phi adamw_kernel)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, amsgrad=False,
                 name=None, *, moment_dtype=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision,
                         amsgrad=amsgrad, moment_dtype=moment_dtype,
                         name=name)
        self._apply_decay_param_fun = apply_decay_param_fun

    def _decoupled_weight_decay(self):
        return True

    def _decay_of(self, p):
        if self._apply_decay_param_fun is not None and \
                not self._apply_decay_param_fun(p.name):
            return 0.0
        return self._wd_coeff()

    def _fused_decays(self, params_grads):
        return tuple((0.0, self._decay_of(p)) for p, _ in params_grads)

    # step() is the base implementation: _decay_of + decoupled wd plumbing
    # cover the AdamW differences


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _init_state(self, p):
        return {"moment": jnp.zeros_like(p._data, dtype=jnp.float32),
                "inf_norm": jnp.zeros_like(p._data, dtype=jnp.float32),
                "beta1_pow": jnp.ones((), jnp.float32)}

    def _update(self, param, grad, state, lr, wd=0.0):
        m = self._beta1 * state["moment"] + (1 - self._beta1) * grad
        u = jnp.maximum(self._beta2 * state["inf_norm"], jnp.abs(grad))
        b1p = state["beta1_pow"] * self._beta1
        new_param = param - lr / (1 - b1p) * m / (u + self._epsilon)
        return new_param, {"moment": m, "inf_norm": u, "beta1_pow": b1p}


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._epsilon, self._rho = epsilon, rho

    def _init_state(self, p):
        return {"avg_squared_grad": jnp.zeros_like(p._data, dtype=jnp.float32),
                "avg_squared_update": jnp.zeros_like(p._data, dtype=jnp.float32)}

    def _update(self, param, grad, state, lr, wd=0.0):
        asg = self._rho * state["avg_squared_grad"] + (1 - self._rho) * jnp.square(grad)
        upd = grad * jnp.sqrt(state["avg_squared_update"] + self._epsilon) / \
            jnp.sqrt(asg + self._epsilon)
        asu = self._rho * state["avg_squared_update"] + (1 - self._rho) * jnp.square(upd)
        return param - lr * upd, {"avg_squared_grad": asg,
                                  "avg_squared_update": asu}


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, lamb_weight_decay,
                         grad_clip, name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _decoupled_weight_decay(self):
        return True

    def _init_state(self, p):
        return {"moment1": jnp.zeros_like(p._data, dtype=jnp.float32),
                "moment2": jnp.zeros_like(p._data, dtype=jnp.float32),
                "beta1_pow": jnp.ones((), jnp.float32),
                "beta2_pow": jnp.ones((), jnp.float32)}

    def _decay_of(self, p) -> float:
        if self._exclude_fn is not None and self._exclude_fn(p):
            return 0.0
        return self._wd_coeff()

    def _update(self, param, grad, state, lr, wd=None):
        # wd=0.0 is a valid "no decay" (excluded param); only None means
        # "unset, use the constructor coefficient".
        if wd is None:
            wd = self._wd_coeff()
        b1, b2 = self._beta1, self._beta2
        m1 = b1 * state["moment1"] + (1 - b1) * grad
        m2 = b2 * state["moment2"] + (1 - b2) * jnp.square(grad)
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        r = (m1 / (1 - b1p)) / (jnp.sqrt(m2 / (1 - b2p)) + self._epsilon) + wd * param
        w_norm = jnp.linalg.norm(param)
        r_norm = jnp.linalg.norm(r)
        ratio = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return param - lr * ratio * r, \
            {"moment1": m1, "moment2": m2, "beta1_pow": b1p, "beta2_pow": b2p}


class Rprop(Optimizer):
    """Resilient backprop (parity: paddle.optimizer.Rprop — per-element
    step sizes grown/shrunk by gradient sign agreement; reference
    python/paddle/optimizer/rprop.py)."""

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50.0),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         name, multi_precision)
        self._lr_range = learning_rate_range
        self._etas = etas

    def _init_state(self, p):
        return {
            "prev_grad": jnp.zeros_like(p._data, dtype=jnp.float32),
            "lr": jnp.full(p._data.shape, float(self._base_lr_value()),
                           jnp.float32),
        }

    def _base_lr_value(self):
        lr = self._learning_rate
        return lr if isinstance(lr, float) else lr()

    def _update(self, param, grad, state, lr, wd=0.0):
        eta_neg, eta_pos = self._etas
        lo, hi = self._lr_range
        sign = jnp.sign(grad * state["prev_grad"])
        factor = jnp.where(sign > 0, eta_pos,
                           jnp.where(sign < 0, eta_neg, 1.0))
        new_lr = jnp.clip(state["lr"] * factor, lo, hi)
        # on sign flip the reference zeroes the step and the stored grad
        step_grad = jnp.where(sign < 0, 0.0, grad)
        new_param = param - jnp.sign(step_grad) * new_lr
        return new_param, {"prev_grad": step_grad, "lr": new_lr}


class LBFGS(Optimizer):
    """Limited-memory BFGS with strong-Wolfe line search (parity:
    paddle.optimizer.LBFGS, reference python/paddle/optimizer/lbfgs.py).

    Full-batch second-order method: ``step(closure)`` re-evaluates the
    loss/gradients through the closure, matching the reference contract.
    History is kept on host; the directional math is vectorized XLA.
    """

    def __init__(self, learning_rate=1.0, max_iter=20, max_eval=None,
                 tolerance_grad=1e-7, tolerance_change=1e-9, history_size=100,
                 line_search_fn=None, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, False)
        self._max_iter = max_iter
        self._max_eval = max_eval if max_eval is not None \
            else max_iter * 5 // 4
        self._tol_grad = tolerance_grad
        self._tol_change = tolerance_change
        self._history = history_size
        self._line_search_fn = line_search_fn
        self._s_hist = []
        self._y_hist = []
        self._rho = []
        self._prev_flat_grad = None

    def _flat(self, arrays):
        return jnp.concatenate([a.reshape(-1) for a in arrays])

    def _gather(self):
        params = [p for p in self._parameter_list]
        flat_p = self._flat([p._data.astype(jnp.float32) for p in params])
        if self._grad_clip is not None:
            pg = [(p, p.grad) for p in params if p.grad is not None]
            clipped = dict(
                (id(p), g) for p, g in self._grad_clip(pg))
        else:
            clipped = None
        grads = []
        for p in params:
            g = p.grad if clipped is None else clipped.get(id(p), p.grad)
            garr = jnp.zeros_like(p._data, jnp.float32) if g is None \
                else g._data.astype(jnp.float32)
            decay = self._decay_of(p)
            if decay:
                garr = garr + decay * p._data.astype(jnp.float32)
            grads.append(garr)
        flat_g = self._flat(grads)
        return params, flat_p, flat_g

    def _scatter(self, params, flat_p):
        off = 0
        for p in params:
            n = int(np.prod(p._data.shape)) if p._data.shape else 1
            chunk = flat_p[off:off + n].reshape(p._data.shape)
            p._data = chunk.astype(p._data.dtype)
            off += n

    def step(self, closure=None):
        if closure is None:
            raise ValueError("LBFGS.step requires a closure that "
                             "re-evaluates the model and returns the loss")
        lr = self._learning_rate if isinstance(self._learning_rate, float) \
            else self._learning_rate()
        loss = closure()
        params, flat_p, flat_g = self._gather()
        n_eval = 1
        for it in range(self._max_iter):
            if float(jnp.max(jnp.abs(flat_g))) <= self._tol_grad:
                break
            # two-loop recursion
            q = -flat_g
            alphas = []
            for s, y, rho in zip(reversed(self._s_hist),
                                 reversed(self._y_hist),
                                 reversed(self._rho)):
                a = rho * jnp.dot(s, q)
                alphas.append(a)
                q = q - a * y
            if self._y_hist:
                y_last = self._y_hist[-1]
                s_last = self._s_hist[-1]
                gamma = jnp.dot(s_last, y_last) / jnp.maximum(
                    jnp.dot(y_last, y_last), 1e-10)
                q = q * gamma
            for (s, y, rho), a in zip(zip(self._s_hist, self._y_hist,
                                          self._rho), reversed(alphas)):
                b = rho * jnp.dot(y, q)
                q = q + (a - b) * s
            direction = q
            gtd = float(jnp.dot(flat_g, direction))
            if gtd > -1e-15:
                direction = -flat_g
                gtd = float(jnp.dot(flat_g, direction))
            t = lr if it > 0 or self._s_hist else \
                min(1.0, 1.0 / max(float(jnp.sum(jnp.abs(flat_g))), 1e-10)) \
                * lr
            if self._line_search_fn == "strong_wolfe":
                t, loss, flat_g_new, evals = self._strong_wolfe(
                    closure, params, flat_p, float(loss), flat_g,
                    direction, t, gtd)
                n_eval += evals
            else:
                self._scatter(params, flat_p + t * direction)
                loss = closure()
                n_eval += 1
                _, _, flat_g_new = self._gather()
            flat_p_new = flat_p + t * direction
            self._scatter(params, flat_p_new)
            s = flat_p_new - flat_p
            y = flat_g_new - flat_g
            sy = float(jnp.dot(s, y))
            if sy > 1e-10:
                self._s_hist.append(s)
                self._y_hist.append(y)
                self._rho.append(1.0 / sy)
                if len(self._s_hist) > self._history:
                    self._s_hist.pop(0)
                    self._y_hist.pop(0)
                    self._rho.pop(0)
            if float(jnp.max(jnp.abs(s))) <= self._tol_change:
                flat_p, flat_g = flat_p_new, flat_g_new
                break
            flat_p, flat_g = flat_p_new, flat_g_new
            if n_eval >= self._max_eval:
                break
        return loss

    def _strong_wolfe(self, closure, params, flat_p, f0, g0, d, t, gtd0,
                      c1=1e-4, c2=0.9, max_ls=25):
        """Bracketing strong-Wolfe line search (reference lbfgs.py
        _strong_wolfe)."""
        evals = 0
        f_prev, t_prev = f0, 0.0
        g_prev = g0
        for ls in range(max_ls):
            self._scatter(params, flat_p + t * d)
            f_new = float(closure())
            _, _, g_new = self._gather()
            evals += 1
            gtd_new = float(jnp.dot(g_new, d))
            if f_new > f0 + c1 * t * gtd0 or (ls > 0 and f_new >= f_prev):
                return self._zoom(closure, params, flat_p, f0, gtd0, d,
                                  t_prev, t, f_prev, f_new, c1, c2,
                                  evals)
            if abs(gtd_new) <= -c2 * gtd0:
                return t, f_new, g_new, evals
            if gtd_new >= 0:
                return self._zoom(closure, params, flat_p, f0, gtd0, d,
                                  t, t_prev, f_new, f_prev, c1, c2,
                                  evals)
            t_prev, f_prev, g_prev = t, f_new, g_new
            t = t * 2.0
        return t, f_new, g_new, evals

    def _zoom(self, closure, params, flat_p, f0, gtd0, d, t_lo, t_hi,
              f_lo, f_hi, c1, c2, evals, max_zoom=10):
        for _ in range(max_zoom):
            t = 0.5 * (t_lo + t_hi)
            self._scatter(params, flat_p + t * d)
            f_new = float(closure())
            _, _, g_new = self._gather()
            evals += 1
            gtd_new = float(jnp.dot(g_new, d))
            if f_new > f0 + c1 * t * gtd0 or f_new >= f_lo:
                t_hi, f_hi = t, f_new
            else:
                if abs(gtd_new) <= -c2 * gtd0:
                    return t, f_new, g_new, evals
                if gtd_new * (t_hi - t_lo) >= 0:
                    t_hi, f_hi = t_lo, f_lo
                t_lo, f_lo = t, f_new
        return t, f_new, g_new, evals
