"""Linear algebra ops (parity: python/paddle/tensor/linalg.py, 4.6k LoC in
the reference). matmul-class ops are the MXU hot path — kept as single jnp
calls so XLA tiles them onto the systolic array in bf16."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import run_op
from ..core.tensor import Tensor

__all__ = [
    "matmul", "mm", "bmm", "dot", "t", "transpose", "dist", "norm", "cond",
    "cross", "cholesky", "cholesky_solve", "bincount", "histogram", "mv",
    "matrix_power", "qr", "lu", "eig", "eigvals", "eigh", "eigvalsh",
    "multi_dot", "svd", "pinv", "solve", "triangular_solve", "lstsq", "slogdet",
    "det", "matrix_rank", "corrcoef", "cov", "householder_product", "vander",
    "vecdot", "matrix_norm", "vector_norm", "inv", "lu_unpack",
    "matrix_exp", "pca_lowrank",
]


def matmul(x, y, transpose_x=False, transpose_y=False, name=None):
    def fn(a, b):
        if transpose_x:
            a = jnp.swapaxes(a, -1, -2) if a.ndim > 1 else a
        if transpose_y:
            b = jnp.swapaxes(b, -1, -2) if b.ndim > 1 else b
        return jnp.matmul(a, b)
    return run_op("matmul", fn, (x, y),
                  attrs={"transpose_x": transpose_x,
                         "transpose_y": transpose_y})


def mm(input, mat2, name=None):
    return run_op("matmul", jnp.matmul, (input, mat2))


def bmm(x, y, name=None):
    return run_op("matmul", jnp.matmul, (x, y))


def dot(x, y, name=None):
    return run_op("dot", lambda a, b: jnp.sum(a * b, axis=-1), (x, y))


def mv(x, vec, name=None):
    return run_op("matmul", jnp.matmul, (x, vec))


def t(input, name=None):
    def fn(a):
        if a.ndim <= 1:
            return a
        return a.T
    return run_op("t", fn, (input,))


def transpose(x, perm, name=None):
    from .manipulation import transpose as _tr
    return _tr(x, perm)


def dist(x, y, p=2, name=None):
    def fn(a, b):
        d = jnp.abs(a - b)
        if p == float("inf"):
            return jnp.max(d)
        if p == 0:
            return jnp.sum(d != 0).astype(a.dtype)
        return jnp.power(jnp.sum(jnp.power(d, p)), 1.0 / p)
    return run_op("dist", fn, (x, y))


def norm(x, p=None, axis=None, keepdim=False, name=None):
    def fn(a):
        if p is None or p == "fro":
            if axis is None:
                return jnp.sqrt(jnp.sum(jnp.square(a)))
            return jnp.linalg.norm(a, ord=None, axis=_ax(axis), keepdims=keepdim)
        if p == float("inf"):
            return jnp.max(jnp.abs(a), axis=_ax(axis), keepdims=keepdim)
        if p == float("-inf"):
            return jnp.min(jnp.abs(a), axis=_ax(axis), keepdims=keepdim)
        if axis is None:
            return jnp.power(jnp.sum(jnp.power(jnp.abs(a), p)), 1.0 / p)
        return jnp.power(jnp.sum(jnp.power(jnp.abs(a), p), axis=_ax(axis),
                                 keepdims=keepdim), 1.0 / p)
    return run_op("norm", fn, (x,))


def vector_norm(x, p=2.0, axis=None, keepdim=False, name=None):
    return norm(x, p=p, axis=axis, keepdim=keepdim)


def matrix_norm(x, p="fro", axis=(-2, -1), keepdim=False, name=None):
    return run_op("matrix_norm",
                  lambda a: jnp.linalg.norm(a, ord=None if p == "fro" else p,
                                            axis=tuple(axis), keepdims=keepdim), (x,))


def cond(x, p=None, name=None):
    return run_op("cond", lambda a: jnp.linalg.cond(a, p=p), (x,))


def cross(x, y, axis=9, name=None):
    def fn(a, b):
        ax = axis
        if ax == 9:
            ax = next(i for i, s in enumerate(a.shape) if s == 3)
        return jnp.cross(a, b, axis=ax)
    return run_op("cross", fn, (x, y))


def cholesky(x, upper=False, name=None):
    def fn(a):
        l = jnp.linalg.cholesky(a)
        return jnp.swapaxes(l, -1, -2).conj() if upper else l
    return run_op("cholesky", fn, (x,))


def cholesky_solve(x, y, upper=False, name=None):
    def fn(b, l):
        if upper:
            l = jnp.swapaxes(l, -1, -2).conj()
        z = jax.scipy.linalg.solve_triangular(l, b, lower=True)
        return jax.scipy.linalg.solve_triangular(jnp.swapaxes(l, -1, -2).conj(), z, lower=False)
    return run_op("cholesky_solve", fn, (x, y))


def bincount(x, weights=None, minlength=0, name=None):
    data = np.asarray(x._data if isinstance(x, Tensor) else x)
    length = max(int(data.max()) + 1 if data.size else 0, minlength)
    if weights is not None:
        return run_op("bincount",
                      lambda i, w: jnp.bincount(i.astype(jnp.int32), w, length=length),
                      (x, weights))
    return run_op("bincount",
                  lambda i: jnp.bincount(i.astype(jnp.int32), length=length), (x,))


def histogram(input, bins=100, min=0, max=0, weight=None, density=False, name=None):
    data = np.asarray(input._data if isinstance(input, Tensor) else input)
    lo, hi = (float(data.min()), float(data.max())) if min == 0 and max == 0 else (min, max)
    w = np.asarray(weight._data) if isinstance(weight, Tensor) else weight
    h, _ = np.histogram(data, bins=bins, range=(lo, hi), weights=w, density=density)
    return Tensor(jnp.asarray(h if density else h.astype(np.int64)))


def matrix_power(x, n, name=None):
    return run_op("matrix_power", lambda a: jnp.linalg.matrix_power(a, n), (x,))


def qr(x, mode="reduced", name=None):
    return run_op("qr", lambda a: tuple(jnp.linalg.qr(a, mode=mode)), (x,))


def lu(x, pivot=True, get_infos=False, name=None):
    def fn(a):
        lu_, piv = jax.scipy.linalg.lu_factor(a)
        return lu_, (piv + 1).astype(jnp.int32)
    lu_t, piv = run_op("lu", fn, (x,), num_nondiff_outputs=1)
    if get_infos:
        info = Tensor(jnp.zeros(x.shape[:-2], jnp.int32))
        return lu_t, piv, info
    return lu_t, piv


def eig(x, name=None):
    data = np.asarray(x._data if isinstance(x, Tensor) else x)
    w, v = np.linalg.eig(data)
    return Tensor(jnp.asarray(w)), Tensor(jnp.asarray(v))


def eigvals(x, name=None):
    data = np.asarray(x._data if isinstance(x, Tensor) else x)
    return Tensor(jnp.asarray(np.linalg.eigvals(data)))


def eigh(x, UPLO="L", name=None):
    return run_op("eigh", lambda a: tuple(jnp.linalg.eigh(a, UPLO=UPLO)), (x,))


def eigvalsh(x, UPLO="L", name=None):
    return run_op("eigvalsh", lambda a: jnp.linalg.eigvalsh(a, UPLO=UPLO), (x,))


def multi_dot(x, name=None):
    return run_op("multi_dot", lambda *xs: jnp.linalg.multi_dot(xs), tuple(x))


def _svd_on_host(*operands) -> bool:
    """XLA's TPU compiler aborts the whole process on the SVD HLO (libtpu
    0.0.34, jax 0.9.0, on a v5e: ``jnp.linalg.svd`` of a float32
    [3, 48, 32] batch dies with ``Check failed: buffer != nullptr`` at
    shape.h:842 inside the TransposeFolding pass, SIGABRT -- not an
    exception a caller could catch), so off the CPU backend the
    SVD-family ops (svd/pinv/lstsq) run on the host in eager mode — the
    reference keeps CPU fallback kernels for exactly this class
    (paddle/phi/core/kernel_factory.h CPU-fallback path). Differentiable
    jnp path is kept on CPU (tests) and under tracing; on TPU, grads ride
    the host tape node with the analytic SVD vjp (_svd_host_node)."""
    del operands
    return jax.default_backend() != "cpu"


def _needs_grad(*operands) -> bool:
    from ..core import autograd as _ag
    return _ag.is_tape_active() and any(
        isinstance(o, Tensor) and not o.stop_gradient for o in operands)


def _svd_vjp_host(u, s, vh, dus, dss, dvhs):
    """Analytic thin-SVD vjp in numpy (the standard U/S/V cotangent
    formula, batched over leading dims). u (..., m, k), s (..., k),
    vh (..., k, n); cotangents may be None."""
    m, k = u.shape[-2], u.shape[-1]
    n = vh.shape[-1]
    v = np.swapaxes(vh, -1, -2)
    s2 = s[..., None, :] ** 2 - s[..., :, None] ** 2
    eye = np.eye(k, dtype=bool)
    with np.errstate(divide="ignore", invalid="ignore"):
        F = np.where(eye, 0.0, 1.0 / np.where(eye, 1.0, s2))
    sinv = np.where(s > 0, 1.0 / np.maximum(s, 1e-38), 0.0)

    mid = np.zeros(u.shape[:-2] + (k, k), u.dtype)
    if dss is not None:
        idx = np.arange(k)
        mid[..., idx, idx] = dss
    da_extra = 0.0
    if dus is not None:
        utdu = np.swapaxes(u, -1, -2) @ dus
        J = F * (utdu - np.swapaxes(utdu, -1, -2))
        mid = mid + J * s[..., None, :]
        # component of dU outside span(U): (I - U U^T) dU S^{-1} V^T
        proj = dus - u @ utdu
        da_extra = da_extra + proj * sinv[..., None, :] @ vh
    if dvhs is not None:
        dv = np.swapaxes(dvhs, -1, -2)
        vtdv = np.swapaxes(v, -1, -2) @ dv
        K = F * (vtdv - np.swapaxes(vtdv, -1, -2))
        mid = mid + s[..., :, None] * K
        projv = dv - v @ vtdv
        da_extra = da_extra + u * sinv[..., None, :] @ np.swapaxes(projv, -1, -2)
    return u @ mid @ vh + da_extra


def _svd_host_node(x):
    """Host np SVD with a tape node whose vjp is the analytic formula —
    the TPU path for differentiable svd (full_matrices=False only, like
    jax's own svd JVP rule)."""
    from ..core import autograd as _ag
    a_np = np.asarray(x._data)
    if np.iscomplexobj(a_np):
        # _svd_vjp_host implements the REAL-valued cotangent formula (no
        # conjugation terms); silently wrong complex grads must not ship
        raise NotImplementedError(
            "differentiable svd on the host tape path supports real "
            "dtypes only (the analytic vjp lacks the conjugate terms); "
            "run complex svd under stop_gradient or on the CPU backend")
    u, s, vh = np.linalg.svd(a_np, full_matrices=False)
    outs = (jnp.asarray(u), jnp.asarray(s), jnp.asarray(vh))

    a_dtype = a_np.dtype  # don't pin the input copy in the closure

    def vjp_fn(cts):
        du, ds, dvh = [None if c is None else np.asarray(c) for c in cts]
        da = _svd_vjp_host(u, s, vh, du, ds, dvh)
        return (jnp.asarray(da.astype(a_dtype)),)

    node = _ag.TapeNode(
        "svd_host", [x], vjp_fn,
        [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in outs])
    wrapped = []
    for i, o in enumerate(outs):
        t = Tensor(o, stop_gradient=False)
        t._node = node
        t._out_idx = i
        wrapped.append(t)
    return tuple(wrapped)


def svd(x, full_matrices=False, name=None):
    a = x._data if isinstance(x, Tensor) else x
    if not isinstance(a, jax.core.Tracer) and _svd_on_host(x):
        if _needs_grad(x):
            if full_matrices:
                raise NotImplementedError(
                    "svd gradients need full_matrices=False (jax's own "
                    "constraint)")
            return _svd_host_node(x)
        u, s, vh = np.linalg.svd(np.asarray(a), full_matrices=full_matrices)
        return (Tensor(jnp.asarray(u)), Tensor(jnp.asarray(s)),
                Tensor(jnp.asarray(vh)))
    return run_op("svd",
                  lambda a: tuple(jnp.linalg.svd(a, full_matrices=full_matrices)), (x,))


def pinv(x, rcond=1e-15, hermitian=False, name=None):
    a = x._data if isinstance(x, Tensor) else x
    if not isinstance(a, jax.core.Tracer) and not hermitian \
            and _svd_on_host(x):
        if _needs_grad(x):
            # compose from the differentiable host svd: grads flow
            # through the analytic svd vjp (2-D only, like the svd node)
            if len(a.shape) != 2:
                raise NotImplementedError(
                    "pinv gradients on the host-fallback path support 2-D "
                    "inputs only; batch with a Python loop")
            from . import manipulation as M
            from . import math as Tm
            dt = np.asarray(a).dtype
            u, s, vh = svd(x, full_matrices=False)
            cutoff = float(rcond) * float(np.max(np.asarray(s._data)))
            sinv_np = np.where(np.asarray(s._data) > cutoff,
                               1.0 / np.asarray(s._data), 0.0)
            mask = Tensor(jnp.asarray((sinv_np > 0).astype(dt)))
            sinv = mask / Tm.maximum(s, Tensor(jnp.asarray(
                dt.type(max(cutoff, 1e-38)))))
            vt = M.transpose(vh, [1, 0])
            ut = M.transpose(u, [1, 0])
            return matmul(vt * M.reshape(sinv, [1, -1]), ut)
        return Tensor(jnp.asarray(np.linalg.pinv(np.asarray(a), rcond=rcond)))
    return run_op("pinv", lambda a: jnp.linalg.pinv(a, rtol=rcond, hermitian=hermitian), (x,))


def inv(x, name=None):
    return run_op("inv", jnp.linalg.inv, (x,))


def solve(x, y, name=None):
    return run_op("solve", jnp.linalg.solve, (x, y))


def triangular_solve(x, y, upper=True, transpose=False, unitriangular=False, name=None):
    return run_op("triangular_solve",
                  lambda a, b: jax.scipy.linalg.solve_triangular(
                      a, b, lower=not upper, trans=1 if transpose else 0,
                      unit_diagonal=unitriangular), (x, y))


def lstsq(x, y, rcond=None, driver=None, name=None):
    a0 = x._data if isinstance(x, Tensor) else x
    if not isinstance(a0, jax.core.Tracer) and _svd_on_host(x, y):
        b0 = y._data if isinstance(y, Tensor) else y
        a_np, b_np = np.asarray(a0), np.asarray(b0)
        if _needs_grad(x, y):
            # differentiable solution via the composed host pinv (the
            # minimum-norm least-squares solution IS pinv(A) @ b) with
            # numpy's effective rcond (None -> eps * max(m, n)) so the
            # forward matches the no-grad path; rank/sv come from a
            # values-only svd pass and res from the solution itself (no
            # duplicate full lstsq solve)
            from . import manipulation as M
            from . import math as Tm
            m, n = a_np.shape[-2], a_np.shape[-1]
            rcond_eff = (float(rcond) if rcond is not None
                         else np.finfo(a_np.dtype).eps * max(m, n))
            # ONE host SVD: the differentiable factors give the pinv
            # composition, their values give rank/sv
            u_t, s_t, vh_t = svd(x, full_matrices=False)
            sv = np.asarray(s_t._data)
            cutoff = rcond_eff * (sv.max() if sv.size else 0.0)
            dt = a_np.dtype
            mask = Tensor(jnp.asarray((sv > cutoff).astype(dt)))
            sinv = mask / Tm.maximum(s_t, Tensor(jnp.asarray(
                dt.type(max(cutoff, 1e-38)))))
            pinv_x = matmul(M.transpose(vh_t, [1, 0])
                            * M.reshape(sinv, [1, -1]),
                            M.transpose(u_t, [1, 0]))
            sol = matmul(pinv_x, y)
            rank = int(np.sum(sv > cutoff))
            if rank == n and m > n:
                diff = a_np @ np.asarray(sol._data) - b_np
                res = np.atleast_1d(np.sum(diff * diff, axis=0))
            else:
                res = np.zeros((0,), a_np.dtype)
            return (sol, Tensor(jnp.asarray(res)),
                    Tensor(jnp.asarray(np.int32(rank))),
                    Tensor(jnp.asarray(sv)))
        sol_np, res, rank, sv = np.linalg.lstsq(a_np, b_np, rcond=rcond)
        return (Tensor(jnp.asarray(sol_np)), Tensor(jnp.asarray(res)),
                Tensor(jnp.asarray(np.int32(rank))),
                Tensor(jnp.asarray(sv)))

    def fn(a, b):
        sol, res, rank, sv = jnp.linalg.lstsq(a, b, rcond=rcond)
        return sol, res, rank.astype(jnp.int32), sv
    s, r, rk, sv = run_op("lstsq", fn, (x, y), num_nondiff_outputs=2)
    return s, r, rk, sv


def slogdet(x, name=None):
    def fn(a):
        sign, logdet = jnp.linalg.slogdet(a)
        return jnp.stack([sign, logdet])
    return run_op("slogdet", fn, (x,))


def det(x, name=None):
    return run_op("det", jnp.linalg.det, (x,))


def matrix_rank(x, tol=None, hermitian=False, name=None):
    return run_op("matrix_rank",
                  lambda a: jnp.linalg.matrix_rank(a, rtol=tol).astype(jnp.int64),
                  (x,), num_nondiff_outputs=1)


def corrcoef(x, rowvar=True, name=None):
    return run_op("corrcoef", lambda a: jnp.corrcoef(a, rowvar=rowvar), (x,))


def cov(x, rowvar=True, ddof=True, fweights=None, aweights=None, name=None):
    fw = fweights._data if isinstance(fweights, Tensor) else fweights
    aw = aweights._data if isinstance(aweights, Tensor) else aweights
    return run_op("cov", lambda a: jnp.cov(a, rowvar=rowvar, ddof=1 if ddof else 0,
                                           fweights=fw, aweights=aw), (x,))


def householder_product(x, tau, name=None):
    def fn(a, t_):
        *batch, m, n = a.shape
        q = jnp.broadcast_to(jnp.eye(m, dtype=a.dtype), (*batch, m, m)).copy()
        for i in range(n):
            v = jnp.zeros((*batch, m), a.dtype).at[..., i].set(1.0)
            v = v.at[..., i + 1:].set(a[..., i + 1:, i])
            vv = jnp.einsum("...i,...j->...ij", v, v)
            h = jnp.eye(m, dtype=a.dtype) - t_[..., i, None, None] * vv
            q = q @ h
        return q[..., :n]
    return run_op("householder_product", fn, (x, tau))


def vander(x, n=None, increasing=False, name=None):
    return run_op("vander", lambda a: jnp.vander(a, N=n, increasing=increasing), (x,))


def vecdot(x, y, axis=-1, name=None):
    return run_op("vecdot", lambda a, b: jnp.sum(a * b, axis=axis), (x, y))


def _ax(axis):
    if axis is None:
        return None
    if isinstance(axis, (list, tuple)):
        return tuple(int(a) for a in axis)
    return int(axis)


def lu_unpack(x, y, unpack_ludata=True, unpack_pivots=True, name=None):
    """Unpack LU factorization (parity: paddle.linalg.lu_unpack over the
    `lu_unpack` kernel, reference python/paddle/tensor/linalg.py)."""
    def fn(lu_, piv):
        *batch, m, n = lu_.shape
        k = min(m, n)
        l_ = jnp.tril(lu_[..., :, :k], -1) + jnp.broadcast_to(
            jnp.eye(m, k, dtype=lu_.dtype), (*batch, m, k))
        u = jnp.triu(lu_[..., :k, :])
        # pivots are 1-based sequential row swaps -> permutation matrix
        piv0 = piv.astype(jnp.int32) - 1
        perm = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32),
                                (*batch, m))

        def body(i, pm):
            j = piv0[..., i]
            idx_i = jnp.full((*batch, 1), i, jnp.int32)
            vi = jnp.take_along_axis(pm, idx_i, axis=-1)
            vj = jnp.take_along_axis(pm, j[..., None], axis=-1)
            pm = jnp.put_along_axis(pm, idx_i, vj, axis=-1, inplace=False)
            pm = jnp.put_along_axis(pm, j[..., None], vi, axis=-1,
                                    inplace=False)
            return pm

        perm = jax.lax.fori_loop(0, piv.shape[-1], body, perm)
        # P[perm[i], i] = 1  (A = P L U with row swaps recorded in perm)
        p = jnp.swapaxes(jax.nn.one_hot(perm, m, dtype=lu_.dtype), -1, -2)
        return p, l_, u
    return run_op("lu_unpack", fn, (x, y))


def matrix_exp(x, name=None):
    return run_op("matrix_exp", jax.scipy.linalg.expm, (x,))


def pca_lowrank(x, q=None, center=True, niter=2, name=None):
    """Low-rank PCA via randomized SVD (parity: paddle.linalg.pca_lowrank).
    Composed from matmul/qr/svd ops so the small SVD takes the host
    fallback on TPU (see _svd_on_host)."""
    xt = x if isinstance(x, Tensor) else Tensor(x)
    m, n = xt.shape[-2], xt.shape[-1]
    k = q if q is not None else min(6, m, n)
    if center:
        from .math import mean, subtract
        b = subtract(xt, mean(xt, axis=-2, keepdim=True))
    else:
        b = xt
    omega = Tensor(jax.random.normal(jax.random.key(0),
                                     (*xt.shape[:-2], n, k), xt.dtype))
    y = matmul(b, omega)
    for _ in range(niter):
        y = matmul(b, matmul(b, y, transpose_x=True))
    qmat, _ = qr(y)
    bsmall = matmul(qmat, b, transpose_x=True)
    u_s, s, vh = svd(bsmall, full_matrices=False)
    u = matmul(qmat, u_s)
    from .manipulation import transpose as _tr
    perm = list(range(len(vh.shape)))
    perm[-1], perm[-2] = perm[-2], perm[-1]
    v = _tr(vh, perm)
    return u, s, v
