"""Convolution functionals (parity: python/paddle/nn/functional/conv.py).
All lower to lax.conv_general_dilated — XLA maps these onto the MXU; there
is no cuDNN-style algorithm search because the compiler owns scheduling
(the reference's cache of searched conv algorithms is subsumed)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import run_op

__all__ = ["conv1d", "conv2d", "conv3d", "conv1d_transpose",
           "conv2d_transpose", "conv3d_transpose"]


def _norm_tuple(v, n):
    if isinstance(v, (list, tuple)):
        out = list(v)
        if len(out) == 1:
            out = out * n
        return tuple(int(x) for x in out)
    return (int(v),) * n


def _norm_padding(padding, n):
    if isinstance(padding, str):
        return padding.upper()  # SAME / VALID
    if isinstance(padding, (list, tuple)):
        flat = list(padding)
        if len(flat) == n and all(isinstance(p, (list, tuple)) for p in flat):
            return [tuple(p) for p in flat]
        if len(flat) == n:
            return [(int(p), int(p)) for p in flat]
        if len(flat) == 2 * n:
            return [(int(flat[2 * i]), int(flat[2 * i + 1])) for i in range(n)]
    return [(int(padding), int(padding))] * n


def _conv(name, ndim, x, weight, bias, stride, padding, dilation, groups,
          data_format):
    n = ndim
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    pad = _norm_padding(padding, n)
    spatial = "DHW"[-n:] if n == 3 else ("HW" if n == 2 else "W")
    cf = data_format.startswith("NC")
    lhs_spec = "NC" + spatial if cf else "N" + spatial + "C"
    out_spec = lhs_spec
    rhs_spec = "OI" + spatial
    dn = jax.lax.conv_dimension_numbers(
        (1,) * (n + 2), (1,) * (n + 2), (lhs_spec, rhs_spec, out_spec))

    def fn(a, w, *b):
        out = jax.lax.conv_general_dilated(
            a, w, window_strides=stride, padding=pad,
            rhs_dilation=dilation, dimension_numbers=dn,
            feature_group_count=groups,
            preferred_element_type=jnp.float32 if a.dtype == jnp.bfloat16 else None)
        out = out.astype(a.dtype)
        if b:
            shape = [1] * out.ndim
            shape[1 if cf else -1] = b[0].shape[0]
            out = out + b[0].reshape(shape).astype(out.dtype)
        return out
    ops = (x, weight) + ((bias,) if bias is not None else ())
    return run_op(name, fn, ops)


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    df = "NCW" if data_format in ("NCL", "NCW") else "NWC"
    return _conv("conv1d", 1, x, weight, bias, stride, padding, dilation,
                 groups, df)


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    return _conv("conv2d", 2, x, weight, bias, stride, padding, dilation,
                 groups, data_format)


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    return _conv("conv3d", 3, x, weight, bias, stride, padding, dilation,
                 groups, data_format)


def _conv_transpose(name, ndim, x, weight, bias, stride, padding,
                    output_padding, dilation, groups, data_format, output_size):
    n = ndim
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    pad = _norm_padding(padding, n)
    opad = _norm_tuple(output_padding, n)
    spatial = "DHW"[-n:] if n == 3 else ("HW" if n == 2 else "W")
    cf = data_format.startswith("NC")
    lhs_spec = "NC" + spatial if cf else "N" + spatial + "C"
    rhs_spec = "IO" + spatial  # paddle transpose-conv weight: [in, out/groups, *k]
    dn = (lhs_spec, rhs_spec, lhs_spec)

    def fn(a, w, *b):
        if isinstance(pad, str):
            tpad = pad
        else:
            # standard transpose-conv padding transformation
            k = w.shape[2:]
            tpad = [(dilation[i] * (k[i] - 1) - pad[i][0],
                     dilation[i] * (k[i] - 1) - pad[i][1] + opad[i])
                    for i in range(n)]
        out = jax.lax.conv_general_dilated(
            a, w, window_strides=(1,) * n, padding=tpad,
            lhs_dilation=stride, rhs_dilation=dilation,
            dimension_numbers=jax.lax.conv_dimension_numbers(
                a.shape, w.shape, dn),
            feature_group_count=groups,
            transpose_kernel=False)
        out = out.astype(a.dtype)
        if b:
            shape = [1] * out.ndim
            shape[1 if cf else -1] = b[0].shape[0]
            out = out + b[0].reshape(shape).astype(out.dtype)
        return out

    # IO spec expects weight [in, out, *k]; flip spatial dims for true
    # transposed conv semantics
    def fn_flipped(a, w, *b):
        w = jnp.flip(w, axis=tuple(range(2, w.ndim)))
        return fn(a, w, *b)

    ops = (x, weight) + ((bias,) if bias is not None else ())
    return run_op(name, fn_flipped, ops)


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCL", name=None):
    df = "NCW" if data_format in ("NCL", "NCW") else "NWC"
    return _conv_transpose("conv1d_transpose", 1, x, weight, bias, stride,
                           padding, output_padding, dilation, groups, df,
                           output_size)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCHW", name=None):
    return _conv_transpose("conv2d_transpose", 2, x, weight, bias, stride,
                           padding, output_padding, dilation, groups,
                           data_format, output_size)


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCDHW", name=None):
    return _conv_transpose("conv3d_transpose", 3, x, weight, bias, stride,
                           padding, output_padding, dilation, groups,
                           data_format, output_size)
