"""The arithmetic a plain reference computes in, and its lower-precision control.

``Exact`` is float32 with every matrix multiplication at ``highest``
precision (on a TPU a float32 matmul otherwise runs in one bf16 pass).
``Fp8`` is the control of "How correct is decided": the same reference with
both operands of every matrix multiplication rounded to float8 e4m3 under a
per-tensor scale, the recipe a later PR would be tempted by. Nothing here
imports the program.

Rounding is ``lax.reduce_precision``, never ``astype`` there and back: XLA
removes such a pair of converts (``xla_allow_excess_precision``), and on the
chip the reference then silently keeps float32 (PR 24 read a gain of every
norm unmoved in the program and moved in the reference, a gap of 1, until
this was found).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def round_to(x, exponent_bits: int, mantissa_bits: int):
    """float32 values rounded to a narrower float format, kept in float32."""
    return jax.lax.reduce_precision(x, exponent_bits, mantissa_bits)


class Exact:
    name = "float32-highest"

    def operand(self, x):
        return x.astype(jnp.float32)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self.operand(a), self.operand(b),
                          precision=_HIGHEST,
                          preferred_element_type=jnp.float32)


class Fp8(Exact):
    name = "float8_e4m3-operands"

    def operand(self, x):
        x = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(jax.lax.stop_gradient(x)))
        # 4 exponent bits, 3 of mantissa; 240 is that format's largest
        # finite value when its top exponent is kept for infinities
        scale = jnp.where(amax > 0, 240.0 / amax, 1.0)
        q = round_to(x * scale, 4, 3)
        # straight-through: the backward pass sees the rounded operands of
        # its own matmuls, not a zero slope
        return x + jax.lax.stop_gradient(q / scale - x)


class Bf16(Exact):
    """Operands rounded to bfloat16: what the configurations state. Used by
    the benchmark's tests to show a reading between Exact and Fp8."""
    name = "bfloat16-operands"

    def operand(self, x):
        x = x.astype(jnp.float32)
        return x + jax.lax.stop_gradient(round_to(x, 8, 7) - x)
