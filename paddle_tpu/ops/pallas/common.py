"""Shared helpers for the Pallas TPU kernels.

Mosaic constraints handled here:
- index-map constants must be i32 — the package runs with jax_enable_x64
  on, and Mosaic cannot legalize the i64 values the tracer would produce
  for bare Python ints;
- per-row scalars (lse, labels, norm stats) ride as trailing-unit
  (rows, 1) refs — rank-1 blocks that are neither full-dim nor a
  128-multiple are rejected on hardware;
- interpret-mode selection lives in ONE place (:func:`pallas_interpret`)
  so every kernel agrees on what "not on TPU" means (GL906).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_Z = np.int32(0)


def on_tpu() -> bool:
    """True when the default backend is a real TPU."""
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """Whether pallas_call should run in interpret mode: the single
    source of truth every kernel's ``interpret=`` routes through."""
    return not on_tpu()


def pad_rows(a, br):
    """Pad the leading (row) dim of `a` up to a multiple of `br`."""
    pad = (-a.shape[0]) % br
    if pad:
        cfg = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        a = jnp.pad(a, cfg)
    return a
