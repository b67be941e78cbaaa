"""Jitted public wrapper: shape normalization + padding for the kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import prefix_scan_pallas

__all__ = ["prefix_scan"]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def prefix_scan(x: jax.Array, *, block: int = 256,
                interpret: Optional[bool] = None) -> jax.Array:
    """Inclusive prefix sum along the last axis; any rank ≥ 1; pads the
    last axis to a block multiple and the rows to a multiple of 8
    internally."""
    shape = x.shape
    n = shape[-1]
    x2 = x.reshape(-1, n)
    rows = x2.shape[0]
    pad_r, pad = (-rows) % 8, (-n) % block
    if pad_r or pad:
        x2 = jnp.pad(x2, ((0, pad_r), (0, pad)))
    y = prefix_scan_pallas(x2, block=block, interpret=interpret)
    return y[:rows, :n].reshape(shape)
