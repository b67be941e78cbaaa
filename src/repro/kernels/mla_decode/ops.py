"""Public wrapper: pads the latent view to whole kv blocks."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import mla_decode_pallas

__all__ = ["mla_decode"]


@functools.partial(jax.jit, static_argnames=("rank", "scale", "bk",
                                             "interpret"))
def mla_decode(q: jax.Array, lat: jax.Array, kv_valid: jax.Array, *,
               rank: int, scale: float, bk: int = 512,
               interpret: Optional[bool] = None) -> jax.Array:
    """q: [B, H, W]; lat: [B, T, W]; kv_valid: [B] → [B, H, rank].  Padded
    latents lie past every sequence's valid count, so they are masked."""
    t = lat.shape[1]
    bk = min(bk, max(8, 1 << (t - 1).bit_length()))
    pad = (-t) % bk
    if pad:
        lat = jnp.pad(lat, ((0, 0), (0, pad), (0, 0)))
    return mla_decode_pallas(q, lat, kv_valid, rank=rank, scale=scale,
                             bk=bk, interpret=interpret)
