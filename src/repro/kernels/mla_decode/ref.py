"""Pure-jnp oracle of the absorbed MLA decode kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["mla_decode_ref"]


def mla_decode_ref(q: jax.Array, lat: jax.Array, kv_valid: jax.Array, *,
                   rank: int, scale: float) -> jax.Array:
    """q: [B, H, W]; lat: [B, T, W]; kv_valid: [B] → [B, H, rank] (fp32)."""
    s = jnp.einsum("bhw,btw->bht", q.astype(jnp.float32),
                   lat.astype(jnp.float32)) * scale
    valid = jnp.arange(lat.shape[1])[None, None, :] < kv_valid[:, None, None]
    p = jax.nn.softmax(jnp.where(valid, s, -jnp.inf), axis=-1)
    return jnp.einsum("bht,btr->bhr", p, lat[..., :rank].astype(jnp.float32))
