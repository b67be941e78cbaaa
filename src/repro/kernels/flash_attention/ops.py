"""Public wrappers: model layout [B, S, H, d] in/out, padding, GQA.

``flash_attention`` is the name the model/serving layer imports for
prefill and training; the raw grid kernel is
``kernel.flash_attention_pallas`` (kernel-layout [B, H, S, d]).  See the
kernel docstring for the masking knobs (``q_offset`` for s≠t causal
alignment, ``kv_valid`` for decode over a partially-filled cache).
``flash_decode`` is single-token decode through
``decode.flash_attention_pallas_decode``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .decode import (decode_kv_dtype, decode_tile,
                     flash_attention_pallas_decode)
from .kernel import flash_attention_pallas

__all__ = ["flash_attention", "flash_decode"]


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "interpret", "bq", "bk",
                                             "q_offset"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    kv_valid: Optional[jax.Array] = None, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None, bq: int = 128,
                    bk: int = 128, interpret: Optional[bool] = None,
                    q_offset: int = 0) -> jax.Array:
    """q: [B, S, H, d]; k, v: [B, T, Hkv, d] → [B, S, H, d].

    ``kv_valid``: optional [B] int32 per-sequence count of valid kv
    positions (single-token decode over a shared cache at mixed depths).
    ``q_offset``: absolute position of query row 0 for causal/window masks
    (``t - s`` = bottom-right alignment for chunked prefill)."""
    b, s, h, d = q.shape
    t = k.shape[1]
    bq = min(bq, max(8, 1 << (s - 1).bit_length()))
    bk = min(bk, max(8, 1 << (t - 1).bit_length()))
    pad_q = (-s) % bq
    pad_k = (-t) % bk
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    # kv_len masking inside the kernel ignores padded columns
    out = flash_attention_pallas(qt, kt, vt, kv_valid, causal=causal,
                                 window=window, scale=scale, bq=bq, bk=bk,
                                 interpret=interpret, kv_len=t,
                                 q_offset=q_offset)
    out = out[:, :, :s]
    return jnp.moveaxis(out, 1, 2)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 kv_valid: jax.Array, *, scale: Optional[float] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """q: [B, 1, H, d]; k, v: [B, T, Hkv, d]; kv_valid: [B] int32 valid kv
    positions per sequence → [B, 1, H, d].  Query head ``h`` reads kv head
    ``h // (H // Hkv)``; positions ``>= kv_valid[b]`` are masked (and the
    kernel fetches none of their tiles)."""
    b, _, h, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    dt = decode_kv_dtype(hkv, d, k.dtype)
    k, v = k.astype(dt), v.astype(dt)
    bk = decode_tile(t, hkv * d, dt.itemsize)
    pad = (-t) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # free reshapes: row t*Hkv + g is token t's head g, as the cache lays
    # it out
    tp = t + pad
    out = flash_attention_pallas_decode(
        q.reshape(b, hkv, h // hkv, d), k.reshape(b, tp * hkv, d),
        v.reshape(b, tp * hkv, d), jnp.minimum(kv_valid, t).astype(jnp.int32),
        scale=d ** -0.5 if scale is None else scale, bk=bk,
        interpret=interpret)
    return out.reshape(b, 1, h, d)
