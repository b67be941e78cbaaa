"""Single-token GQA decode attention (flash decode) for TPU.

One query token per sequence attends over the sequence's logical KV view
at a per-sequence valid count ``kv_valid`` (decode over a partially filled
or ring-wrapped cache).  The generic kernel (``kernel.py``) is built for
prefill's ``bq x bk`` causal tiles; decode wants the opposite shape, so it
has this kernel of its own:

* Grid ``(B, T/bk)``: one step loads one ``bk``-token tile of K and of V
  for **all** KV heads and scores each head's keys against the ``group``
  query rows that share it (q laid out ``[B, kvH, group, hd]``).  Every
  live tile is read once for the whole batch row.
* K and V arrive as ``[B, T*kvH, hd]``, row ``t*kvH + g`` holding token
  ``t``'s head ``g``: a reshape of the model's ``[B, T, kvH, hd]`` that
  keeps the cache's tiled memory layout, so XLA passes the gathered view
  without a copy (``[B, T, kvH*hd]`` would need a relayout of the whole
  view).  The kernel picks a head's rows with a strided read.
* The online-softmax carry (max, denominator, accumulator per query row)
  lives in VMEM scratch across the sequential tile axis.
* Tiles past a row's valid count are neither fetched nor computed: their
  index map repeats the last live tile, so the pipeline starts no copy, and
  the body runs under ``pl.when``.

Scores, softmax and the accumulator are float32.  q.k feeds the MXU the
operands' own type (bf16 products are exact in float32); p stays float32
for p.V.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import compiler_params, resolve_interpret

__all__ = ["flash_attention_pallas_decode", "decode_tile", "decode_kv_dtype"]

_NEG = -1e30
#: widest tile, in tokens, and the most bytes one K tile may take
_MAX_TILE = 512
_MAX_TILE_BYTES = 1 << 20


def decode_tile(cap: int, kv_width: int, itemsize: int = 2) -> int:
    """Tokens per KV tile of the decode kernel over a ``cap``-token view of
    ``kv_width`` (= kvH * hd) values per token: 512, cut to the ring
    rounded up to a power of two for short rings, and halved while one K
    tile would take more than 1 MiB."""
    bk = min(_MAX_TILE, max(16, 1 << (cap - 1).bit_length()))
    while bk > 16 and bk * kv_width * itemsize > _MAX_TILE_BYTES:
        bk //= 2
    return bk


def decode_kv_dtype(kvh: int, hd: int, dtype) -> jnp.dtype:
    """The type the kernel reads K and V in: their own, except several
    16-bit heads that cannot be read as bfloat16 pairs of whole lane rows
    (another 16-bit type, kvH odd, or hd not a multiple of 128), which
    widen to float32 (exact)."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize == 2 and kvh > 1 and (
            dtype != jnp.bfloat16 or kvh % 2 or hd % 128):
        return jnp.dtype(jnp.float32)
    return dtype


def _head_rows(ref, g, kvh, bk):
    """Head ``g``'s ``bk`` keys or values from a tile whose row ``t*kvh + g``
    is token ``t``'s head ``g``.  Two bfloat16 heads of a token share one
    32-bit word (rows ``2i``, ``2i+1`` are its low and high halves), so
    bfloat16 tiles are read as words and the head's half is widened to
    float32, which is exact."""
    if ref.dtype.itemsize == 4 or kvh == 1:
        return ref[0, pl.ds(g, bk, stride=kvh), :]
    words = ref.bitcast(jnp.uint32)[0, pl.ds(g // 2, bk, stride=kvh // 2), :]
    bits = words << 16 if g % 2 == 0 else words & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, bk):
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    valid = valid_ref[bi]
    kvh = q_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ki * bk < valid)
    def _compute():
        dt = jnp.promote_types(q_ref.dtype, k_ref.dtype)
        for g in range(kvh):
            q = q_ref[0, g].astype(dt)                           # [group, hd]
            k = _head_rows(k_ref, g, kvh, bk).astype(dt)         # [bk, hd]
            v = _head_rows(v_ref, g, kvh, bk).astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = cols < valid
            s = jnp.where(mask, s, _NEG)
            m_prev = m_ref[g, :, 0]
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[g, :, 0] = l_ref[g, :, 0] * alpha + p.sum(axis=-1)
            acc_ref[g] = acc_ref[g] * alpha[:, None] + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[g, :, 0] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bk", "interpret"))
def flash_attention_pallas_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                                  kv_valid: jax.Array, *, scale: float,
                                  bk: int,
                                  interpret: Optional[bool] = None
                                  ) -> jax.Array:
    """q: [B, kvH, group, hd]; k, v: [B, T*kvH, hd], row ``t*kvH + g``
    token ``t``'s head ``g`` (a free reshape of ``[B, T, kvH, hd]``), with
    T % bk == 0, in ``decode_kv_dtype``'s type.  kv_valid: [B] int32
    valid kv count per sequence, at most T.  Returns [B, kvH, group, hd] in
    q's type."""
    b, kvh, group, hd = q.shape
    t = k.shape[1] // kvh
    assert k.shape == (b, t * kvh, hd) and t % bk == 0, (q.shape, k.shape, bk)
    assert decode_kv_dtype(kvh, hd, k.dtype) == k.dtype, (k.dtype, kvh, hd)

    def kv_block(b_, ki, valid):
        last = jnp.maximum(valid[b_] - 1, 0) // bk
        return (b_, jnp.minimum(ki, last), 0)

    def row_block(b_, ki, _):
        return (b_, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, t // bk),
        in_specs=[pl.BlockSpec((1, kvh, group, hd), row_block),
                  pl.BlockSpec((1, bk * kvh, hd), kv_block),
                  pl.BlockSpec((1, bk * kvh, hd), kv_block)],
        out_specs=pl.BlockSpec((1, kvh, group, hd), row_block),
        scratch_shapes=[pltpu.VMEM((kvh, group, hd), jnp.float32),
                        pltpu.VMEM((kvh, group, 1), jnp.float32),
                        pltpu.VMEM((kvh, group, 1), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, bk=bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(kv_valid.astype(jnp.int32), q, k, v)
