"""Absorbed latent-attention (MLA) decode for TPU.

One query row per sequence and head, ``[B, H, W]`` with ``W`` the cached
width (latent ``rank`` values plus the rotary key), attends over the
sequence's logical latent view ``[B, T, W]``; the values are each latent's
first ``rank`` entries.  All heads share the one cached "head", so the grid
is ``(B, T/bk)``: every latent block is read once for all H query rows
(the ``[H, W] x [W, bk]`` score matmul), and the online-softmax carry
(max, denominator, ``[H, rank]`` accumulator) lives in VMEM scratch across
the sequential kv axis.

Blocks past a sequence's valid count (``kv_valid``, scalar-prefetched into
SMEM) are neither computed nor fetched: their index map repeats the last
valid block, so the pipeline issues no new copy.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import compiler_params, resolve_interpret

__all__ = ["mla_decode_pallas"]

_NEG = -1e30


def _kernel(valid_ref, q_ref, lat_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, rank, bk):
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    valid = valid_ref[bi]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ki * bk < valid)
    def _compute():
        q = q_ref[0]                                    # [H, W]
        lat = lat_ref[0]                                # [bk, W]
        s = jax.lax.dot_general(q, lat, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = cols < valid
        s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p.astype(lat.dtype), lat[:, :rank],
            preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _flush():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "bk",
                                             "interpret"))
def mla_decode_pallas(q: jax.Array, lat: jax.Array, kv_valid: jax.Array, *,
                      rank: int, scale: float, bk: int = 512,
                      interpret: Optional[bool] = None) -> jax.Array:
    """q: [B, H, W]; lat: [B, T, W] with T % bk == 0; kv_valid: [B] int32
    valid latents per sequence.  Returns [B, H, rank] in q's type."""
    b, h, w = q.shape
    t = lat.shape[1]
    assert t % bk == 0, (t, bk)

    def lat_block(b_, ki, valid):
        last = jnp.maximum(valid[b_] - 1, 0) // bk
        return (b_, jnp.minimum(ki, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, t // bk),
        in_specs=[pl.BlockSpec((1, h, w), lambda b_, ki, _: (b_, 0, 0)),
                  pl.BlockSpec((1, bk, w), lat_block)],
        out_specs=pl.BlockSpec((1, h, rank), lambda b_, ki, _: (b_, 0, 0)),
        scratch_shapes=[pltpu.VMEM((h, rank), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, rank=rank, bk=bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(kv_valid.astype(jnp.int32), q, lat)
