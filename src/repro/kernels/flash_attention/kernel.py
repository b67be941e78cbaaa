"""Blocked online-softmax attention (flash attention) for TPU.

Grid (B, H, S/bq, T/bk): the kv-block dimension is innermost and sequential,
so the running max/denominator/accumulator live in VMEM scratch across kv
steps — the same carry-across-sequential-grid pattern as the prefix-scan
kernel.  GQA is handled in the K/V BlockSpec index maps (query head h reads
kv head h // group), causal + sliding-window masking by block-index
predicates, and fully-masked kv blocks are skipped with ``pl.when`` — for
SWA this turns the O(S·T) sweep into O(S·window) compute.

Masking knobs (all composable):

* ``q_offset`` — absolute position of query row 0.  ``0`` is the top-left
  causal convention (row i sees cols <= i); ``t - s`` gives the
  bottom-right alignment a chunked prefill over history needs.
* ``kv_len`` — static true (unpadded) kv length; padded columns beyond it
  are always masked.
* ``kv_valid`` — optional per-batch *dynamic* valid-kv count ``[B]``.  This
  is the single-token decode path over a partially-filled (or ring-wrapped)
  cache: slots ``>= kv_valid[b]`` are masked for that sequence only.  The
  counts are scalar-prefetched into SMEM (a ``[B]`` vector cannot be tiled
  into VMEM blocks one element wide).

Forward only: the training path uses XLA attention (or this kernel under
``jax.checkpoint`` recomputation); serving uses it directly — prefill via
the causal path, decode via ``causal=False`` + ``kv_valid``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import compiler_params, resolve_interpret

__all__ = ["flash_attention_pallas"]

_NEG = -1e30


def _kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, window, bq, bk, kv_len, q_offset):
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Block-level reachability (static in program ids → cheap skip).  Rows
    # are absolute query positions (local row + q_offset).
    q_lo = q_offset + qi * bq
    q_hi = q_lo + bq - 1
    k_lo = ki * bk
    k_hi = k_lo + bk - 1
    live = jnp.bool_(True)
    if causal:
        live &= k_lo <= q_hi
    if window is not None:
        live &= q_lo - k_hi < window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)            # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols < jnp.minimum(kv_len, valid_ref[bi])
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= rows - cols < window
        s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_prev * alpha + p.sum(axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot(p, v,
                                      preferred_element_type=jnp.float32))
        m_ref[:, 0] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "bq", "bk", "interpret",
                                             "kv_len", "q_offset"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           kv_valid: Optional[jax.Array] = None, *,
                           causal: bool = True, window: Optional[int] = None,
                           scale: Optional[float] = None, bq: int = 128,
                           bk: int = 128, interpret: Optional[bool] = None,
                           kv_len: Optional[int] = None,
                           q_offset: int = 0) -> jax.Array:
    """q: [B, H, S, d]; k, v: [B, Hkv, T, d] with H % Hkv == 0.
    S % bq == 0 and T % bk == 0 (ops wrapper pads; ``kv_len`` = true,
    unpadded T so padded columns are masked out).  ``kv_valid``: optional
    [B] int32 per-batch valid kv count (decode over a partial cache).
    Returns [B, H, S, d]."""
    b, h, s, d = q.shape
    _, hkv, t, _ = k.shape
    assert h % hkv == 0 and s % bq == 0 and t % bk == 0
    group = h // hkv
    if scale is None:
        scale = d ** -0.5
    if kv_valid is None:
        kv_valid = jnp.full((b,), t, jnp.int32)
    # index maps receive the scalar-prefetch ref as a trailing argument
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, s // bq, t // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, h_, qi, ki, _: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, qi, ki, _: (b_, h_ // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, qi, ki, _: (b_, h_ // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h_, qi, ki, _: (b_, h_, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ])
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk,
                          kv_len=kv_len or t, q_offset=q_offset),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(kv_valid.astype(jnp.int32), q, k, v)
