"""One-pass blocked prefix scan — the paper's prefix-sum strategy made
structural on TPU.

The paper's insight: if blocks are processed in order by one place, the
previous block's total can be folded in during the single pass, eliminating
the scan-of-block-sums and fix-up passes.  On a TPU core the Pallas grid's
innermost dimension executes **sequentially**, so "some place processes
blocks in order" is guaranteed by construction: a carry cell in VMEM scratch
survives across grid steps and plays the role of the paper's global counter
+ running total.  One pass, no extra kernel launches, 2× less HBM traffic
than the 3-pass parallel algorithm.

Grid: (rows // 8, N // block).  The row dimension may be split across TPU cores
(parallel); the block dimension is sequential per row, and the carry is
reset at block 0 of each row.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import compiler_params, resolve_interpret

__all__ = ["prefix_scan_pallas"]

#: rows per tile (the TPU's sublane count)
_ROWS = 8


def _tile_scan(x: jax.Array) -> jax.Array:
    """Inclusive scan along the lanes of one tile in log2(width) steps of
    shift-and-add (Hillis-Steele); Mosaic has no cumsum lowering."""
    width = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    shift = 1
    while shift < width:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, x.ndim - 1),
                          jnp.zeros_like(x))
        shift *= 2
    return x


def _kernel(x_ref, o_ref, carry_ref, *, acc_dtype):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _reset():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    seg = _tile_scan(x_ref[...].astype(acc_dtype))
    o_ref[...] = (seg + carry_ref[...]).astype(o_ref.dtype)
    carry_ref[...] = carry_ref[...] + seg[:, -1:]


@functools.partial(jax.jit,
                   static_argnames=("block", "interpret", "acc_dtype"))
def prefix_scan_pallas(x: jax.Array, *, block: int = 256,
                       interpret: Optional[bool] = None,
                       acc_dtype=None) -> jax.Array:
    """Inclusive prefix sum along the last axis of a 2-D array.

    x: [R, N] with N % block == 0 and R % 8 == 0 (the ops wrapper pads):
    each grid step scans an (8, block) tile, the smallest the TPU tiles.
    """
    r, n = x.shape
    assert n % block == 0 and r % _ROWS == 0, (r, n, block)
    if acc_dtype is None:
        acc_dtype = (jnp.float32 if jnp.issubdtype(x.dtype, jnp.floating)
                     else jnp.int32)
    grid = (r // _ROWS, n // block)
    return pl.pallas_call(
        functools.partial(_kernel, acc_dtype=acc_dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((_ROWS, block), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((_ROWS, block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((_ROWS, 1), acc_dtype)],
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(x)
