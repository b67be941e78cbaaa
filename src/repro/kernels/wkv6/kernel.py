"""RWKV-6 WKV recurrence kernel (data-dependent decay linear attention).

Grid (B, H, T/c) with the time-chunk dimension innermost and sequential; the
[N, N] per-head state lives in VMEM scratch and is carried across chunks —
the HBM traffic is O(T·N) for the ios instead of O(T·N²) for materialized
states, and the sequential chunk walk is the same carry pattern as the
prefix-scan kernel (the paper's one-pass strategy).  Within a chunk the
recurrence is stepped with a ``fori_loop`` over VPU outer-products (the MXU
has no use here: the state update is rank-1); the column operands of those
products are built by a diagonal mask-and-reduce, not a transpose.

Forward only (serving/prefill path; training uses the chunked associative
scan in ``models/ssm.py``, which this kernel is verified against).
Takes an optional initial state ``s0`` (decode → re-prefill hand-off) and
emits y plus the final state (prefill → decode hand-off).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import compiler_params, resolve_interpret

__all__ = ["wkv6_pallas"]


def _column(row: jax.Array, eye: jax.Array) -> jax.Array:
    """[1, N] row -> [N, 1] column without a transpose: mask the broadcast
    row to its diagonal and reduce over lanes (exact, VPU/XLU only)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, s_out_ref,
            s_ref, *, chunk):
    ti = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(ti == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0]

    n = s_ref.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    u = u_ref[0]                                      # [1, N]

    def step(i, s):                                   # s: [N(k), N(v)]
        row = pl.ds(i, 1)
        ri = r_ref[0, 0, row, :]                      # [1, N]
        ki = k_ref[0, 0, row, :]
        vi = v_ref[0, 0, row, :]
        wi = w_ref[0, 0, row, :]
        bonus = jnp.sum(ri * u * ki, axis=1, keepdims=True)       # [1, 1]
        y_ref[0, 0, row, :] = (jnp.sum(_column(ri, eye) * s, axis=0,
                                       keepdims=True) + bonus * vi)
        return _column(wi, eye) * s + _column(ki, eye) * vi

    s_ref[...] = jax.lax.fori_loop(0, chunk, step, s_ref[...])

    @pl.when(ti == nt - 1)
    def _flush_state():
        s_out_ref[0, 0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                u: jax.Array, s0: Optional[jax.Array] = None, *,
                chunk: int = 64, interpret: Optional[bool] = None):
    """r, k, v: [B, T, H, N]; w: [B, T, H, N] decay in (0,1); u: [H, N];
    s0: optional [B, H, N, N] initial state (zeros when omitted).
    Returns (y [B, T, H, N], s_end [B, H, N, N])."""
    b, t, h, n = r.shape
    assert t % chunk == 0, (t, chunk)
    if s0 is None:
        s0 = jnp.zeros((b, h, n, n), jnp.float32)

    # kernel layout [B, H, T, N] in fp32: a block's last two dims are then
    # (chunk, N), which the TPU tiles; (1, N) slices of [B, T, H, N] it
    # cannot.  Rows are read one step at a time, which fp32 keeps unpacked.
    def heads_major(a):
        return jnp.swapaxes(a, 1, 2).astype(jnp.float32)

    grid = (b, h, t // chunk)
    io_spec = pl.BlockSpec((1, 1, chunk, n),
                           lambda b_, h_, ti: (b_, h_, ti, 0))
    state_spec = pl.BlockSpec((1, 1, n, n),
                              lambda b_, h_, ti: (b_, h_, 0, 0))
    y, s_end = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[io_spec, io_spec, io_spec, io_spec,
                  pl.BlockSpec((1, 1, n), lambda b_, h_, ti: (h_, 0, 0)),
                  state_spec],
        out_specs=[io_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, n), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, n, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(heads_major(r), heads_major(k), heads_major(v), heads_major(w),
      u.astype(jnp.float32).reshape(h, 1, n), s0.astype(jnp.float32))
    return jnp.swapaxes(y, 1, 2).astype(r.dtype), s_end
