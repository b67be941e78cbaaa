"""Shared building blocks: RMSNorm, linear, RoPE, SwiGLU MLP, embeddings.

Parameters are plain pytrees (nested dicts of jnp arrays); every module is an
``init_*``/apply pair.  Compute happens in ``cfg.dtype`` (bf16 on TPU);
normalization statistics in fp32.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig

__all__ = ["dtype_of", "init_linear", "linear", "init_rms_norm", "rms_norm",
           "init_embedding", "embed", "rope_freqs", "apply_rope",
           "yarn_inv_freq", "yarn_mscale", "apply_rope_inv",
           "init_mlp", "mlp", "init_group_norm", "group_norm"]


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _he(key, shape, dtype, fan_in: Optional[int] = None):
    fan = fan_in if fan_in is not None else shape[0]
    return (jax.random.normal(key, shape) / jnp.sqrt(fan)).astype(dtype)


def init_linear(key, d_in: int, d_out: int, *, bias: bool = False,
                dtype=jnp.bfloat16) -> dict:
    p = {"w": _he(key, (d_in, d_out), dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: dict, x: jax.Array) -> jax.Array:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def init_rms_norm(d: int, dtype=jnp.bfloat16) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * p["scale"]


def init_group_norm(num_groups: int, d: int, dtype=jnp.bfloat16) -> dict:
    del num_groups  # static: callers pass it to group_norm (not a param)
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def group_norm(p: dict, x: jax.Array, groups: int,
               eps: float = 1e-5) -> jax.Array:
    """GroupNorm over the last dim split into ``groups`` groups."""
    g = groups
    shape = x.shape
    xf = x.astype(jnp.float32).reshape(*shape[:-1], g, shape[-1] // g)
    mean = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    xf = (xf - mean) * jax.lax.rsqrt(var + eps)
    return xf.reshape(shape).astype(x.dtype) * p["scale"] + p["bias"]


def init_embedding(key, vocab: int, d: int, dtype=jnp.bfloat16) -> dict:
    return {"table": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


def embed(p: dict, tokens: jax.Array, onehot: bool = False) -> jax.Array:
    if onehot:
        # matmul-based lookup: partitions cleanly when the table's vocab dim
        # is sharded (gather would force a replication fallback in SPMD)
        oh = jax.nn.one_hot(tokens, p["table"].shape[0],
                            dtype=p["table"].dtype)
        return oh @ p["table"]
    return p["table"][tokens]


def unembed(p: dict, x: jax.Array) -> jax.Array:
    return x @ p["table"].T


# -- rotary ------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    return inv  # [head_dim/2]


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, hd]; positions: broadcastable to [..., S]."""
    if theta <= 0:
        return x
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)
    ang = positions[..., None].astype(jnp.float32) * inv   # [..., S, hd/2]
    cos = jnp.cos(ang)[..., None, :]                       # [..., S, 1, hd/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max_position: int, beta_fast: float,
                  beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies of a ``dim``-wide rotary embedding (the
    DeepSeek-V2 form): dimensions that turn more than ``beta_fast`` times
    over the original context keep their frequency, those that turn fewer
    than ``beta_slow`` times are slowed by ``factor``, and a linear ramp
    blends the ones between.  ``factor <= 1`` is plain RoPE."""
    base = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra = 1.0 / base
    if factor <= 1:
        return extra

    def turns_dim(turns):
        return dim * math.log(original_max_position / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return extra / factor * (1.0 - keep) + extra * keep


def apply_rope_inv(x: jax.Array, positions: jax.Array, inv,
                   scale: float = 1.0) -> jax.Array:
    """Rotate-half RoPE with given inverse frequencies ``inv`` [hd/2] and
    cos/sin scaled by ``scale``.  x: [..., S, H, hd]; positions
    broadcastable to [..., S]."""
    inv = jnp.asarray(inv, jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * inv
    cos = (jnp.cos(ang) * scale)[..., None, :]
    sin = (jnp.sin(ang) * scale)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# -- SwiGLU MLP ---------------------------------------------------------------

def init_mlp(key, d: int, d_ff: int, dtype=jnp.bfloat16) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"gate": init_linear(k1, d, d_ff, dtype=dtype),
            "up": init_linear(k2, d, d_ff, dtype=dtype),
            "down": init_linear(k3, d_ff, d, dtype=dtype)}


def mlp(p: dict, x: jax.Array) -> jax.Array:
    return linear(p["down"], jax.nn.silu(linear(p["gate"], x))
                  * linear(p["up"], x))
