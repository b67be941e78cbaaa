"""Gradient compression for the slow cross-pod (DCN/ICI-hop) reduction.

fp32→bf16 with **error feedback**: the quantization residual is carried into
the next step's gradient, so the compression bias vanishes over time (the
standard EF-SGD construction).  ``compressed_allreduce`` performs the
cross-pod mean in bf16 inside ``shard_map`` — halving cross-pod collective
bytes, which is exactly the term that dominates the multi-pod roofline.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["error_feedback_compress", "compressed_allreduce"]


def error_feedback_compress(grads, error):
    """Quantize (grads + error) to bf16; return (compressed, new_error)."""
    def one(g, e):
        target = g.astype(jnp.float32) + e
        q = target.astype(jnp.bfloat16)
        return q, target - q.astype(jnp.float32)

    pairs = jax.tree.map(one, grads, error)
    comp = jax.tree.map(lambda t: t[0], pairs,
                        is_leaf=lambda t: isinstance(t, tuple))
    new_err = jax.tree.map(lambda t: t[1], pairs,
                           is_leaf=lambda t: isinstance(t, tuple))
    return comp, new_err


def compressed_allreduce(grads, error, mesh, axis: str = "pod"):
    """Mean-reduce ``grads`` over ``axis`` in bf16 with error feedback.

    Inside-pod reduction should already have happened (cheap ICI); this
    covers the expensive cross-pod hop.  Returns (reduced fp32, new_error).
    """
    comp, new_err = error_feedback_compress(grads, error)

    specs = jax.tree.map(lambda _: P(), comp)

    def reduce_fn(tree):
        return jax.tree.map(
            lambda g: (jax.lax.psum(g.astype(jnp.bfloat16), axis)
                       / mesh.shape[axis]).astype(jnp.float32), tree)

    mapped = shard_map(reduce_fn, mesh=mesh, in_specs=(specs,),
                       out_specs=specs, check_vma=False)
    reduced = mapped(comp)
    return reduced, new_err
