"""Step functions: training (with microbatch gradient accumulation) and
serving (prefill / decode).  These are the functions the launcher jits with
explicit in/out shardings.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..models.model_zoo import Model
from ..optim.adamw import AdamWState, adamw_update

__all__ = ["make_train_step", "make_prefill_step", "make_decode_step"]


def make_train_step(model: Model, *, num_microbatches: int = 1,
                    weight_decay: float = 0.1, clip_norm: float = 1.0,
                    b1: float = 0.9, b2: float = 0.95,
                    unroll: bool = False) -> Callable:
    """Returns train_step(params, opt_state, batch, lr) →
    (params, opt_state, metrics).

    With ``num_microbatches > 1`` the global batch is split along the batch
    axis and gradients accumulate in fp32 through a ``lax.scan`` — bounding
    activation memory to one microbatch (the standard large-model recipe).
    """

    grad_fn = jax.value_and_grad(model.loss, has_aux=True)

    def train_step(params, opt_state: AdamWState, batch: dict, lr):
        n = num_microbatches
        if n == 1:
            (_, metrics), grads = grad_fn(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                assert b % n == 0, (b, n)
                return x.reshape((n, b // n) + x.shape[1:])

            mbs = jax.tree.map(split, batch)
            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)

            def body(acc, mb):
                (_, met), g = grad_fn(params, mb)
                acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32) / n, acc, g)
                return acc, met

            grads, mets = jax.lax.scan(body, zeros, mbs,
                                       unroll=unroll)
            metrics = jax.tree.map(lambda m: m.mean(), mets)
        new_params, new_opt, om = adamw_update(
            grads, opt_state, params, lr, weight_decay=weight_decay,
            clip_norm=clip_norm, b1=b1, b2=b2)
        return new_params, new_opt, {**metrics, **om}

    return train_step


def make_prefill_step(model: Model, s_max: Optional[int] = None) -> Callable:
    def prefill_step(params, batch):
        return model.prefill(params, batch, s_max)
    return prefill_step


def make_decode_step(model: Model, *, sample: bool = False,
                     temperature: float = 1.0) -> Callable:
    """decode_step(params, token [B,1], cache, pos) →
    (next_token [B,1], logits, cache)."""

    def decode_step(params, token, cache, pos, rng=None):
        logits, cache = model.decode_step(params, token, cache, pos)
        if sample and rng is not None:
            nxt = jax.random.categorical(rng, logits[:, -1]
                                         / temperature)[:, None]
        else:
            nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        return nxt.astype(jnp.int32), logits, cache

    return decode_step
