"""Strategy-scheduled MoE token dispatch.

The paper's decision procedures, applied to the per-token routing problem of
a Mixture-of-Experts layer (tokens = tasks, experts = places):

* **priority** — under capacity pressure, an expert keeps the tokens with the
  highest router probability (the strategy's priority), not the
  first-arrived ones (the oblivious baseline, ``policy="arrival"``).
* **dead tasks** — assignments beyond capacity are *dropped before compute*
  (never "stolen" into the expert buffer), and their probability mass is
  excised from the combine weights.
* **steal (second choice)** — with ``resteal=True`` dropped assignments are
  re-routed to the token's next-best expert where spare capacity remains:
  idle places steal work the busy place had to shed.  Implemented as ONE
  extra priority-dispatch pass in which already-kept assignments carry +inf
  priority (they were within capacity, so they stay put).

Everything is static-shape / jit-safe: sort-based segment positioning, no
data-dependent control flow.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

__all__ = ["route_topk", "priority_dispatch", "gather_expert_inputs",
           "combine_expert_outputs", "DispatchPlan"]


class DispatchPlan(NamedTuple):
    """Static-shape dispatch decision for T tokens × k choices → E experts of
    capacity C."""
    slot_src: jax.Array      # [E, C] int32: flat assignment index (t*k+slot), or -1
    kept: jax.Array          # [T, k] bool: assignment survived capacity
    expert: jax.Array        # [T, k] int32: expert finally serving the assignment
    gate: jax.Array          # [T, k] f32: combine weight (0 where dropped)
    load: jax.Array          # [E] int32: tokens per expert (≤ C)
    dropped_mass: jax.Array  # [] f32: router prob mass lost to drops


def route_topk(logits: jax.Array, k: int, *, renormalize: bool = True):
    """Top-k routing. Returns (expert_idx [T,k], gate [T,k], full_probs [T,E])."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, k)
    if renormalize:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return expert_idx.astype(jnp.int32), gate, probs


def _dispatch_once(e: jax.Array, prio: jax.Array, num_experts: int,
                   capacity: int):
    """Sort-based segment dispatch.  e: [A] expert ids, prio: [A] priority
    (higher first).  Returns (pos [A] position-within-expert, keep [A])."""
    a = e.shape[0]
    # lexsort: primary key experts ascending, secondary priority descending.
    # Routing decisions are not differentiated (gradients flow through the
    # combine gates only), so cut the tangent before the sort.  An id of
    # ``num_experts`` (an expert another share holds) sorts last and is
    # never kept.
    order = jnp.lexsort((-jax.lax.stop_gradient(prio), e))
    e_sorted = e[order]
    seg_start = jnp.searchsorted(e_sorted, jnp.arange(num_experts + 1),
                                 side="left")
    pos_sorted = jnp.arange(a, dtype=jnp.int32) - seg_start[e_sorted].astype(jnp.int32)
    keep_sorted = (pos_sorted < capacity) & (e_sorted < num_experts)
    pos = jnp.zeros(a, jnp.int32).at[order].set(pos_sorted)
    keep = jnp.zeros(a, bool).at[order].set(keep_sorted)
    return pos, keep


@functools.partial(jax.jit,
                   static_argnames=("num_experts", "capacity", "policy",
                                    "resteal"))
def priority_dispatch(expert_idx: jax.Array, gate: jax.Array,
                      full_probs: jax.Array, *, num_experts: int,
                      capacity: int, policy: str = "priority",
                      resteal: bool = False,
                      held: Optional[jax.Array] = None) -> DispatchPlan:
    """Build the dispatch plan for [T, k] routed assignments.

    policy="priority": strategy scheduling — highest router prob survives.
    policy="arrival":  oblivious baseline — first-come-first-served (token
                       order), the moral equivalent of LIFO/FIFO.
    resteal=True:      dropped assignments take the token's next-best expert
                       with spare capacity (one extra pass).
    held:              [T, k] bool — for a layer that holds a share of the
                       experts: ``expert_idx`` counts within the share, and
                       an assignment to an expert outside it is another
                       chip's work: never kept, not counted as dropped.
    """
    t, k = expert_idx.shape
    a = t * k
    e = expert_idx.reshape(a)
    g = gate.reshape(a)
    mine = jnp.ones(a, bool)
    if held is not None:
        if resteal:
            raise ValueError("restealing needs every expert in the share")
        mine = held.reshape(a)
        e = jnp.where(mine, e, num_experts)
    arrival = -jnp.arange(a, dtype=jnp.float32)   # earlier = higher prio
    prio = g if policy == "priority" else arrival

    pos, keep = _dispatch_once(e, prio, num_experts, capacity)

    if resteal:
        # Next-best expert not already among the token's top-k choices.
        # (one-hot mask instead of batched scatter: cleaner transpose rule)
        chosen = jax.nn.one_hot(expert_idx, num_experts,
                                dtype=jnp.float32).sum(1)      # [T, E]
        masked = jnp.where(chosen > 0, -jnp.inf, full_probs)
        alt_e = jnp.argmax(masked, axis=-1).astype(jnp.int32)    # [T]
        alt_p = jnp.max(masked, axis=-1)                          # [T]
        alt_e_a = jnp.repeat(alt_e, k)
        alt_p_a = jnp.repeat(alt_p, k)
        # Dropped assignments move to the alternate expert; kept ones get a
        # +inf priority boost so the second pass cannot evict them.
        e2 = jnp.where(keep, e, alt_e_a)
        prio2 = jnp.where(keep, jnp.inf, alt_p_a if policy == "priority"
                          else arrival)
        pos2, keep2 = _dispatch_once(e2, prio2, num_experts, capacity)
        restolen = keep2 & ~keep
        e = jnp.where(restolen, e2, e)
        g = jnp.where(restolen, alt_p_a.astype(g.dtype), g)
        pos, keep = pos2, keep2

    slot = jnp.where(keep, e * capacity + pos, num_experts * capacity)
    slot_src = jnp.full(num_experts * capacity + 1, -1, jnp.int32)
    slot_src = slot_src.at[slot].set(jnp.arange(a, dtype=jnp.int32))
    slot_src = slot_src[:-1].reshape(num_experts, capacity)

    load = jnp.sum(
        (jnp.arange(num_experts)[:, None] == e[None, :]) & keep[None, :],
        axis=1).astype(jnp.int32)
    gate_kept = jnp.where(keep, g, 0.0)
    dropped_mass = jnp.sum(jnp.where(keep | ~mine, 0.0, g))
    return DispatchPlan(slot_src=slot_src,
                        kept=keep.reshape(t, k),
                        expert=e.reshape(t, k).astype(jnp.int32),
                        gate=gate_kept.reshape(t, k).astype(jnp.float32),
                        load=load,
                        dropped_mass=dropped_mass)


def gather_expert_inputs(x: jax.Array, plan: DispatchPlan,
                         num_choices: int) -> jax.Array:
    """Gather token vectors into expert buffers.  x: [T, D] → [E, C, D];
    empty slots are zero."""
    token = jnp.where(plan.slot_src >= 0, plan.slot_src // num_choices, 0)
    buf = x[token]
    return buf * (plan.slot_src >= 0)[..., None].astype(x.dtype)


def combine_expert_outputs(y_buf: jax.Array, plan: DispatchPlan,
                           num_tokens: int, num_choices: int) -> jax.Array:
    """Scatter expert outputs back and apply combine (gate) weights.
    y_buf: [E, C, D] → [T, D]."""
    e, c, d = y_buf.shape
    flat_src = plan.slot_src.reshape(e * c)
    valid = flat_src >= 0
    token = jnp.where(valid, flat_src // num_choices, num_tokens)
    gate = plan.gate.reshape(-1)[jnp.clip(flat_src, 0)]
    contrib = (y_buf.reshape(e * c, d).astype(jnp.float32)
               * (gate * valid)[:, None])
    out = jnp.zeros((num_tokens + 1, d), jnp.float32).at[token].add(contrib)
    return out[:num_tokens].astype(y_buf.dtype)
