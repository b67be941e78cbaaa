"""Mixture-of-Experts layer with strategy-scheduled dispatch.

Routing/dispatch is the paper's decision procedure compiled into the step
(see ``core/device/moe_balance.py``): router probability = task priority,
capacity overflow = dead tasks, second-choice restealing = idle experts
stealing shed work.  The oblivious baseline (``dispatch_policy="arrival"``)
reproduces a standard first-come-first-served MoE.

Expert compute is a grouped matmul over the dispatch buffers
([E, C, D] × [E, D, F]); the Pallas kernel in ``kernels/moe_gmm`` implements
the TPU tiling, with the einsum here as the portable path / oracle.

A layer may hold a share of the experts (``cfg.experts_held`` of the
``cfg.num_experts`` the router scores, from ``cfg.expert_offset``): it
routes over all of them and computes only its own experts' part of the
routed result, as one chip of an expert-parallel deployment does before the
exchange.  Shared experts (``cfg.shared_expert_d_ff``) are one SwiGLU every
token passes, added to the routed result.  Every op runs under
``jax.named_scope("moe")``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core.device.moe_balance import (combine_expert_outputs,
                                       gather_expert_inputs,
                                       priority_dispatch, route_topk)
from .layers import init_linear, init_mlp, mlp

__all__ = ["init_moe", "moe_fwd", "MoEStats", "moe_capacity"]


class MoEStats(NamedTuple):
    load: jax.Array          # [E] tokens kept per expert
    dropped_mass: jax.Array  # [] router prob mass dropped (dead tasks)
    aux_loss: jax.Array      # [] load-balancing auxiliary loss


def moe_capacity(cfg: ModelConfig, num_tokens: int) -> int:
    k, e = cfg.num_experts_per_tok, cfg.num_experts
    return max(1, int(num_tokens * k * cfg.capacity_factor / e + 0.5))


def init_moe(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    """Router over all ``num_experts``; weights of the held experts only."""
    e, d, f = cfg.resolved_experts_held, cfg.d_model, cfg.resolved_moe_d_ff
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    scale_in = 1.0 / jnp.sqrt(d)
    scale_out = 1.0 / jnp.sqrt(f)
    p = {
        "router": init_linear(kr, d, cfg.num_experts, dtype=jnp.float32),
        "w_gate": (jax.random.normal(kg, (e, d, f)) * scale_in).astype(dtype),
        "w_up": (jax.random.normal(ku, (e, d, f)) * scale_in).astype(dtype),
        "w_down": (jax.random.normal(kd, (e, f, d)) * scale_out).astype(dtype),
    }
    if cfg.shared_expert_d_ff:
        p["shared"] = init_mlp(ks, d, cfg.shared_expert_d_ff, dtype)
    return p


def _expert_ffn(p: dict, buf: jax.Array, use_kernel: bool) -> jax.Array:
    """buf: [E, C, D] → [E, C, D] per-expert SwiGLU (grouped matmul)."""
    if use_kernel:
        from ..kernels.moe_gmm.ops import grouped_swiglu
        return grouped_swiglu(buf, p["w_gate"], p["w_up"], p["w_down"])
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"])


def _shared_ffn(p: dict, x: jax.Array, use_kernel: bool) -> jax.Array:
    """The shared experts' SwiGLU over every token: x [T, D] → [T, D]."""
    if not use_kernel:
        return mlp(p, x)
    from ..kernels.moe_gmm.ops import grouped_swiglu
    return grouped_swiglu(x[None], p["gate"]["w"][None], p["up"]["w"][None],
                          p["down"]["w"][None])[0]


def moe_fwd(p: dict, x: jax.Array, cfg: ModelConfig,
            use_kernel: bool = False) -> tuple[jax.Array, MoEStats]:
    """x: [B, S, D] (or [T, D]) → same shape + stats."""
    with jax.named_scope("moe"):
        return _moe_fwd(p, x, cfg, use_kernel)


def _moe_fwd(p: dict, x: jax.Array, cfg: ModelConfig,
             use_kernel: bool) -> tuple[jax.Array, MoEStats]:
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    k, e = cfg.num_experts_per_tok, cfg.num_experts
    # Dropless: capacity = T is the exact worst case (top-k experts are
    # distinct, so one expert sees at most one assignment per token) — no
    # assignment can shed, so decode ≡ forward.  The cost is dense-buffer
    # padding: ~E/(k·cf) more slots (mostly zeros) than droppy dispatch;
    # a tighter static bound cannot exist (routing may send every token to
    # one expert), so throughput studies that can tolerate drops opt out
    # via moe_dropless=False (launch/train.py does).
    # Droppy: the configured capacity, clamped to the same T bound (slots
    # past it are dead space).
    cap = t if cfg.moe_dropless else min(moe_capacity(cfg, t), t)

    logits = xt.astype(jnp.float32) @ p["router"]["w"]
    expert_idx, gate, probs = route_topk(logits, k,
                                         renormalize=cfg.moe_norm_topk)
    held, lo = cfg.resolved_experts_held, cfg.expert_offset
    mine = None
    if held < e:
        # a share: count experts within it; the rest are other chips' work
        expert_idx = expert_idx - lo
        mine = (expert_idx >= 0) & (expert_idx < held)
    plan = priority_dispatch(expert_idx, gate, probs, num_experts=held,
                             capacity=cap, policy=cfg.dispatch_policy,
                             resteal=cfg.dispatch_resteal and mine is None,
                             held=mine)
    buf = gather_expert_inputs(xt, plan, k)          # [E_held, C, D]
    buf = _expert_ffn(p, buf, use_kernel)
    y = combine_expert_outputs(buf, plan, t, k).astype(x.dtype)
    if "shared" in p:
        y = y + _shared_ffn(p["shared"], xt, use_kernel)

    # Switch-style load-balance aux loss: E * Σ_e f_e · P_e.
    me = probs.mean(0)[lo:lo + held]                  # mean router prob
    ce = plan.load.astype(jnp.float32) / jnp.maximum(plan.load.sum(), 1)
    aux = e * jnp.sum(me * ce)
    stats = MoEStats(load=plan.load, dropped_mass=plan.dropped_mass,
                     aux_loss=aux)
    return y.reshape(orig_shape), stats
