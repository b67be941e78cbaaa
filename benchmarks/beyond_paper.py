"""Beyond-paper benchmarks: the strategy decisions compiled into the
LM stack (MoE dispatch quality, weighted packing balance, serving
scheduler, kernel microbenches in interpret mode)."""
from __future__ import annotations

import numpy as np

from .common import SCALE, emit, timed


def moe_dispatch_quality() -> None:
    """Strategy (priority + resteal) vs oblivious (arrival) dispatch:
    router-probability mass preserved under capacity pressure."""
    import jax
    import jax.numpy as jnp
    from repro.core.device import priority_dispatch, route_topk

    t, e, k = 4096, 64, 2
    logits = jax.random.normal(jax.random.PRNGKey(0), (t, e)) * 2.0
    eidx, gate, probs = route_topk(logits, k)
    total = float(gate.sum())
    for cf in (1.0, 1.25):
        cap = max(1, int(t * k * cf / e))
        rows = {}
        for name, policy, resteal in (
                ("arrival", "arrival", False),
                ("priority", "priority", False),
                ("priority+resteal", "priority", True)):
            def fn(policy=policy, resteal=resteal):
                return priority_dispatch(eidx, gate, probs, num_experts=e,
                                         capacity=cap, policy=policy,
                                         resteal=resteal)
            plan, dt = timed(lambda: jax.block_until_ready(fn()), repeats=2)
            kept = total - float(plan.dropped_mass)
            rows[name] = kept
            emit(f"moe_dispatch_cf{cf}_{name}", dt,
                 f"kept_mass={kept / total:.4f} "
                 f"max_load={int(plan.load.max())} cap={cap}")


def packing_balance() -> None:
    """Steal-half-work shard assignment vs round-robin on mixed-length
    documents (straggler-free steps need equal WORK per shard)."""
    from repro.data import pack_documents
    rng = np.random.default_rng(0)
    lengths = np.clip(rng.lognormal(6.0, 1.0, int(2000 * SCALE)), 16,
                      16384).astype(int)
    (rows, shard), dt = timed(pack_documents, lengths, 4096, 16)
    fill = np.array([sum(ln for _, ln in r) for r in rows], np.float64)
    loads = np.bincount(shard, weights=fill, minlength=16)
    rr = np.bincount(np.arange(len(fill)) % 16, weights=fill, minlength=16)
    emit("packing_steal_half_work", dt,
         f"imbalance={loads.max() / loads.mean():.4f} "
         f"roundrobin={rr.max() / rr.mean():.4f}")


def serving_scheduler() -> None:
    """Continuous batching with strategies: merged prefills + priority."""
    from repro.core.device import ContinuousBatcher, Request
    now = [0.0]
    b = ContinuousBatcher(max_batch=16, prefill_token_budget=2048,
                          now=lambda: now[0])
    rng = np.random.default_rng(1)
    reqs = [Request(prompt_len=int(rng.integers(16, 512)),
                    max_new_tokens=int(rng.integers(8, 64)),
                    priority=float(rng.integers(0, 3)))
            for _ in range(int(256 * SCALE))]

    def drive():
        b.submit_many(reqs)
        steps = 0
        while any(r.state.name not in ("DONE", "CANCELLED") for r in reqs) \
                and steps < 100_000:
            plan = b.plan_step()
            b.complete_prefill(plan.prefill)
            b.complete_decode(plan.decode)
            now[0] += 0.01
            steps += 1
        return steps

    steps, dt = timed(drive)
    m = b.metrics
    emit("serving_batcher", dt,
         f"steps={steps} merged_prefills={m['merged_prefills']} "
         f"throughput={len(reqs) / max(now[0], 1e-9):.1f}req_per_sim_s")


def kernel_microbench() -> None:
    """interpret-mode kernels vs their jnp oracles (correct-path cost on
    CPU; speed is measured on the chip by ``bench/``)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.prefix_scan.ops import prefix_scan
    from repro.kernels.prefix_scan.ref import prefix_scan_ref
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.moe_gmm.ops import grouped_swiglu
    from repro.kernels.wkv6.ops import wkv6

    x = jnp.arange(1 << 14, dtype=jnp.int32).reshape(4, -1)
    _, dt_k = timed(lambda: jax.block_until_ready(prefix_scan(x)), repeats=2)
    _, dt_r = timed(lambda: jax.block_until_ready(prefix_scan_ref(x)),
                    repeats=2)
    emit("kernel_prefix_scan_interp", dt_k, f"ref={dt_r * 1e6:.0f}us")

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    _, dt_k = timed(lambda: jax.block_until_ready(
        flash_attention(q, k, v, bq=64, bk=64)), repeats=2)
    emit("kernel_flash_attn_interp", dt_k, "s=256 h=4 d=64")

    e, c, d, f = 4, 64, 64, 128
    xw = jax.random.normal(ks[0], (e, c, d))
    wg = jax.random.normal(ks[1], (e, d, f)) / 8
    wu = jax.random.normal(ks[2], (e, d, f)) / 8
    wd = jax.random.normal(ks[0], (e, f, d)) / 11
    _, dt_k = timed(lambda: jax.block_until_ready(
        grouped_swiglu(xw, wg, wu, wd, bc=32, bf=64)), repeats=2)
    emit("kernel_moe_gmm_interp", dt_k, f"e{e} c{c} d{d} f{f}")

    r = jax.random.normal(ks[0], (1, 64, 2, 32))
    kk = jax.random.normal(ks[1], (1, 64, 2, 32))
    vv = jax.random.normal(ks[2], (1, 64, 2, 32))
    w = jax.nn.sigmoid(jax.random.normal(ks[0], (1, 64, 2, 32))) * 0.5 + 0.45
    u = jax.random.normal(ks[1], (2, 32)) * 0.1
    _, dt_k = timed(lambda: jax.block_until_ready(
        wkv6(r, kk, vv, w, u, chunk=16)[0]), repeats=2)
    emit("kernel_wkv6_interp", dt_k, "t=64 h=2 n=32")


ALL = [moe_dispatch_quality, packing_balance, serving_scheduler,
       kernel_microbench]
