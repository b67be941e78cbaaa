"""Jamba-style hybrid stack: superblocks of ``attn_every`` layers with one
attention layer (at ``attn_index``) and Mamba elsewhere; every
``moe_layer_period``-th layer's FFN is MoE, the rest dense MLP.

Superblocks are homogeneous, so the stack scans over superblocks (stacked
params) while the heterogeneous interior is unrolled — HLO stays O(block)
instead of O(depth).  Decode carries one KV cache per superblock plus Mamba
states for the SSM positions; attention KV is the only cache that grows with
context, which is what makes the hybrid ``long_500k``-capable.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import (KVCache, PagedKVCache, attention_decode,
                        attention_fwd, attention_paged, init_attention,
                        init_kv_cache, init_paged_kv_cache)
from .layers import (dtype_of, embed, init_embedding, init_linear, init_mlp,
                     init_rms_norm, linear, mlp, rms_norm)
from .moe import init_moe, moe_fwd
from .ssm import MambaState, init_mamba, mamba_decode, mamba_fwd
from .transformer import LMOutputs

__all__ = ["init_hybrid_lm", "hybrid_forward", "hybrid_prefill",
           "hybrid_decode_step", "init_hybrid_cache", "HybridCache",
           "hybrid_insert_prefill", "HybridPagedCache",
           "init_hybrid_paged_cache", "hybrid_decode_step_paged",
           "hybrid_insert_prefill_paged"]


class HybridCache(NamedTuple):
    kv: KVCache          # [n_sb, B, S, kvH, hd] (one attn layer / superblock)
    conv: jax.Array      # [n_sb, n_mamba, B, dc-1, di]
    h: jax.Array         # [n_sb, n_mamba, B, di, ds]


class HybridPagedCache(NamedTuple):
    """Paged hybrid cache: only the attention KV (the part that grows with
    context) is paged; Mamba conv/ssm states are O(1) per sequence and stay
    slot-indexed on the batch axis."""
    kv: PagedKVCache     # [n_sb, num_blocks, bs, kvH, hd]
    conv: jax.Array      # [n_sb, n_mamba, B, dc-1, di]
    h: jax.Array         # [n_sb, n_mamba, B, di, ds]


def _positions(cfg: ModelConfig):
    sb = cfg.attn_every
    attn_at = cfg.attn_index % sb
    moe_at = [i for i in range(sb) if (i % cfg.moe_layer_period)
              == (cfg.moe_layer_period - 1)] if cfg.num_experts else []
    return sb, attn_at, moe_at


def _init_superblock(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg)
    sb, attn_at, moe_at = _positions(cfg)
    layers = []
    keys = jax.random.split(key, sb)
    for i in range(sb):
        k1, k2 = jax.random.split(keys[i])
        layer = {"ln1": init_rms_norm(cfg.d_model, dt),
                 "ln2": init_rms_norm(cfg.d_model, dt)}
        if i == attn_at:
            layer["attn"] = init_attention(k1, cfg, dt)
        else:
            layer["mamba"] = init_mamba(k1, cfg, dt)
        if i in moe_at:
            layer["moe"] = init_moe(k2, cfg, dt)
        else:
            layer["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dt)
        layers.append(layer)
    return {"layers": layers}


def init_hybrid_lm(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg)
    sb, _, _ = _positions(cfg)
    assert cfg.num_layers % sb == 0, "layers must tile into superblocks"
    n_sb = cfg.num_layers // sb
    ke, kl, kh = jax.random.split(key, 3)
    sb_keys = jax.random.split(kl, n_sb)
    return {
        "embed": init_embedding(ke, cfg.vocab_size, cfg.d_model, dt),
        "superblocks": jax.vmap(lambda k: _init_superblock(k, cfg))(sb_keys),
        "ln_f": init_rms_norm(cfg.d_model, dt),
        "lm_head": init_linear(kh, cfg.d_model, cfg.vocab_size, dtype=dt),
    }


def _ffn(layer: dict, h: jax.Array, cfg: ModelConfig):
    z = rms_norm(layer["ln2"], h, cfg.norm_eps)
    if "moe" in layer:
        y, stats = moe_fwd(layer["moe"], z, cfg, use_kernel=cfg.use_flash)
        return h + y, stats.aux_loss
    return h + mlp(layer["mlp"], z), jnp.float32(0)


def _superblock_fwd(p: dict, x: jax.Array, cfg: ModelConfig, positions,
                    return_kv: bool = False):
    aux = jnp.float32(0)
    kv_out = None
    mamba_states = []
    for layer in p["layers"]:
        z = rms_norm(layer["ln1"], x, cfg.norm_eps)
        if "attn" in layer:
            out = attention_fwd(layer["attn"], z, cfg, positions,
                                use_flash=cfg.use_flash,
                                return_kv=return_kv)
            if return_kv:
                out, kv_out = out
            x = x + out
        else:
            out, mstate = mamba_fwd(layer["mamba"], z, cfg)
            mamba_states.append(mstate)
            x = x + out
        x, a = _ffn(layer, x, cfg)
        aux = aux + a
    def stack(xs):
        return jax.tree.map(lambda *a: jnp.stack(a), *xs)
    return x, (aux, kv_out, stack(mamba_states) if return_kv else None)


def hybrid_forward(params: dict, batch: dict, cfg: ModelConfig) -> LMOutputs:
    x = embed(params["embed"], batch["tokens"], cfg.onehot_embed)
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]

    def body(h, pl):
        y, (aux, _, _) = _superblock_fwd(pl, h, cfg, positions)
        return y, aux

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, auxs = jax.lax.scan(body_fn, x, params["superblocks"],
                           unroll=cfg.unroll_scans)
    x = rms_norm(params["ln_f"], x, cfg.norm_eps)
    return LMOutputs(linear(params["lm_head"], x), moe_aux=auxs.mean())


def init_hybrid_cache(cfg: ModelConfig, batch: int, s_max: int) -> HybridCache:
    sb, _, _ = _positions(cfg)
    n_sb = cfg.num_layers // sb
    n_mamba = sb - 1
    dt = dtype_of(cfg)
    one = init_kv_cache(cfg, batch, s_max, dt)
    def rep(a):
        return jnp.broadcast_to(a[None], (n_sb,) + a.shape).copy()
    return HybridCache(
        kv=KVCache(rep(one.k), rep(one.v)),
        conv=jnp.zeros((n_sb, n_mamba, batch, cfg.mamba_d_conv - 1,
                        cfg.mamba_d_inner), dt),
        h=jnp.zeros((n_sb, n_mamba, batch, cfg.mamba_d_inner,
                     cfg.mamba_d_state), jnp.float32))


def hybrid_prefill(params: dict, batch: dict, cfg: ModelConfig,
                   s_max: Optional[int] = None):
    x = embed(params["embed"], batch["tokens"], cfg.onehot_embed)
    b, s, _ = x.shape
    s_max = s_max or s
    positions = jnp.arange(s)[None, :]

    def body(h, pl):
        y, (aux, kv, mstates) = _superblock_fwd(pl, h, cfg, positions,
                                                return_kv=True)
        return y, (kv, mstates)

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, (kvs, mstates) = jax.lax.scan(body_fn, x, params["superblocks"],
                                     unroll=cfg.unroll_scans)
    x = rms_norm(params["ln_f"], x, cfg.norm_eps)
    logits = linear(params["lm_head"], x[:, -1:])
    cache = init_hybrid_cache(cfg, b, s_max)
    cap = cache.kv.k.shape[2]  # [n_sb, B, S, kvH, hd] — seq axis
    w = min(s, cap)
    tk, tv = kvs[0][:, :, s - w:s], kvs[1][:, :, s - w:s]
    if w == cap and s % cap:
        tk = jnp.roll(tk, s % cap, axis=2)
        tv = jnp.roll(tv, s % cap, axis=2)
    cache = cache._replace(
        kv=KVCache(jax.lax.dynamic_update_slice_in_dim(cache.kv.k, tk, 0, 2),
                   jax.lax.dynamic_update_slice_in_dim(cache.kv.v, tv, 0, 2)),
        conv=mstates.conv, h=mstates.h)
    return logits, cache


def _superblock_decode(p: dict, x, kv: KVCache, conv, h, pos,
                       cfg: ModelConfig):
    new_kv = kv
    new_conv, new_h = [], []
    mi = 0
    for layer in p["layers"]:
        z = rms_norm(layer["ln1"], x, cfg.norm_eps)
        if "attn" in layer:
            y, new_kv = attention_decode(layer["attn"], z, kv, pos, cfg)
            x = x + y
        else:
            st = MambaState(conv=conv[mi], h=h[mi])
            y, st2 = mamba_decode(layer["mamba"], z, cfg, st)
            new_conv.append(st2.conv)
            new_h.append(st2.h)
            mi += 1
            x = x + y
        x, _ = _ffn(layer, x, cfg)
    return x, new_kv, jnp.stack(new_conv), jnp.stack(new_h)


def hybrid_decode_step(params: dict, token: jax.Array, cache: HybridCache,
                       pos, cfg: ModelConfig):
    x = embed(params["embed"], token, cfg.onehot_embed)

    def body(hx, layer):
        pl, kv_k, kv_v, conv, h = layer
        y, kv, conv2, h2 = _superblock_decode(pl, hx, KVCache(kv_k, kv_v),
                                              conv, h, pos, cfg)
        return y, (kv, conv2, h2)

    x, (kv, conv, h) = jax.lax.scan(
        body, x, (params["superblocks"], cache.kv.k, cache.kv.v,
                  cache.conv, cache.h), unroll=cfg.unroll_scans)
    x = rms_norm(params["ln_f"], x, cfg.norm_eps)
    return linear(params["lm_head"], x), HybridCache(kv, conv, h)


def hybrid_insert_prefill(cache: HybridCache, dense: HybridCache,
                          slot, cfg: ModelConfig) -> HybridCache:
    """Insert one request's prefill cache (B=1) into batch slot ``slot`` of
    the engine's contiguous cache.  The batch axis differs per leaf — KV
    carries it on axis 1, Mamba conv/ssm states on axis 2 — so a uniform
    tree-map over one axis would corrupt neighbouring slots' Mamba states."""
    def put(full, one, ax):
        return jax.lax.dynamic_update_slice_in_dim(
            full, one.astype(full.dtype), slot, ax)
    return HybridCache(
        kv=KVCache(put(cache.kv.k, dense.kv.k, 1),
                   put(cache.kv.v, dense.kv.v, 1)),
        conv=put(cache.conv, dense.conv, 2),
        h=put(cache.h, dense.h, 2))


# --------------------------------------------------------------------------
# Paged KV (attention superblocks page; Mamba states stay slot-dense)
# --------------------------------------------------------------------------

def init_hybrid_paged_cache(cfg: ModelConfig, batch: int, num_blocks: int,
                            block_size: int) -> HybridPagedCache:
    sb, _, _ = _positions(cfg)
    n_sb = cfg.num_layers // sb
    n_mamba = sb - 1
    dt = dtype_of(cfg)
    return HybridPagedCache(
        kv=init_paged_kv_cache(cfg, n_sb, num_blocks, block_size, dt),
        conv=jnp.zeros((n_sb, n_mamba, batch, cfg.mamba_d_conv - 1,
                        cfg.mamba_d_inner), dt),
        h=jnp.zeros((n_sb, n_mamba, batch, cfg.mamba_d_inner,
                     cfg.mamba_d_state), jnp.float32))


def _superblock_decode_paged(p: dict, x, kv: PagedKVCache, sb, conv, h,
                             table, pos, cfg: ModelConfig):
    """Superblock ``sb``: its attention layer reads and writes layer ``sb``
    of the stacked pool ``kv``."""
    new_conv, new_h = [], []
    mi = 0
    for layer in p["layers"]:
        z = rms_norm(layer["ln1"], x, cfg.norm_eps)
        if "attn" in layer:
            y, kv = attention_paged(layer["attn"], z, kv, sb, table, pos,
                                    cfg)
            x = x + y
        else:
            st = MambaState(conv=conv[mi], h=h[mi])
            y, st2 = mamba_decode(layer["mamba"], z, cfg, st)
            new_conv.append(st2.conv)
            new_h.append(st2.h)
            mi += 1
            x = x + y
        x, _ = _ffn(layer, x, cfg)
    return x, kv, jnp.stack(new_conv), jnp.stack(new_h)


def hybrid_decode_step_paged(params: dict, token: jax.Array,
                             cache: HybridPagedCache, table: jax.Array,
                             pos, cfg: ModelConfig):
    """Paged hybrid decode: attention KV read through ``table``
    [B, max_blocks] from the stacked pool, which the scan carries (as
    ``transformer._scan_stacks`` does); conv/ssm states indexed by batch
    slot as before."""
    x = embed(params["embed"], token, cfg.onehot_embed)

    def body(carry, layer):
        hx, kv = carry
        pl, sb, conv, h = layer
        y, kv, conv2, h2 = _superblock_decode_paged(
            pl, hx, kv, sb, conv, h, table, pos, cfg)
        return (y, kv), (conv2, h2)

    (x, kv), (conv, h) = jax.lax.scan(
        body, (x, cache.kv), (params["superblocks"],
                              jnp.arange(cache.conv.shape[0]),
                              cache.conv, cache.h), unroll=cfg.unroll_scans)
    x = rms_norm(params["ln_f"], x, cfg.norm_eps)
    return linear(params["lm_head"], x), HybridPagedCache(kv, conv, h)


def hybrid_insert_prefill_paged(cache: HybridPagedCache, dense: HybridCache,
                                table_row: jax.Array, slot,
                                cfg: ModelConfig) -> HybridPagedCache:
    """Scatter a single request's contiguous prefill cache (B=1) into the
    pool blockwise, and its Mamba states into batch slot ``slot``."""
    nblk = table_row.shape[0]
    bs = cache.kv.k.shape[2]
    n_sb = cache.kv.k.shape[0]

    def scatter(pool, full):
        blocks = full[:, 0].reshape(n_sb, nblk, bs, *pool.shape[3:])
        return pool.at[:, table_row].set(blocks.astype(pool.dtype))

    conv = cache.conv.at[:, :, slot].set(
        dense.conv[:, :, 0].astype(cache.conv.dtype))
    h = cache.h.at[:, :, slot].set(dense.h[:, :, 0].astype(cache.h.dtype))
    return HybridPagedCache(
        PagedKVCache(scatter(cache.kv.k, dense.kv.k),
                     scatter(cache.kv.v, dense.kv.v)), conv, h)
