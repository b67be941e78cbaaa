"""Decoder-only transformer trunk (dense / MoE / VLM language model).

The layer stack is homogeneous, so parameters are stacked with a leading
layer axis (``vmap`` over init) and the forward is a ``lax.scan`` over
layers — HLO size stays O(1) in depth, which keeps 88-layer × 512-device
compiles tractable.  ``jax.checkpoint`` on the block body gives per-layer
rematerialization.

A model with ``cfg.first_dense_layers`` leading dense layers (DeepSeek-V2)
is two such stacks, one scan each: ``dense_blocks`` with a dense MLP, then
``blocks``.  Its caches are then a tuple with one layer-stacked cache per
stack, so no call slices or joins a whole cache.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import (KVCache, attention_decode, attention_fwd,
                        attention_paged, init_attention, init_kv_cache,
                        init_paged_kv_cache)
from .layers import (dtype_of, embed, init_embedding, init_linear,
                     init_mlp, init_rms_norm, linear, mlp, rms_norm)
from .moe import MoEStats, init_moe, moe_fwd

__all__ = ["init_lm", "lm_forward", "lm_prefill", "lm_decode_step",
           "init_lm_cache", "LMOutputs", "init_lm_paged_cache", "lm_paged",
           "lm_insert_prefill_paged"]


class LMOutputs(NamedTuple):
    logits: jax.Array
    moe_load: Optional[jax.Array] = None      # [L, E]
    moe_dropped: Optional[jax.Array] = None   # [L]
    moe_aux: Optional[jax.Array] = None       # [] load-balance loss


def _is_moe(cfg: ModelConfig) -> bool:
    return cfg.num_experts > 0


def _pin(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Keep activations batch-sharded at layer boundaries (under a mesh
    with a 'data' axis); prevents SPMD replicate-then-reshard round trips
    at scan/microbatch seams.  MoE trunks additionally shard the hidden dim
    over 'model' so layer-boundary layouts match the expert-parallel
    dispatch (avoids reshards around the all-to-all)."""
    if not cfg.activation_sharding:
        return x
    try:
        from jax.sharding import PartitionSpec as P
        spec = [None] * x.ndim
        spec[0] = "data"
        if cfg.num_experts and x.ndim >= 3 \
                and cfg.activation_sharding_moe_model:
            spec[-1] = "model"
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except (ValueError, RuntimeError):
        return x


def _stacks(cfg: ModelConfig) -> list:
    """(params key, number of layers, MoE?) of each stack, in order."""
    lead = cfg.first_dense_layers
    main = ("blocks", cfg.num_layers - lead, _is_moe(cfg))
    return ([("dense_blocks", lead, False)] if lead else []) + [main]


def _split(cache, cfg: ModelConfig) -> list:
    """One cache per stack (see the module docstring)."""
    return list(cache) if cfg.first_dense_layers else [cache]


def _join(caches: list, cfg: ModelConfig):
    return tuple(caches) if cfg.first_dense_layers else caches[0]


def _stacked(cfg: ModelConfig, one) -> list:
    """``one`` layer's cache stacked to each stack's depth."""
    return [jax.tree.map(
        lambda a, n=n: jnp.broadcast_to(a[None], (n,) + a.shape).copy(), one)
        for _, n, _ in _stacks(cfg)]


def _scan_stacks(params: dict, x: jax.Array, cache, cfg: ModelConfig,
                 layer):
    """Scan each stack's layers over ``x``, carrying the stack's whole
    layer-stacked cache: ``layer(p, h, cache, l, moe) -> (h, cache)``
    reads and writes layer ``l`` of it.  A carried cache that the caller
    donates is updated where it lies; as a scanned input and output it
    would be sliced out and stacked anew, a copy of the whole cache per
    call.  Returns the last hidden state and the new cache."""
    new = []
    for (name, n, moe), c in zip(_stacks(cfg), _split(cache, cfg)):
        def body(hc, pl, moe=moe):
            h, c = layer(pl[0], hc[0], hc[1], pl[1], moe)
            return (h, c), None

        (x, c), _ = jax.lax.scan(body, (x, c), (params[name], jnp.arange(n)),
                                 unroll=cfg.unroll_scans)
        new.append(c)
    return x, _join(new, cfg)


def _init_block(key, cfg: ModelConfig, moe: bool) -> dict:
    dt = dtype_of(cfg)
    k1, k2 = jax.random.split(key)
    p = {"ln1": init_rms_norm(cfg.d_model, dt),
         "attn": init_attention(k1, cfg, dt),
         "ln2": init_rms_norm(cfg.d_model, dt)}
    if moe:
        p["moe"] = init_moe(k2, cfg, dt)
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dt)
    return p


def _ffn(p: dict, z: jax.Array, cfg: ModelConfig, moe: bool):
    """The block's MLP (or MoE) and its routing stats."""
    if moe:
        # the same kernel selection on every path: decode must not drift
        return moe_fwd(p["moe"], z, cfg, use_kernel=cfg.use_flash)
    return mlp(p["mlp"], z), MoEStats(jnp.zeros((1,), jnp.int32),
                                      jnp.float32(0), jnp.float32(0))


def _block_fwd(p: dict, x: jax.Array, cfg: ModelConfig, positions, mask,
               moe: bool, return_kv: bool = False):
    attn_out = attention_fwd(p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps),
                             cfg, positions, mask, use_flash=cfg.use_flash,
                             return_kv=return_kv)
    if return_kv:
        attn_out, kv = attn_out
    h = x + attn_out
    y, stats = _ffn(p, rms_norm(p["ln2"], h, cfg.norm_eps), cfg, moe)
    out = _pin(h + y, cfg)
    if return_kv:
        return out, (stats, kv)
    return out, stats


def _block_decode(p: dict, x: jax.Array, cache: KVCache, layer, pos, cfg,
                  moe):
    """One block's decode against layer ``layer`` of the stacked
    contiguous ``cache``."""
    y_attn, new = attention_decode(
        p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps),
        jax.tree.map(lambda a: a[layer], cache), pos, cfg)
    h = x + y_attn
    y, _ = _ffn(p, rms_norm(p["ln2"], h, cfg.norm_eps), cfg, moe)
    return h + y, jax.tree.map(lambda a, n: a.at[layer].set(n), cache, new)


def init_lm(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg)
    ke, kl, kh, kp = jax.random.split(key, 4)
    layer_keys = jax.random.split(kl, cfg.num_layers)
    params = {
        "embed": init_embedding(ke, cfg.vocab_size, cfg.d_model, dt),
        "ln_f": init_rms_norm(cfg.d_model, dt),
    }
    first = 0
    for name, n, moe in _stacks(cfg):
        params[name] = jax.vmap(lambda k, moe=moe: _init_block(k, cfg, moe))(
            layer_keys[first:first + n])
        first += n
    if not cfg.tie_embeddings:
        params["lm_head"] = init_linear(kh, cfg.d_model, cfg.vocab_size,
                                        dtype=dt)
    if cfg.vision_embed_dim:
        # 2-layer projector: vision hidden → d_model (InternVL-style)
        k1, k2 = jax.random.split(kp)
        params["vis_proj"] = {
            "fc1": init_linear(k1, cfg.vision_embed_dim, cfg.d_model,
                               dtype=dt),
            "fc2": init_linear(k2, cfg.d_model, cfg.d_model, dtype=dt),
        }
    return params


def _embed_inputs(params: dict, batch: dict, cfg: ModelConfig):
    """Token (+ optional image) embeddings → [B, S, D]."""
    x = embed(params["embed"], batch["tokens"], cfg.onehot_embed)
    if cfg.vision_embed_dim and "image_embeds" in batch:
        vp = params["vis_proj"]
        img = linear(vp["fc2"], jax.nn.gelu(
            linear(vp["fc1"], batch["image_embeds"].astype(x.dtype))))
        x = jnp.concatenate([img, x], axis=1)   # image tokens prefixed
    return x


def _unembed(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return x @ params["embed"]["table"].T
    return linear(params["lm_head"], x)


def lm_forward(params: dict, batch: dict, cfg: ModelConfig) -> LMOutputs:
    """Training forward over the full sequence."""
    x = _pin(_embed_inputs(params, batch, cfg), cfg)
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]
    for name, _, moe in _stacks(cfg):
        def body(h, pl, moe=moe):
            y, st = _block_fwd(pl, h, cfg, positions, None, moe)
            return y, st

        body_fn = jax.checkpoint(body) if cfg.remat else body
        x, st = jax.lax.scan(body_fn, x, params[name],
                             unroll=cfg.unroll_scans)
        if moe:
            stats = st
    x = rms_norm(params["ln_f"], x, cfg.norm_eps)
    logits = _unembed(params, x, cfg)
    if _is_moe(cfg):
        return LMOutputs(logits, stats.load, stats.dropped_mass,
                         stats.aux_loss.mean())
    return LMOutputs(logits)


def init_lm_cache(cfg: ModelConfig, batch: int, s_max: int):
    one = init_kv_cache(cfg, batch, s_max, dtype_of(cfg))
    return _join(_stacked(cfg, one), cfg)


def lm_prefill(params: dict, batch: dict, cfg: ModelConfig,
               s_max: Optional[int] = None):
    """Run the prompt, return (last-position logits, filled cache)."""
    x = _embed_inputs(params, batch, cfg)
    b, s, _ = x.shape
    s_max = s_max or s
    positions = jnp.arange(s)[None, :]
    seqs = []
    for name, _, moe in _stacks(cfg):
        def body(h, pl, moe=moe):
            y, (_, kv) = _block_fwd(pl, h, cfg, positions, None, moe,
                                    return_kv=True)
            return y, kv

        body_fn = jax.checkpoint(body) if cfg.remat else body
        x, kv = jax.lax.scan(body_fn, x, params[name],
                             unroll=cfg.unroll_scans)
        seqs.append(kv)
    x = rms_norm(params["ln_f"], x, cfg.norm_eps)
    logits = _unembed(params, x[:, -1:], cfg)

    # Place the prompt's cache tail into a cache of capacity s_max;
    # ring-align so that position p sits at slot p % s_max (what decode
    # expects).
    def place(full, seq):
        cap = full.shape[2]
        w = min(s, cap)
        tail = seq[:, :, s - w:s]
        if w == cap and s % cap:
            tail = jnp.roll(tail, s % cap, axis=2)
        return jax.lax.dynamic_update_slice_in_dim(full, tail, 0, 2)

    cache = jax.tree.map(place, init_lm_cache(cfg, b, s_max),
                         _join(seqs, cfg))
    return logits, cache


def lm_decode_step(params: dict, token: jax.Array, cache,
                   pos: jax.Array, cfg: ModelConfig):
    """token: [B, 1] int32; pos: [] position index.  Returns
    (logits [B,1,V], new cache)."""
    x = embed(params["embed"], token, cfg.onehot_embed)
    x, new_cache = _scan_stacks(
        params, x, cache, cfg,
        lambda pl, h, c, l, moe: _block_decode(pl, h, c, l, pos, cfg, moe))
    x = rms_norm(params["ln_f"], x, cfg.norm_eps)
    return _unembed(params, x, cfg), new_cache


# --------------------------------------------------------------------------
# Paged KV: decode, verify and chunked prefill through block tables
# --------------------------------------------------------------------------

def init_lm_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int):
    """Layer-stacked physical block pool [L, num_blocks, ...] per stack;
    the block table (host-side, ``serving.paged_kv``) is shared across
    layers — block id ``b`` names row ``b`` of every layer's pool."""
    return _join([init_paged_kv_cache(cfg, n, num_blocks, block_size,
                                      dtype_of(cfg))
                  for _, n, _ in _stacks(cfg)], cfg)


def lm_paged(params: dict, tokens: jax.Array, cache, table: jax.Array,
             pos: jax.Array, cfg: ModelConfig, last_only: bool = False):
    """Run ``tokens`` [B, c] through every layer at absolute positions
    ``pos[b] .. pos[b]+c-1`` against the paged pool, K/V read and written
    through ``table`` [B, max_blocks] (:func:`attention_paged`).  Returns
    (logits, new pool): every row's [B, c, V], or with ``last_only`` the
    last row's [B, 1, V].  The layer scan carries the pool, so under a
    jit that donates it every layer writes its rows in place.

    Decode (``c == 1``) is bit-identical (fp32) to :func:`lm_decode_step`
    over a contiguous cache of the same logical capacity.  Speculative
    verify (``c > 1``, the last accepted token then the draft's proposals)
    needs every row: row ``i`` decides whether draft token ``i+1`` is
    accepted; with dropless MoE routing each token's computation is
    independent of its batch neighbours, so the logits match ``c``
    sequential decode steps.  A prompt chunk (``B == 1``) needs only its
    last row, which seeds the first generated token on the final chunk."""
    x = embed(params["embed"], tokens, cfg.onehot_embed)

    def layer(pl, h, pool, l, moe):
        z = rms_norm(pl["ln1"], h, cfg.norm_eps)
        attn, pool = attention_paged(pl["attn"], z, pool, l, table, pos, cfg)
        h = h + attn
        y, _ = _ffn(pl, rms_norm(pl["ln2"], h, cfg.norm_eps), cfg, moe)
        return h + y, pool

    x, new_cache = _scan_stacks(params, x, cache, cfg, layer)
    x = rms_norm(params["ln_f"], x, cfg.norm_eps)
    return _unembed(params, x[:, -1:] if last_only else x, cfg), new_cache


def lm_insert_prefill_paged(cache, dense, table_row: jax.Array, slot,
                            cfg: ModelConfig):
    """Scatter a single request's contiguous prefill cache (ring-aligned
    [L, 1, cap, ...], from :func:`lm_prefill`) into the pool blockwise.
    Sink-padded table entries receive the (zero) tail blocks — harmless, the
    sink is never unmasked.  ``slot`` is unused (the transformer keeps no
    per-slot state beyond KV); hybrid's variant writes Mamba states there."""
    del slot
    nblk = table_row.shape[0]

    def scatter(pool, full):
        blocks = full[:, 0].reshape(pool.shape[0], nblk, *pool.shape[2:])
        return pool.at[:, table_row].set(blocks.astype(pool.dtype))

    leaves, tree = jax.tree.flatten(cache)
    return jax.tree.unflatten(tree, [
        scatter(pool, full)
        for pool, full in zip(leaves, jax.tree.leaves(dense))])
