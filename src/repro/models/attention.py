"""Grouped-query attention with RoPE, optional sliding window, qk-norm and
QKV bias; full-sequence (training/prefill) and single-token (decode) paths.
Latent attention (MLA, ``cfg.is_mla``) takes the same entry points: its
cache holds one latent per token instead of per-head K and V (see the MLA
section below).

Serving reads a paged pool through per-request block tables: one
:func:`attention_paged` step covers decode, speculative verify and chunked
prefill.  Without ``cfg.use_flash`` attention is plain einsums + softmax;
with it, prefill and decode run the kernels in ``kernels/flash_attention``
(and ``kernels/mla_decode`` for latent decode).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import (apply_rope, apply_rope_inv, init_linear, init_rms_norm,
                     linear, rms_norm, yarn_inv_freq, yarn_mscale)

__all__ = ["init_attention", "attention_fwd", "attention_decode", "KVCache",
           "PagedKVCache", "attention_paged", "init_paged_kv_cache",
           "LatentKVCache", "LatentPagedCache"]


class KVCache(NamedTuple):
    k: jax.Array   # [B, S_max, kvH, hd]
    v: jax.Array   # [B, S_max, kvH, hd]


class PagedKVCache(NamedTuple):
    """Shared physical block pool of every layer: logical slot ``s`` of a
    request lives at ``pool[layer, table[s // bs], s % bs]`` where
    ``table`` is the request's block table (``serving.paged_kv`` owns the
    accounting; block 0 is the write sink for empty batch slots and is
    always masked)."""
    k: jax.Array   # [L, num_blocks, block_size, kvH, hd]
    v: jax.Array   # [L, num_blocks, block_size, kvH, hd]


class LatentKVCache(NamedTuple):
    """MLA's cache: per token the normalised latent (``kv_lora_rank``) and
    the rotated rotary key (``qk_rope_head_dim``), side by side."""
    c: jax.Array   # [B, S_max, kv_lora_rank + qk_rope_head_dim]


class LatentPagedCache(NamedTuple):
    """MLA's paged pool: blocks of latents, addressed like
    :class:`PagedKVCache`; the values are the latent's first
    ``kv_lora_rank`` entries, so there is no separate V leaf.  A block's
    ``block_size`` latents are stored flattened, in rows of 128 values
    (the TPU's lanes; of fewer where the block's size is not a multiple
    of 128): 576 values are not a multiple of 128, and a
    ``[.., block_size, 576]`` pool is laid out with the blocks on the
    lanes, which every program relays out (copies) whole, while in rows
    of 128 a block is one contiguous, unpadded piece of memory."""
    c: jax.Array   # [L, num_blocks, block_size * (r + rope) // 128, 128]


def init_attention(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    if cfg.is_mla:
        return _init_mla(key, cfg, dtype)
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": init_linear(k1, cfg.d_model, cfg.num_heads * hd,
                          bias=cfg.qkv_bias, dtype=dtype),
        "wk": init_linear(k2, cfg.d_model, cfg.num_kv_heads * hd,
                          bias=cfg.qkv_bias, dtype=dtype),
        "wv": init_linear(k3, cfg.d_model, cfg.num_kv_heads * hd,
                          bias=cfg.qkv_bias, dtype=dtype),
        "wo": init_linear(k4, cfg.num_heads * hd, cfg.d_model, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rms_norm(hd, dtype)
        p["k_norm"] = init_rms_norm(hd, dtype)
    return p


def _project_qkv(p: dict, x: jax.Array, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = linear(p["wq"], x).reshape(b, s, cfg.num_heads, hd)
    k = linear(p["wk"], x).reshape(b, s, cfg.num_kv_heads, hd)
    v = linear(p["wv"], x).reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, scale):
    """q: [B,S,H,hd]; k,v: [B,T,Hkv,hd]; GQA by head-group reshape."""
    b, s, h, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    q = q.reshape(b, s, hkv, g, hd)
    logits = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[:, None, None, :, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return out.reshape(b, s, h, v.shape[-1])


#: sequences at least this long take the chunked online-softmax path
_CHUNK_THRESHOLD = 8192
_Q_CHUNK = 1024
_KV_CHUNK = 2048


def _sdpa_chunked(q, k, v, scale, causal: bool, window: Optional[int],
                  kv_len: Optional[int] = None):
    """Flash-attention algorithm in plain XLA ops: double scan over query
    and key/value chunks with a running (max, denom, accumulator) — peak
    memory O(S·d + chunk²) instead of O(S²).  Inference path (prefill of
    long contexts); the Pallas kernel is the TPU-native version of the same
    loop."""
    b, s, h, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qc, kc = _Q_CHUNK, _KV_CHUNK
    assert s % qc == 0 and t % kc == 0, (s, t)
    qf = q.reshape(b, s // qc, qc, hkv, g, hd).astype(jnp.float32)
    kf = k.reshape(b, t // kc, kc, hkv, hd).astype(jnp.float32)
    vf = v.reshape(b, t // kc, kc, hkv, hd).astype(jnp.float32)

    def q_step(_, qi):
        qblk, qidx = qi           # [B, qc, hkv, g, hd], []
        rows = qidx * qc + jnp.arange(qc)

        def kv_step(carry, ki):
            acc, m, l = carry
            kblk, vblk, kidx = ki
            cols = kidx * kc + jnp.arange(kc)
            s_blk = jnp.einsum("bqkgd,bckd->bkgqc", qblk, kblk) * scale
            valid = jnp.ones((qc, kc), bool)
            if causal:
                valid &= cols[None, :] <= rows[:, None]
            if window is not None:
                valid &= rows[:, None] - cols[None, :] < window
            if kv_len is not None:
                valid &= cols[None, :] < kv_len
            s_blk = jnp.where(valid[None, None, None], s_blk, -1e30)
            m_new = jnp.maximum(m, s_blk.max(-1))
            p = jnp.where(valid[None, None, None],
                          jnp.exp(s_blk - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqc,bckd->bkgqd", p, vblk)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, hkv, g, qc, hd), jnp.float32)
        m0 = jnp.full((b, hkv, g, qc), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, qc), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (kf.swapaxes(0, 1), vf.swapaxes(0, 1),
             jnp.arange(t // kc)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.transpose(0, 3, 1, 2, 4)   # [B, qc, hkv, g, hd]

    _, outs = jax.lax.scan(q_step, None,
                           (qf.swapaxes(0, 1), jnp.arange(s // qc)))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h, hd)
    return out.astype(q.dtype)


def causal_mask(s: int, window: Optional[int] = None,
                dtype=bool) -> jax.Array:
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    m = j <= i
    if window is not None:
        m &= (i - j) < window
    return m.astype(dtype)


def attention_fwd(p: dict, x: jax.Array, cfg: ModelConfig,
                  positions: Optional[jax.Array] = None,
                  mask: Optional[jax.Array] = None,
                  kv: Optional[tuple] = None,
                  use_flash: bool = False,
                  return_kv: bool = False):
    """Full-sequence attention.  ``kv`` overrides keys/values for
    cross-attention (tuple of [B,T,kvH,hd]).  With ``return_kv`` the
    projected k/v are also returned as a :class:`KVCache` of the sequence
    (prefill fills the cache from them)."""
    if cfg.is_mla:
        return _mla_fwd(p, x, cfg, positions, return_kv)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = _project_qkv(p, x, cfg, positions)
    if kv is not None:
        k, v = kv
    if mask is None:
        if kv is None:
            mask = causal_mask(s, cfg.sliding_window)[None]
        else:
            mask = jnp.ones((1, s, k.shape[1]), bool)
    scale = cfg.resolved_head_dim ** -0.5
    if use_flash:
        from ..kernels.flash_attention.ops import flash_attention
        out = flash_attention(q, k, v, causal=(kv is None),
                              window=cfg.sliding_window, scale=scale)
    elif (s >= _CHUNK_THRESHOLD or k.shape[1] >= _CHUNK_THRESHOLD) \
            and s % _Q_CHUNK == 0 and k.shape[1] % _KV_CHUNK == 0:
        out = _sdpa_chunked(q, k, v, scale, causal=(kv is None),
                            window=cfg.sliding_window if kv is None
                            else None)
    else:
        out = _sdpa(q, k, v, mask, scale)
    y = linear(p["wo"], out.reshape(b, s, -1))
    if return_kv:
        return y, KVCache(k, v)
    return y


def _attend_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                   pos_vec: jax.Array, cfg: ModelConfig) -> jax.Array:
    """One query token per sequence over a dense logical cache view
    ``[B, cap, kvH, hd]`` at per-sequence positions.  Shared by the
    contiguous and paged decode paths: identical view widths and masks make
    the two bit-identical in fp32."""
    s_max = k_cache.shape[1]
    hd = cfg.resolved_head_dim
    if cfg.use_flash:
        # Flash decode: one query row per sequence, per-sequence valid-kv
        # count; the kernel reads each live KV tile once for all query
        # heads and fetches no tile past the count.  Cache slots are
        # filled 0..pos before wrap and the whole ring is live after
        # (window eviction == ring eviction), so the count is
        # min(pos+1, ring size) — slot order does not matter (RoPE is
        # applied at projection, attention is kv-permutation invariant).
        from ..kernels.flash_attention.ops import flash_decode
        kv_valid = jnp.minimum(pos_vec + 1, s_max).astype(jnp.int32)
        return flash_decode(q, k_cache, v_cache, kv_valid, scale=hd ** -0.5)
    # valid positions per sequence: j <= pos (within window when sliding)
    j = jnp.arange(s_max)[None, :]
    pcol = pos_vec[:, None]
    valid = j <= pcol
    if cfg.sliding_window is not None:
        valid = (pcol - j < cfg.sliding_window) & (j <= pcol)
        valid |= s_max <= pcol   # wrapped: the whole ring is valid
    mask = valid[:, None, :]
    return _sdpa(q, k_cache, v_cache, mask, hd ** -0.5)


def attention_decode(p: dict, x: jax.Array, cache: KVCache, pos: jax.Array,
                     cfg: ModelConfig) -> tuple[jax.Array, KVCache]:
    """One-token decode.  x: [B, 1, D]; pos: [] or [B] current position
    (per-sequence positions support continuous batching, where slots are at
    different depths); cache holds S_max past positions (ring-buffered for
    sliding window)."""
    if cfg.is_mla:
        return _mla_decode(p, x, cache, pos, cfg)
    b = x.shape[0]
    s_max = cache.k.shape[1]
    pos_vec = jnp.broadcast_to(jnp.asarray(pos).reshape(-1), (b,))
    positions = pos_vec[:, None]
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)
    # ring-buffer write (sliding window wraps; full cache: pos < s_max)
    write_idx = pos_vec % s_max
    bidx = jnp.arange(b)
    k_cache = cache.k.at[bidx, write_idx].set(
        k_new[:, 0].astype(cache.k.dtype))
    v_cache = cache.v.at[bidx, write_idx].set(
        v_new[:, 0].astype(cache.v.dtype))
    out = _attend_decode(q, k_cache, v_cache, pos_vec, cfg)
    y = linear(p["wo"], out.reshape(b, 1, -1))
    return y, KVCache(k_cache, v_cache)


def _paged_write(pool: jax.Array, layer, table: jax.Array,
                 rows: jax.Array, new: jax.Array) -> jax.Array:
    """Scatter ``new`` [B, c, ...] into layer ``layer`` of the stacked
    ``pool`` [L, num_blocks, ...] at absolute positions ``rows`` [B, c]:
    ring slot ``s = rows % cap`` of sequence ``b`` lives at
    ``pool[layer, table[b, s // bs], s % bs]`` (empty batch slots' table
    rows point at the sink).  A block holds ``bs`` token rows of
    ``new``'s trailing shape, stored as they are or flattened
    (:class:`LatentPagedCache`)."""
    row = new.shape[2:]
    bs = math.prod(pool.shape[2:]) // math.prod(row)
    slot = rows % (table.shape[1] * bs)
    blk = jnp.take_along_axis(table, slot // bs, axis=1).reshape(-1)
    off = (slot % bs).reshape(-1)
    at = (jnp.broadcast_to(layer, blk.shape), blk)
    new = new.reshape(-1, *row).astype(pool.dtype)
    if pool.shape[3:] == row:
        return pool.at[at + (off,)].set(new)
    # A flattened block: zero the token's segment of it, then add the token
    # in.  Both are scatters of whole blocks; one of the segment alone
    # compiles to a serial loop over the rows.  The values are exact
    # (``x * 1``, ``0 + x``), and two rows never share a segment except in
    # the sink.
    seg = jnp.arange(math.prod(pool.shape[2:]))[None, :] // row[0] \
        == off[:, None]
    blocks = (-1,) + pool.shape[2:]
    pool = pool.at[at].multiply((~seg).astype(pool.dtype).reshape(blocks))
    return pool.at[at].add(
        jnp.where(seg, jnp.tile(new, (1, bs)), 0).reshape(blocks))


def _paged_view(pool: jax.Array, layer, table: jax.Array,
                row: tuple) -> jax.Array:
    """Gather each sequence's logical view [B, cap, *row] of layer
    ``layer`` of ``pool``."""
    return pool[layer, table].reshape(table.shape[0], -1, *row)


def attention_paged(p: dict, x: jax.Array, cache: PagedKVCache, layer,
                    table: jax.Array, pos: jax.Array,
                    cfg: ModelConfig) -> tuple[jax.Array, PagedKVCache]:
    """``c`` tokens per sequence at absolute positions ``pos[b] ..
    pos[b]+c-1``, each batch row reading and writing K/V through its own
    block table over layer ``layer`` of the shared, layer-stacked physical
    pool.  x: [B, c, D]; table: [B, max_blocks] int32 physical block ids
    (logical block ``j`` of sequence ``b`` at ``table[b, j]``; unallocated
    entries point at the sink block, whose contents are never unmasked);
    pos: [] or [B].  Decode is ``c == 1``, speculative verify ``c > 1``
    over B rows, a prompt chunk ``B == 1``.  Returns the whole pool with
    this layer's rows written: carried through the layer scan and donated
    by the engine, it is updated where it lies.

    At ``c == 1`` the gathered logical view has the width, mask and values
    of :func:`attention_decode` over a contiguous cache of capacity
    ``cap = max_blocks * block_size`` (sliding-window ring included), so
    fp32 decode is bit-identical.  At ``c > 1`` row ``i`` attends logical
    columns ``j <= pos[b]+i`` on the masked XLA path (the flash kernel's
    ``q_offset`` is static per shape); with ``c == 1`` that is decode's
    masked path, which makes a greedy verify's accepted tokens
    bit-identical to sequential decode.  It requires ``pos[b] + c <= cap``
    for live rows: no ring wrap, since the engine falls back to plain
    decode or whole-prompt prefill near the wrap.  The mask needs no
    window term there: the engine clamps ``cap`` to the window, so
    ``j <= row`` already implies ``row - j < window``."""
    b, c, _ = x.shape
    pos_vec = jnp.broadcast_to(jnp.asarray(pos).reshape(-1), (b,))
    rows = pos_vec[:, None] + jnp.arange(c, dtype=pos_vec.dtype)   # [B, c]
    if cfg.is_mla:
        return _mla_paged(p, x, cache, layer, table, rows, cfg)
    q, k_new, v_new = _project_qkv(p, x, cfg, rows)
    k_pool = _paged_write(cache.k, layer, table, rows, k_new)
    v_pool = _paged_write(cache.v, layer, table, rows, v_new)
    row = k_new.shape[2:]
    k_log = _paged_view(k_pool, layer, table, row)
    v_log = _paged_view(v_pool, layer, table, row)
    if c == 1:
        out = _attend_decode(q, k_log, v_log, rows[:, 0], cfg)
    else:
        valid = jnp.arange(k_log.shape[1])[None, None, :] <= rows[:, :, None]
        out = _sdpa(q, k_log, v_log, valid, cfg.resolved_head_dim ** -0.5)
    y = linear(p["wo"], out.reshape(b, c, -1))
    return y, PagedKVCache(k_pool, v_pool)


def init_kv_cache(cfg: ModelConfig, batch: int, s_max: int,
                  dtype=jnp.bfloat16):
    if cfg.is_mla:
        return LatentKVCache(jnp.zeros((batch, s_max, cfg.mla_latent_width),
                                       dtype))
    hd = cfg.resolved_head_dim
    if cfg.sliding_window is not None:
        s_max = min(s_max, cfg.sliding_window)
    shape = (batch, s_max, cfg.num_kv_heads, hd)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def init_paged_kv_cache(cfg: ModelConfig, num_layers: int, num_blocks: int,
                        block_size: int, dtype=jnp.bfloat16):
    """The zero pool of ``num_layers`` layers (:class:`PagedKVCache`, or
    :class:`LatentPagedCache` for MLA)."""
    if cfg.is_mla:
        # a block's latents, flattened, in rows of 128 values
        flat = block_size * cfg.mla_latent_width
        lanes = math.gcd(flat, 128)
        return LatentPagedCache(jnp.zeros(
            (num_layers, num_blocks, flat // lanes, lanes), dtype))
    shape = (num_layers, num_blocks, block_size, cfg.num_kv_heads,
             cfg.resolved_head_dim)
    return PagedKVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


# --------------------------------------------------------------------------
# Latent attention (MLA, DeepSeek-V2 §2.1)
#
# Per token, x projects to one latent c = RMSNorm(x W_dkv) of kv_lora_rank
# values and one rotary key k_R = RoPE(x W_kr) shared by every head; the
# cache keeps [c || k_R].  Head h's key is [c W_uk[h] || k_R] and its value
# c W_uv[h] (W_uk, W_uv: the two halves of kv_b_proj).  Prefill expands the
# latents into keys and values (the paper's equations); decode absorbs
# W_uk into the query instead, so one query row of kv_lora_rank +
# qk_rope_head_dim values scores the cached latents directly and the
# softmax-weighted latent goes through W_uv afterwards: the cache is read as
# one shared head for all query heads.  Every MLA op runs under
# ``jax.named_scope("mla")``.
# --------------------------------------------------------------------------

def _init_mla(key, cfg: ModelConfig, dtype) -> dict:
    h, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"wq": init_linear(k1, cfg.d_model, h * (nope + rope), dtype=dtype),
            "wkv_a": init_linear(k2, cfg.d_model, r + rope, dtype=dtype),
            "kv_norm": init_rms_norm(r, dtype),
            "wkv_b": init_linear(k3, r, h * (nope + vd), dtype=dtype),
            "wo": init_linear(k4, h * vd, cfg.d_model, dtype=dtype)}


def _mla_softmax_scale(cfg: ModelConfig) -> float:
    """``(nope + rope) ** -0.5``, times YaRN's ``mscale_all_dim``
    temperature squared."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor > 1 and cfg.yarn_mscale_all_dim:
        m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        scale *= m * m
    return scale


def _mla_rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig):
    inv = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                        cfg.yarn_factor, cfg.yarn_original_max_position,
                        cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    scale = 1.0
    if cfg.yarn_factor > 1:
        scale = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
                 / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return apply_rope_inv(x, positions, inv.astype("float32"), scale)


def _mla_project(p: dict, x: jax.Array, cfg: ModelConfig, positions):
    """x: [B, S, D] → (q_nope [B,S,H,nope], rotated q_rope [B,S,H,rope],
    latents [B, S, r + rope])."""
    b, s, _ = x.shape
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = linear(p["wq"], x).reshape(b, s, cfg.num_heads, -1)
    q_rope = _mla_rope(q[..., nope:], positions, cfg)
    ckv = linear(p["wkv_a"], x)
    c = rms_norm(p["kv_norm"], ckv[..., :r], cfg.norm_eps)
    k_rope = _mla_rope(ckv[..., None, r:], positions, cfg)[..., 0, :]
    return q[..., :nope], q_rope, jnp.concatenate([c, k_rope], -1)


def _mla_expand(p: dict, lat: jax.Array, cfg: ModelConfig):
    """Latents [B, T, r + rope] → keys [B,T,H,nope+rope], values [B,T,H,v]."""
    b, t, _ = lat.shape
    h, r, nope = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv = linear(p["wkv_b"], lat[..., :r]).reshape(b, t, h, -1)
    k_rope = jnp.broadcast_to(lat[..., None, r:],
                              (b, t, h, cfg.qk_rope_head_dim))
    return jnp.concatenate([kv[..., :nope], k_rope], -1), kv[..., nope:]


def _mla_fwd(p: dict, x: jax.Array, cfg: ModelConfig, positions,
             return_kv: bool):
    """Full-sequence MLA in the expanded form, causal."""
    with jax.named_scope("mla"):
        b, s, _ = x.shape
        if positions is None:
            positions = jnp.arange(s)[None, :]
        q_nope, q_rope, lat = _mla_project(p, x, cfg, positions)
        k, v = _mla_expand(p, lat, cfg)
        out = _sdpa(jnp.concatenate([q_nope, q_rope], -1), k, v,
                    causal_mask(s)[None], _mla_softmax_scale(cfg))
        y = linear(p["wo"], out.reshape(b, s, -1))
    if return_kv:
        return y, LatentKVCache(lat)
    return y


def _mla_attend_latents(p: dict, q_nope, q_rope, lat: jax.Array,
                        pos_vec: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Absorbed decode: one query row per sequence ([B, 1, H, ...]) over
    the logical latent view ``lat`` [B, cap, r + rope] at per-sequence
    positions; returns the attention output [B, 1, D]."""
    b, cap, _ = lat.shape
    h, r, nope = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    w = p["wkv_b"]["w"].reshape(r, h, -1)
    q = jnp.concatenate(
        [jnp.einsum("bshn,rhn->bshr", q_nope, w[..., :nope]), q_rope], -1)
    scale = _mla_softmax_scale(cfg)
    if cfg.use_flash:
        from ..kernels.mla_decode.ops import mla_decode
        kv_valid = jnp.minimum(pos_vec + 1, cap).astype(jnp.int32)
        o = mla_decode(q[:, 0], lat, kv_valid, rank=r, scale=scale)[:, None]
    else:
        valid = jnp.arange(cap)[None, :] <= pos_vec[:, None]
        o = _sdpa(q, lat[:, :, None], lat[:, :, None, :r], valid[:, None, :],
                  scale)
    out = jnp.einsum("bshr,rhv->bshv", o.astype(w.dtype), w[..., nope:])
    return linear(p["wo"], out.reshape(b, 1, -1))


def _mla_decode(p: dict, x: jax.Array, cache: LatentKVCache, pos,
                cfg: ModelConfig):
    with jax.named_scope("mla"):
        b = x.shape[0]
        s_max = cache.c.shape[1]
        pos_vec = jnp.broadcast_to(jnp.asarray(pos).reshape(-1), (b,))
        q_nope, q_rope, lat = _mla_project(p, x, cfg, pos_vec[:, None])
        c = cache.c.at[jnp.arange(b), pos_vec % s_max].set(
            lat[:, 0].astype(cache.c.dtype))
        y = _mla_attend_latents(p, q_nope, q_rope, c, pos_vec, cfg)
    return y, LatentKVCache(c)


def _mla_paged(p: dict, x: jax.Array, cache: LatentPagedCache, layer,
               table: jax.Array, rows: jax.Array, cfg: ModelConfig):
    """:func:`attention_paged` over the latent pool, at positions ``rows``
    [B, c]: absorbed decode at ``c == 1``; at ``c > 1`` (a prompt chunk)
    the queries attend, in the expanded form, over every latent of the
    view."""
    with jax.named_scope("mla"):
        b, c, _ = x.shape
        q_nope, q_rope, lat = _mla_project(p, x, cfg, rows)
        pool = _paged_write(cache.c, layer, table, rows, lat)
        view = _paged_view(pool, layer, table, lat.shape[2:])
        if c == 1:
            y = _mla_attend_latents(p, q_nope, q_rope, view, rows[:, 0], cfg)
        else:
            k, v = _mla_expand(p, view, cfg)
            valid = jnp.arange(view.shape[1])[None, None, :] \
                <= rows[:, :, None]
            out = _sdpa(jnp.concatenate([q_nope, q_rope], -1), k, v, valid,
                        _mla_softmax_scale(cfg))
            y = linear(p["wo"], out.reshape(b, c, -1))
    return y, LatentPagedCache(pool)
