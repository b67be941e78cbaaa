"""Uniform model interface over all assigned architecture families."""
from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import encdec, hybrid, rwkv_lm, transformer
from .transformer import LMOutputs

__all__ = ["Model", "build_model", "lm_loss"]


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[..., dict]                  # (key) -> params
    forward: Callable[..., LMOutputs]          # (params, batch) -> outputs
    prefill: Callable[..., tuple]              # (params, batch, s_max)
    decode_step: Callable[..., tuple]          # (params, token, cache, pos)
    init_cache: Callable[..., Any]             # (batch, s_max) -> cache
    #: contiguous slot insertion (cache, dense_cache_B1, slot) -> cache.
    #: None = every cache leaf carries batch on the engine's batch_axis;
    #: the hybrid overrides it (KV on axis 1, Mamba states on axis 2).
    insert_prefill: Optional[Callable[..., Any]] = None
    # Paged-KV serving paths (None where the family has no paged form —
    # SSM/enc-dec fall back to the contiguous engine).  Each takes the
    # layer-stacked pool as its argument named ``cache`` and returns it
    # updated; the engine donates that argument by name
    # (``serving.paged_kv.jit_pool_program``):
    #   init_paged_cache(batch, num_blocks, block_size) -> pool cache
    #   decode_step_paged(params, token, cache, table, pos)
    #   insert_prefill_paged(cache, dense_cache_B1, table_row, slot)
    #   prefill_chunk_paged(params, batch, cache, table_row, start)
    #   verify_paged(params, tokens_Bc, cache, table, pos) — speculative
    #     verification: all-position logits for c tokens per sequence
    #     (pure-attention trunks only; SSM/hybrid state is not positional,
    #     so rejected draft state could not be rolled back)
    init_paged_cache: Optional[Callable[..., Any]] = None
    decode_step_paged: Optional[Callable[..., tuple]] = None
    insert_prefill_paged: Optional[Callable[..., Any]] = None
    prefill_chunk_paged: Optional[Callable[..., tuple]] = None
    verify_paged: Optional[Callable[..., tuple]] = None

    @property
    def supports_paged(self) -> bool:
        return self.decode_step_paged is not None

    @property
    def supports_speculation(self) -> bool:
        """Can act as a speculative-decoding *target* (paged verify path)."""
        return self.verify_paged is not None

    @property
    def supports_drafting(self) -> bool:
        """Can act as a *draft* model: any family with a standalone
        contiguous cache and decode step (enc-dec caches need the encoder
        pass, so they cannot chain greedy draft steps slot-aligned)."""
        return self.init_cache is not None

    def loss(self, params: dict, batch: dict) -> tuple[jax.Array, dict]:
        return lm_loss(self, params, batch)


def build_model(cfg: ModelConfig) -> Model:
    """The family's model, its functions named after their fields: a
    program jitted from one traces as ``jit_decode_step_paged`` (not
    ``jit__lambda``), and engines sharing the model share its programs."""
    model = _build(cfg)
    for f in fields(model):
        fn = getattr(model, f.name)
        if getattr(fn, "__name__", None) == "<lambda>":
            fn.__name__ = fn.__qualname__ = f.name
    return model


def _build(cfg: ModelConfig) -> Model:
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        return Model(
            cfg=cfg,
            init=lambda key: transformer.init_lm(key, cfg),
            forward=lambda p, b: transformer.lm_forward(p, b, cfg),
            prefill=lambda p, b, s_max=None: transformer.lm_prefill(
                p, b, cfg, s_max),
            decode_step=lambda p, tok, cache, pos: transformer.lm_decode_step(
                p, tok, cache, pos, cfg),
            init_cache=lambda batch, s_max: transformer.init_lm_cache(
                cfg, batch, s_max),
            init_paged_cache=lambda batch, nb, bs:
                transformer.init_lm_paged_cache(cfg, nb, bs),
            decode_step_paged=lambda p, tok, cache, table, pos:
                transformer.lm_paged(p, tok, cache, table, pos, cfg),
            insert_prefill_paged=lambda cache, dense, row, slot:
                transformer.lm_insert_prefill_paged(cache, dense, row, slot,
                                                    cfg),
            prefill_chunk_paged=lambda p, b, cache, row, start:
                transformer.lm_paged(p, b["tokens"], cache, row[None],
                                     jnp.asarray(start, jnp.int32)[None],
                                     cfg, last_only=True),
            # speculative verify has no latent-attention (MLA) form
            verify_paged=None if cfg.is_mla else
            (lambda p, toks, cache, table, pos:
                transformer.lm_paged(p, toks, cache, table, pos, cfg)),
        )
    if fam == "ssm":
        return Model(
            cfg=cfg,
            init=lambda key: rwkv_lm.init_rwkv_lm(key, cfg),
            forward=lambda p, b: rwkv_lm.rwkv_forward(p, b, cfg),
            prefill=lambda p, b, s_max=None: rwkv_lm.rwkv_prefill(
                p, b, cfg, s_max),
            decode_step=lambda p, tok, cache, pos: rwkv_lm.rwkv_decode_step(
                p, tok, cache, pos, cfg),
            init_cache=lambda batch, s_max: rwkv_lm.init_rwkv_cache(
                cfg, batch),
        )
    if fam == "hybrid":
        return Model(
            cfg=cfg,
            init=lambda key: hybrid.init_hybrid_lm(key, cfg),
            forward=lambda p, b: hybrid.hybrid_forward(p, b, cfg),
            prefill=lambda p, b, s_max=None: hybrid.hybrid_prefill(
                p, b, cfg, s_max),
            decode_step=lambda p, tok, cache, pos: hybrid.hybrid_decode_step(
                p, tok, cache, pos, cfg),
            init_cache=lambda batch, s_max: hybrid.init_hybrid_cache(
                cfg, batch, s_max),
            insert_prefill=lambda cache, dense, slot:
                hybrid.hybrid_insert_prefill(cache, dense, slot, cfg),
            init_paged_cache=lambda batch, nb, bs:
                hybrid.init_hybrid_paged_cache(cfg, batch, nb, bs),
            decode_step_paged=lambda p, tok, cache, table, pos:
                hybrid.hybrid_decode_step_paged(p, tok, cache, table, pos,
                                                cfg),
            insert_prefill_paged=lambda cache, dense, row, slot:
                hybrid.hybrid_insert_prefill_paged(cache, dense, row, slot,
                                                   cfg),
            # chunked prefill needs Mamba state carry across chunks — the
            # hybrid prefills whole prompts (still paged for decode)
        )
    if fam == "encdec":
        return Model(
            cfg=cfg,
            init=lambda key: encdec.init_encdec(key, cfg),
            forward=lambda p, b: encdec.encdec_forward(p, b, cfg),
            prefill=lambda p, b, s_max=None: encdec.encdec_prefill(
                p, b, cfg, s_max),
            decode_step=lambda p, tok, cache, pos: encdec.encdec_decode_step(
                p, tok, cache, pos, cfg),
            init_cache=None,  # produced by prefill (needs encoder output)
        )
    raise ValueError(f"unknown family: {fam}")


def _xent(logits: jax.Array, labels: jax.Array,
          vocab_chunk: int = 0) -> jax.Array:
    """Mean next-token cross entropy in fp32 (numerically safe at V>150k).

    ``vocab_chunk > 0`` computes the logsumexp blockwise over the vocab dim
    (running max/denominator — the flash-softmax trick applied to the loss),
    so the fp32 logits copy never materializes at full [.., V]."""
    if not vocab_chunk:
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None],
                                   axis=-1).squeeze(-1)
        return (logz - gold).mean()
    v = logits.shape[-1]
    pad = (-v) % vocab_chunk
    if pad:  # pad vocab with -inf-like logits (no mass)
        logits = jnp.pad(logits, [(0, 0)] * (logits.ndim - 1) + [(0, pad)],
                         constant_values=-1e30)
        v += pad
    n_chunks = v // vocab_chunk
    lead = logits.shape[:-1]
    chunks = jnp.moveaxis(
        logits.reshape(*lead, n_chunks, vocab_chunk), -2, 0)

    def body(carry, ch):
        m, l = carry
        ch = ch.astype(jnp.float32)
        m2 = jnp.maximum(m, ch.max(-1))
        l = l * jnp.exp(m - m2) + jnp.exp(ch - m2[..., None]).sum(-1)
        return (m2, l), None

    m0 = jnp.full(lead, -jnp.inf, jnp.float32)
    l0 = jnp.zeros(lead, jnp.float32)
    (m, l), _ = jax.lax.scan(body, (m0, l0), chunks)
    logz = m + jnp.log(l)
    gold = jnp.take_along_axis(logits, labels[..., None],
                               axis=-1).squeeze(-1).astype(jnp.float32)
    return (logz - gold).mean()


def lm_loss(model: Model, params: dict, batch: dict) -> tuple[jax.Array, dict]:
    """Cross-entropy on next-token labels + MoE auxiliary losses.

    ``batch["labels"]`` aligns with the *text* tokens; for VLM the image
    prefix positions are excluded automatically."""
    out = model.forward(params, batch)
    logits = out.logits
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:     # VLM: image prefix present
        logits = logits[:, logits.shape[1] - labels.shape[1]:]
    loss = _xent(logits[:, :-1], labels[:, 1:],
                 model.cfg.loss_vocab_chunk)
    metrics = {"xent": loss}
    if out.moe_aux is not None:
        aux = out.moe_aux * model.cfg.router_aux_coef
        loss = loss + aux
        metrics["moe_aux"] = aux
        if out.moe_dropped is not None:
            metrics["moe_dropped_mass"] = jnp.asarray(out.moe_dropped).mean()
    metrics["loss"] = loss
    return loss, metrics
