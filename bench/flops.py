"""Operations and bytes from shapes alone, and the chip's peaks.

Every count here depends only on the configuration's sizes and on live
lengths, never on how the program computes, so no implementation can read
above 100% of a peak without leaving out work.

``Dims`` is built from a configuration file's published keys.  Counts:

* a token's model FLOPs: ``2 x`` the weight-matmul parameters of every
  layer, plus ``4 * L * ctx * H * hd`` of attention over its ``ctx`` live
  keys (``QK^T`` and ``PV``), plus ``2 * d * V`` where a logit row is needed;
* decode attention's bytes: per active row and layer, its live K and V
  (``ctx * kvH * hd * 2`` values) plus its query and output (``H * hd``
  each), all in the served type.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Iterable

__all__ = ["Dims", "peaks", "token_flops", "prefill_flops",
           "decode_attention_bytes", "decode_attention_flops",
           "total_params", "kv_bytes_per_token"]

_PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    qk_norm: bool
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, hf: dict) -> "Dims":
        heads = int(hf["num_attention_heads"])
        d = int(hf["hidden_size"])
        prog = hf.get("program", {})
        return cls(layers=int(hf["num_hidden_layers"]), d=d, heads=heads,
                   kv_heads=int(hf["num_key_value_heads"]),
                   head_dim=int(hf.get("head_dim") or d // heads),
                   ff=int(hf["intermediate_size"]),
                   vocab=int(hf["vocab_size"]),
                   tied=bool(hf.get("tie_word_embeddings", False)),
                   qkv_bias=bool(prog.get("qkv_bias", False)),
                   qk_norm=bool(prog.get("qk_norm", False)))

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def layer_matmul_params(self) -> int:
        return (2 * self.d * self.q_width + 2 * self.d * self.kv_width
                + 3 * self.d * self.ff)


def total_params(m: Dims) -> int:
    """Every parameter: embedding, layers (matmuls, norms, biases, qk-norm
    scales), final norm and, untied, the output head."""
    per_layer = m.layer_matmul_params + 2 * m.d
    if m.qkv_bias:
        per_layer += m.q_width + 2 * m.kv_width
    if m.qk_norm:
        per_layer += 2 * m.head_dim
    head = 0 if m.tied else m.d * m.vocab
    return m.vocab * m.d + m.layers * per_layer + m.d + head


def kv_bytes_per_token(m: Dims) -> int:
    return m.layers * 2 * m.kv_width * m.dtype_bytes


def token_flops(m: Dims, ctx: int, logits: bool) -> int:
    """Model FLOPs of one token attending ``ctx`` live keys (itself
    included); ``logits`` adds the output head."""
    f = 2 * m.layers * m.layer_matmul_params \
        + 4 * m.layers * ctx * m.q_width
    return f + (2 * m.d * m.vocab if logits else 0)


def prefill_flops(m: Dims, start: int, length: int) -> int:
    """Model FLOPs of a prefill chunk of ``length`` tokens at positions
    ``start ..``: token ``p`` attends ``p + 1`` keys.  No logit row is
    counted (only the prompt's last token needs one)."""
    ctx_sum = length * start + length * (length + 1) // 2
    return (2 * m.layers * m.layer_matmul_params * length
            + 4 * m.layers * ctx_sum * m.q_width)


def decode_attention_bytes(m: Dims, ctxs: Iterable[int]) -> int:
    """Bytes decode attention must move for one step of rows at live
    lengths ``ctxs``: live K and V, the query and the output, per layer."""
    per_key = 2 * m.kv_width * m.dtype_bytes
    per_row = 2 * m.q_width * m.dtype_bytes
    return m.layers * sum(c * per_key + per_row for c in ctxs)


def decode_attention_flops(m: Dims, ctxs: Iterable[int]) -> int:
    return 4 * m.layers * m.q_width * sum(ctxs)
