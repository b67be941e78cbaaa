"""Operations and bytes of latent-attention (MLA) decode, from shapes and
live lengths alone, beside ``flops.py``.

In the absorbed form (DeepSeek-V2 §2.1) a decode row reads, per layer, its
live latents, ``ctx * (rank + rope)`` values shared by all heads, and moves
its query (``heads * (rank + rope)``) and output (``heads * rank``); each
head scores every latent (``rank + rope`` multiply-adds) and sums the
latents' first ``rank`` values (``rank`` more): ``2 * ctx * heads *
((rank + rope) + rank)`` FLOPs.  Every count depends only on the
configuration and the live lengths, never on how the program computes, so
any implementation of the same attention is read against the same need.
"""
from dataclasses import dataclass
from typing import Iterable

__all__ = ["MLADims", "mla_decode_bytes", "mla_decode_flops",
           "latent_bytes_per_token"]


@dataclass(frozen=True)
class MLADims:
    layers: int
    heads: int
    rank: int
    rope: int
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, hf: dict) -> "MLADims":
        return cls(layers=int(hf["num_hidden_layers"]),
                   heads=int(hf["num_attention_heads"]),
                   rank=int(hf["kv_lora_rank"]),
                   rope=int(hf["qk_rope_head_dim"]))

    @property
    def width(self) -> int:
        """Values cached per token and layer."""
        return self.rank + self.rope


def latent_bytes_per_token(m: MLADims) -> int:
    return m.layers * m.width * m.dtype_bytes


def mla_decode_bytes(m: MLADims, ctxs: Iterable[int]) -> int:
    """Bytes one decode step of rows at live lengths ``ctxs`` must move:
    per layer, each row's live latents, its query and its output."""
    per_row = m.heads * (m.width + m.rank) * m.dtype_bytes
    return m.layers * sum(c * m.width * m.dtype_bytes + per_row
                          for c in ctxs)


def mla_decode_flops(m: MLADims, ctxs: Iterable[int]) -> int:
    return 2 * m.layers * m.heads * (m.width + m.rank) * sum(ctxs)
