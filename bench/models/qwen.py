"""Qwen2 and Qwen3 dense decoders, for the benchmark.

Three things, all from a configuration file's published keys:

* seeded weights (``layer_weights``, ``global_weights``): every tensor is
  drawn from its own key, folded from ``--seed``, the layer index and the
  tensor's index, in the served type (bf16).  So the reference can draw
  layer ``i`` alone and get the very values the program was given;
* ``program_params``: those weights placed in the program's parameter tree
  (``repro.models.transformer``: layer-stacked ``blocks``), made on the
  device in one jitted call;
* ``reference_gaps``: the plain float32 forward of the published
  architecture, run layer by layer over whole sequences, and for each
  served token the gap by which its reference logit lies below the
  reference's best.

The reference follows the published description (Qwen2: arXiv:2407.10671;
Qwen3: hf Qwen/Qwen3-8B): RMSNorm, rotary embedding (rotate-half form,
``rope_theta``), grouped-query causal attention with q/k/v biases (Qwen2)
or per-head q/k RMSNorm before the rotation (Qwen3), SwiGLU MLP, final
RMSNorm and a tied or untied output head.  It imports nothing of the
program and takes none of its arrays: it draws its own weights from the
seed, upcasts them to float32 one layer at a time, and computes every
matmul at ``Precision.HIGHEST``.  The rotary angles are computed in
float64 on the host.

``quant="fp8"`` is the control: the same forward with every weight matrix
(and the embedding table) rounded to float8 e4m3 with a scale per output
channel.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import Dims
from bench.traffic import seed_words

__all__ = ["program_config", "program_params", "layer_weights",
           "global_weights", "reference_gaps", "root_key"]

HI = jax.lax.Precision.HIGHEST
#: reference sequences are padded up to a multiple of this (few shapes)
PAD = 1024
#: rows of logits computed at once in the head
HEAD_ROWS = 128


def program_config(name: str, hf: dict) -> dict:
    """Keyword arguments of the program's ``ModelConfig``."""
    m = Dims.from_config(hf)
    prog = hf["program"]
    return dict(name=name, family=prog["family"], num_layers=m.layers,
                d_model=m.d, num_heads=m.heads, num_kv_heads=m.kv_heads,
                head_dim=m.head_dim, d_ff=m.ff, vocab_size=m.vocab,
                qkv_bias=m.qkv_bias, qk_norm=m.qk_norm,
                tie_embeddings=m.tied, rope_theta=float(hf["rope_theta"]),
                norm_eps=float(hf["rms_norm_eps"]),
                use_flash=bool(prog["use_flash"]), dtype=prog["dtype"],
                param_dtype=prog["param_dtype"])


# -- seeded weights ----------------------------------------------------------

def root_key(seed: int):
    lo, hi = seed_words(seed)
    return jax.random.fold_in(jax.random.key(lo), hi)


def _layer_specs(m: Dims):
    """(name, shape, kind, fan_in) of one layer's tensors, in key order."""
    specs = [("attn_norm", (m.d,), "norm", 0),
             ("wq", (m.d, m.q_width), "w", m.d),
             ("wk", (m.d, m.kv_width), "w", m.d),
             ("wv", (m.d, m.kv_width), "w", m.d),
             ("wo", (m.q_width, m.d), "w", m.q_width),
             ("mlp_norm", (m.d,), "norm", 0),
             ("gate", (m.d, m.ff), "w", m.d),
             ("up", (m.d, m.ff), "w", m.d),
             ("down", (m.ff, m.d), "w", m.ff)]
    if m.qkv_bias:
        specs += [("bq", (m.q_width,), "bias", 0),
                  ("bk", (m.kv_width,), "bias", 0),
                  ("bv", (m.kv_width,), "bias", 0)]
    if m.qk_norm:
        specs += [("q_norm", (m.head_dim,), "norm", 0),
                  ("k_norm", (m.head_dim,), "norm", 0)]
    return specs


def _draw(key, shape, kind: str, fan_in: int) -> jax.Array:
    """Uniform draws (exact in any order of evaluation), scaled: linear
    weights to variance 1/fan_in, the embedding to std 0.02, biases to std
    0.05, norm scales to 1 +- 0.2.  Larger biases make a deep random model
    collapse: the value bias survives attention's averaging over a long
    context, every position's output turns into the same few tokens, and
    their margins hide any error in the logits."""
    u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
    if kind == "w":
        x = u * math.sqrt(3.0 / fan_in)
    elif kind == "embed":
        x = u * (0.02 * math.sqrt(3.0))
    elif kind == "bias":
        x = u * (0.05 * math.sqrt(3.0))
    elif kind == "norm":
        x = 1.0 + 0.2 * u
    else:
        raise ValueError(kind)
    return x.astype(jnp.bfloat16)


def layer_weights(key, i, m: Dims) -> Dict[str, jax.Array]:
    """Layer ``i``'s tensors (``i`` may be traced)."""
    k = jax.random.fold_in(key, i + 1)
    return {name: _draw(jax.random.fold_in(k, j), shape, kind, fan)
            for j, (name, shape, kind, fan) in enumerate(_layer_specs(m))}


def global_weights(key, m: Dims) -> Dict[str, jax.Array]:
    k = jax.random.fold_in(key, 0)
    out = {"embed": _draw(jax.random.fold_in(k, 0), (m.vocab, m.d),
                          "embed", 0),
           "final_norm": _draw(jax.random.fold_in(k, 1), (m.d,), "norm", 0)}
    if not m.tied:
        out["head"] = _draw(jax.random.fold_in(k, 2), (m.d, m.vocab), "w",
                            m.d)
    return out


def program_params(hf: dict, seed: int):
    """The program's parameter tree, made on the default device by one
    jitted call from the seed."""
    m = Dims.from_config(hf)

    def build(key):
        g = global_weights(key, m)
        # one layer at a time, so the draw's temporaries stay one layer big
        ly = jax.lax.map(lambda i: layer_weights(key, i, m),
                         jnp.arange(m.layers))
        attn = {"wq": {"w": ly["wq"]}, "wk": {"w": ly["wk"]},
                "wv": {"w": ly["wv"]}, "wo": {"w": ly["wo"]}}
        if m.qkv_bias:
            for n in ("q", "k", "v"):
                attn["w" + n]["b"] = ly["b" + n]
        if m.qk_norm:
            attn["q_norm"] = {"scale": ly["q_norm"]}
            attn["k_norm"] = {"scale": ly["k_norm"]}
        blocks = {"ln1": {"scale": ly["attn_norm"]}, "attn": attn,
                  "ln2": {"scale": ly["mlp_norm"]},
                  "mlp": {"gate": {"w": ly["gate"]}, "up": {"w": ly["up"]},
                          "down": {"w": ly["down"]}}}
        p = {"embed": {"table": g["embed"]}, "blocks": blocks,
             "ln_f": {"scale": g["final_norm"]}}
        if not m.tied:
            p["lm_head"] = {"w": g["head"]}
        return p

    return jax.jit(build)(root_key(seed))


# -- the plain reference -----------------------------------------------------

def _fp8(w: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 (3 mantissa bits, subnormals below 2**-6) with
    one scale per slice along ``axis`` mapping its largest magnitude to 448,
    the format's largest finite value."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    x = w / s
    mant, ex = jnp.frexp(x)
    normal = jnp.ldexp(jnp.round(mant * 16.0) / 16.0, ex)
    sub = jnp.round(x * 512.0) / 512.0
    return jnp.where(jnp.abs(x) < 2.0 ** -6, sub, normal) * s


def _f32_layer(w: Dict[str, jax.Array], quant: Optional[str]):
    out = {}
    for name, x in w.items():
        x = x.astype(jnp.float32)
        if quant == "fp8" and x.ndim == 2:
            x = _fp8(x, axis=0)               # [in, out]: one scale per out
        out[name] = x
    return out


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, cos, sin):
    """x: [S, heads, hd]; cos, sin: [S, hd/2] (rotate-half form)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer_fwd(w, h, cos, sin, *, m: Dims, eps: float):
    """One decoder layer over one whole sequence h: [S, d] float32."""
    s = h.shape[0]
    x = _rms(h, w["attn_norm"], eps)
    q = jnp.dot(x, w["wq"], precision=HI)
    k = jnp.dot(x, w["wk"], precision=HI)
    v = jnp.dot(x, w["wv"], precision=HI)
    if m.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = q.reshape(s, m.heads, m.head_dim)
    k = k.reshape(s, m.kv_heads, m.head_dim)
    v = v.reshape(s, m.kv_heads, m.head_dim)
    if m.qk_norm:
        q = _rms(q, w["q_norm"], eps)
        k = _rms(k, w["k_norm"], eps)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    g = m.heads // m.kv_heads
    q = q.reshape(s, m.kv_heads, g, m.head_dim)
    scores = jnp.einsum("skgd,tkd->kgst", q, k, precision=HI) \
        / math.sqrt(m.head_dim)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("kgst,tkd->skgd", p, v, precision=HI)
    h = h + jnp.dot(att.reshape(s, m.q_width), w["wo"], precision=HI)
    x = _rms(h, w["mlp_norm"], eps)
    gate = jnp.dot(x, w["gate"], precision=HI)
    up = jnp.dot(x, w["up"], precision=HI)
    return h + jnp.dot(jax.nn.silu(gate) * up, w["down"], precision=HI)


def _head_block(rows, final_norm, out_w, idx, *, eps, tied):
    """rows: [n, d] last hidden states.  Returns (best logit [n], argmax
    [n], logits at ``idx`` [n, k])."""
    x = _rms(rows, final_norm, eps)
    if tied:
        logits = jnp.dot(x, out_w.T, precision=HI)
    else:
        logits = jnp.dot(x, out_w, precision=HI)
    return (logits.max(-1), jnp.argmax(logits, -1).astype(jnp.int32),
            jnp.take_along_axis(logits, idx, axis=1))


def _rope_tables(n: int, m: Dims, theta: float):
    inv = 1.0 / theta ** (np.arange(0, m.head_dim, 2, dtype=np.float64)
                          / m.head_dim)
    ang = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _last_hidden(hf: dict, key, seqs: Sequence[np.ndarray],
                 quant: Optional[str]) -> List[jax.Array]:
    """Run every sequence through every layer (layer-outer, so each
    layer's weights are drawn and upcast once) and return the final
    hidden states [S_padded, d] of each."""
    m = Dims.from_config(hf)
    eps = float(hf["rms_norm_eps"])
    table = _embed_table(key, m, quant)
    lens = [-(-len(t) // PAD) * PAD for t in seqs]
    cos, sin = _rope_tables(max(lens), m, float(hf["rope_theta"]))
    hs = [jnp.take(table, jnp.asarray(np.pad(t, (0, n - len(t)))), axis=0)
          for t, n in zip(seqs, lens)]
    del table
    draw = jax.jit(lambda k, i: _f32_layer(layer_weights(k, i, m), quant))
    fwd = jax.jit(lambda w, h, c, s: _layer_fwd(w, h, c, s, m=m, eps=eps))
    for i in range(m.layers):
        w = draw(key, i)
        hs = [fwd(w, h, cos[:h.shape[0]], sin[:h.shape[0]]) for h in hs]
        del w
    return hs


@functools.partial(jax.jit, static_argnums=(1, 2))
def _embed_table_jit(key, m: Dims, quant: Optional[str]):
    table = global_weights(key, m)["embed"].astype(jnp.float32)
    return _fp8(table, axis=1) if quant == "fp8" else table


def _embed_table(key, m: Dims, quant: Optional[str]):
    """The embedding table in float32 (fp8-rounded per token row)."""
    return _embed_table_jit(key, m, quant)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _head_weights_jit(key, m: Dims, quant: Optional[str]):
    g = global_weights(key, m)
    final = g["final_norm"].astype(jnp.float32)
    if m.tied:
        w = g["embed"].astype(jnp.float32)
        return final, (_fp8(w, axis=1) if quant == "fp8" else w)
    w = g["head"].astype(jnp.float32)
    return final, (_fp8(w, axis=0) if quant == "fp8" else w)


def _head_weights(key, m: Dims, quant: Optional[str]):
    """Final norm scale and output weights in float32."""
    return _head_weights_jit(key, m, quant)


def _score(hf, key, seqs, firsts, targets, quant):
    """For each sequence: (best logit, argmax, logits at ``targets``) at
    its scored rows ``firsts[i] ..``, ``len(targets[i])`` of them."""
    m = Dims.from_config(hf)
    eps = float(hf["rms_norm_eps"])
    hs = _last_hidden(hf, key, seqs, quant)
    rows = [h[f:f + t.shape[0]] for h, f, t in zip(hs, firsts, targets)]
    del hs
    final, out_w = _head_weights(key, m, quant)
    head = jax.jit(lambda r, f, w, i: _head_block(r, f, w, i, eps=eps,
                                                  tied=m.tied))
    res = []
    for r, t in zip(rows, targets):
        n = r.shape[0]
        pad = -(-n // HEAD_ROWS) * HEAD_ROWS
        rp = jnp.pad(r, ((0, pad - n), (0, 0)))
        tp = jnp.asarray(np.pad(t, ((0, pad - n), (0, 0))))
        parts = [head(rp[j:j + HEAD_ROWS], final, out_w, tp[j:j + HEAD_ROWS])
                 for j in range(0, pad, HEAD_ROWS)]
        best = np.concatenate([np.asarray(p[0]) for p in parts])[:n]
        arg = np.concatenate([np.asarray(p[1]) for p in parts])[:n]
        at = np.concatenate([np.asarray(p[2]) for p in parts])[:n]
        res.append((best, arg, at))
    return res


def reference_gaps(hf: dict, seed: int, prompts: Sequence[np.ndarray],
                   served: Sequence[np.ndarray], control: bool = False
                   ) -> List[Dict[str, np.ndarray]]:
    """Teacher-force each prompt with its served tokens through the
    reference.  For every served token ``t`` at its position: ``gap`` =
    best reference logit - reference logit of ``t`` (>= 0; 0 where the
    program chose the reference's argmax).  With ``control``, the same
    positions are also run through the fp8 reference, and ``control_gap``
    is the reference's gap of the token the fp8 forward puts first."""
    key = root_key(seed)
    seqs = [np.concatenate([p, s[:-1]]).astype(np.int32)
            for p, s in zip(prompts, served)]
    firsts = [len(p) - 1 for p in prompts]
    targets = [np.asarray(s, np.int32)[:, None] for s in served]
    if control:
        ctl = _score(hf, key, seqs, firsts, targets, "fp8")
        targets = [np.concatenate([t, c[1][:, None]], axis=1)
                   for t, c in zip(targets, ctl)]
    ref = _score(hf, key, seqs, firsts, targets, None)
    out = []
    for best, _, at in ref:
        d = {"gap": best - at[:, 0]}
        if control:
            d["control_gap"] = best - at[:, 1]
        out.append(d)
    return out
