"""DeepSeek-V2 (latent attention and DeepSeekMoE), for the benchmark.

The same three things as ``qwen.py``, from a configuration file's keys:
seeded weights drawn one layer at a time, ``program_params`` (those weights
in the program's parameter tree: ``dense_blocks`` for the leading dense
layers, ``blocks`` for the MoE layers, latent attention's ``wq``,
``wkv_a``, ``kv_norm``, ``wkv_b``, ``wo``), and ``reference_gaps``.

The reference follows the paper's equations (arXiv:2405.04434 §2.1-2.2,
and the released ``modeling_deepseek.py``): per token ``q = x W_q`` split
into a no-rotary and a rotary part per head; ``[c_kv || k_R] = x W_kva``,
``c_kv`` RMS-normed, ``[k_nope || v] = c_kv W_kvb`` per head; the rotary key
``k_R`` (one for all heads) and the queries' rotary part under YaRN rotary
embedding; causal softmax with scale ``(nope + rope)^-0.5 * mscale^2``;
then the MLP: a dense SwiGLU for the first ``first_k_dense_replace``
layers, else a softmax router over all ``n_routed`` experts, top-k greedy,
weights not renormalised (``norm_topk_prob`` false) times
``routed_scaling_factor``, the experts' SwiGLUs weighted and summed, plus
the shared experts' SwiGLU.  It is written in the *expanded* form (keys
and values per head), never the absorbed one the program decodes with, in
plain float32 ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``, blockwise over query rows so that it fits at the published
widths.  It imports nothing of the program.

Departures from the published model, the same in the program and here:

* the expert share: the configuration's ``n_routed_experts`` experts are
  held (those from ``program.expert_offset``) of the router's
  ``published.n_routed_experts``; a token's weight on an expert held
  elsewhere is left out, as on one chip of an expert-parallel deployment
  before the exchange;
* the weights are random (``qwen.py``'s draws; each expert's tensors from a
  key of its global index);
* the rotary pairs are in the rotate-half order (the checkpoint's
  interleaved order is a fixed permutation of the rotary weight columns).

``quant="fp8"`` is the control: every weight matrix (the experts' too) and
the embedding rounded to float8 e4m3 with a scale per output channel.
"""

import functools
import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.qwen import HEAD_ROWS, PAD, _draw, _fp8, root_key

__all__ = ["Dims", "program_config", "program_params", "layer_weights",
           "global_weights", "reference_logits", "reference_gaps",
           "moe_layer", "root_key"]

HI = jax.lax.Precision.HIGHEST
#: query rows attended at once by the reference
Q_ROWS = 256


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    nope: int
    rope: int
    v: int
    rank: int
    ff: int                 # dense layers' SwiGLU
    moe_ff: int             # one expert's SwiGLU
    shared_ff: int          # the shared experts' SwiGLU
    router: int             # experts the router scores
    held: int               # experts held here
    offset: int             # the first held expert
    top_k: int
    norm_topk: bool
    routed_scale: float
    first_dense: int
    vocab: int

    @classmethod
    def from_config(cls, hf: dict) -> "Dims":
        held = int(hf["n_routed_experts"])
        return cls(
            layers=int(hf["num_hidden_layers"]), d=int(hf["hidden_size"]),
            heads=int(hf["num_attention_heads"]),
            nope=int(hf["qk_nope_head_dim"]),
            rope=int(hf["qk_rope_head_dim"]), v=int(hf["v_head_dim"]),
            rank=int(hf["kv_lora_rank"]), ff=int(hf["intermediate_size"]),
            moe_ff=int(hf["moe_intermediate_size"]),
            shared_ff=int(hf["moe_intermediate_size"])
            * int(hf["n_shared_experts"]),
            router=int(hf.get("published", {}).get("n_routed_experts",
                                                    held)),
            held=held, offset=int(hf["program"].get("expert_offset", 0)),
            top_k=int(hf["num_experts_per_tok"]),
            norm_topk=bool(hf["norm_topk_prob"]),
            routed_scale=float(hf["routed_scaling_factor"]),
            first_dense=int(hf["first_k_dense_replace"]),
            vocab=int(hf["vocab_size"]))

    @property
    def q_width(self) -> int:
        return self.heads * (self.nope + self.rope)


def program_config(name: str, hf: dict) -> dict:
    """Keyword arguments of the program's ``ModelConfig``."""
    m = Dims.from_config(hf)
    if m.routed_scale != 1:
        raise ValueError("the program scales routed experts' weights by 1 "
                         f"only, not {m.routed_scale}")
    prog = hf["program"]
    ys = hf["rope_scaling"]
    return dict(name=name, family=prog["family"], num_layers=m.layers,
                d_model=m.d, num_heads=m.heads,
                num_kv_heads=int(hf["num_key_value_heads"]),
                head_dim=m.nope + m.rope, d_ff=m.ff, vocab_size=m.vocab,
                tie_embeddings=bool(hf["tie_word_embeddings"]),
                rope_theta=float(hf["rope_theta"]),
                norm_eps=float(hf["rms_norm_eps"]),
                kv_lora_rank=m.rank, qk_nope_head_dim=m.nope,
                qk_rope_head_dim=m.rope, v_head_dim=m.v,
                yarn_factor=float(ys["factor"]),
                yarn_original_max_position=int(
                    ys["original_max_position_embeddings"]),
                yarn_beta_fast=float(ys["beta_fast"]),
                yarn_beta_slow=float(ys["beta_slow"]),
                yarn_mscale=float(ys["mscale"]),
                yarn_mscale_all_dim=float(ys["mscale_all_dim"]),
                first_dense_layers=m.first_dense, num_experts=m.router,
                num_experts_per_tok=m.top_k, moe_d_ff=m.moe_ff,
                shared_expert_d_ff=m.shared_ff, experts_held=m.held,
                expert_offset=m.offset, moe_norm_topk=m.norm_topk,
                use_flash=bool(prog["use_flash"]), dtype=prog["dtype"],
                param_dtype=prog["param_dtype"])


# -- seeded weights ----------------------------------------------------------

def _attn_specs(m: Dims):
    return [("attn_norm", (m.d,), "norm", 0),
            ("wq", (m.d, m.q_width), "w", m.d),
            ("wkv_a", (m.d, m.rank + m.rope), "w", m.d),
            ("kv_norm", (m.rank,), "norm", 0),
            ("wkv_b", (m.rank, m.heads * (m.nope + m.v)), "w", m.rank),
            ("wo", (m.heads * m.v, m.d), "w", m.heads * m.v),
            ("mlp_norm", (m.d,), "norm", 0)]


def _swiglu_specs(prefix: str, m: Dims, ff: int):
    return [(prefix + "gate", (m.d, ff), "w", m.d),
            (prefix + "up", (m.d, ff), "w", m.d),
            (prefix + "down", (ff, m.d), "w", ff)]


def _draw_all(k, specs):
    return {name: _draw(jax.random.fold_in(k, j), shape, kind, fan)
            for j, (name, shape, kind, fan) in enumerate(specs)}


def layer_weights(key, i, m: Dims, moe: bool) -> Dict[str, jax.Array]:
    """Layer ``i``'s tensors (``i`` may be traced).  A MoE layer's experts
    are ``w_gate``/``w_up`` [held, d, f] and ``w_down`` [held, f, d]:
    expert ``e`` (global index) from its own key, so a share draws the same
    values as the whole layer does for those experts."""
    k = jax.random.fold_in(key, i + 1)
    if not moe:
        return _draw_all(k, _attn_specs(m) + _swiglu_specs("", m, m.ff))
    w = _draw_all(k, _attn_specs(m)
                  + _swiglu_specs("shared_", m, m.shared_ff)
                  + [("router", (m.d, m.router), "w", m.d)])
    ke = jax.random.fold_in(k, 1000)
    experts = [_draw_all(jax.random.fold_in(ke, e),
                         _swiglu_specs("", m, m.moe_ff))
               for e in range(m.offset, m.offset + m.held)]
    for name in ("gate", "up", "down"):
        w["w_" + name] = jnp.stack([x[name] for x in experts])
    return w


def global_weights(key, m: Dims) -> Dict[str, jax.Array]:
    k = jax.random.fold_in(key, 0)
    return {"embed": _draw(jax.random.fold_in(k, 0), (m.vocab, m.d),
                           "embed", 0),
            "final_norm": _draw(jax.random.fold_in(k, 1), (m.d,), "norm", 0),
            "head": _draw(jax.random.fold_in(k, 2), (m.d, m.vocab), "w",
                          m.d)}


def _swiglu_tree(w: Dict[str, jax.Array], prefix: str = "") -> dict:
    return {n: {"w": w[prefix + n]} for n in ("gate", "up", "down")}


def _block_tree(ly: Dict[str, jax.Array], moe: bool) -> dict:
    attn = {n: {"w": ly[n]} for n in ("wq", "wkv_a", "wkv_b", "wo")}
    attn["kv_norm"] = {"scale": ly["kv_norm"]}
    block = {"ln1": {"scale": ly["attn_norm"]}, "attn": attn,
             "ln2": {"scale": ly["mlp_norm"]}}
    if not moe:
        block["mlp"] = _swiglu_tree(ly)
        return block
    block["moe"] = {"router": {"w": ly["router"].astype(jnp.float32)},
                    "w_gate": ly["w_gate"], "w_up": ly["w_up"],
                    "w_down": ly["w_down"],
                    "shared": _swiglu_tree(ly, "shared_")}
    return block


def program_params(hf: dict, seed: int):
    """The program's parameter tree, made on the default device by one
    jitted call from the seed."""
    m = Dims.from_config(hf)

    def build(key):
        g = global_weights(key, m)
        p = {"embed": {"table": g["embed"]},
             "ln_f": {"scale": g["final_norm"]},
             "lm_head": {"w": g["head"]}}
        stacks = [("blocks", m.first_dense, m.layers, True)]
        if m.first_dense:
            stacks.insert(0, ("dense_blocks", 0, m.first_dense, False))
        for name, lo, hi, moe in stacks:
            # one layer at a time: the draw's temporaries stay one layer big
            ly = jax.lax.map(lambda i, moe=moe: layer_weights(key, i, m, moe),
                             jnp.arange(lo, hi))
            p[name] = _block_tree(ly, moe)
        return p

    return jax.jit(build)(root_key(seed))


# -- the plain reference -----------------------------------------------------

def _f32(w: Dict[str, jax.Array], quant: Optional[str]):
    out = {}
    for name, x in w.items():
        x = x.astype(jnp.float32)
        if quant == "fp8" and x.ndim >= 2:
            x = _fp8(x, axis=-2)             # [.., in, out]: a scale per out
        out[name] = x
    return out


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, cos, sin):
    """x: [S, heads, n]; cos, sin: [S, n/2] (rotate-half form)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _yarn_m(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_tables(n: int, hf: dict, m: Dims):
    """YaRN rotary tables in float64 on the host (the released
    ``DeepseekV2YarnRotaryEmbedding``)."""
    ys = hf["rope_scaling"]
    dim, base = m.rope, float(hf["rope_theta"])
    factor, orig = float(ys["factor"]), ys["original_max_position_embeddings"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    lo = max(math.floor(turns(ys["beta_fast"])), 0)
    hi = min(math.ceil(turns(ys["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    mscale = _yarn_m(factor, ys["mscale"]) / _yarn_m(factor,
                                                    ys["mscale_all_dim"])
    ang = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * mscale, jnp.float32),
            jnp.asarray(np.sin(ang) * mscale, jnp.float32))


def _softmax_scale(hf: dict, m: Dims) -> float:
    ys = hf["rope_scaling"]
    mm = _yarn_m(float(ys["factor"]), float(ys["mscale_all_dim"]))
    return (m.nope + m.rope) ** -0.5 * mm * mm


def _swiglu(x, wg, wu, wd):
    return jnp.dot(jax.nn.silu(jnp.dot(x, wg, precision=HI))
                   * jnp.dot(x, wu, precision=HI), wd, precision=HI)


def _attention(w, x, cos, sin, *, m: Dims, eps: float, scale: float):
    """Latent attention of one whole sequence x: [S, d] (normed), expanded:
    per-head keys and values rebuilt from the latents."""
    s = x.shape[0]
    q = jnp.dot(x, w["wq"], precision=HI).reshape(s, m.heads, -1)
    ckv = jnp.dot(x, w["wkv_a"], precision=HI)
    c = _rms(ckv[:, :m.rank], w["kv_norm"], eps)
    kv = jnp.dot(c, w["wkv_b"], precision=HI).reshape(s, m.heads, -1)
    q = jnp.concatenate([q[..., :m.nope], _rope(q[..., m.nope:], cos, sin)],
                        -1)
    k_rope = _rope(ckv[:, None, m.rank:], cos, sin)
    k = jnp.concatenate([kv[..., :m.nope],
                         jnp.broadcast_to(k_rope, (s, m.heads, m.rope))], -1)
    v = kv[..., m.nope:]

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_ROWS, Q_ROWS)
        sc = jnp.einsum("qhd,thd->hqt", qb, k, precision=HI) * scale
        causal = jnp.arange(s)[None, :] <= (i * Q_ROWS
                                            + jnp.arange(Q_ROWS))[:, None]
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thv->qhv", p, v, precision=HI)

    att = jax.lax.map(rows, jnp.arange(s // Q_ROWS)).reshape(s, -1)
    return jnp.dot(att, w["wo"], precision=HI)


def moe_layer(w, x, m: Dims) -> jax.Array:
    """The routed experts held here (``m.offset`` .., ``m.held`` of them)
    and the shared experts, over tokens x: [T, d] (normed)."""
    probs = jax.nn.softmax(jnp.dot(x, w["router"], precision=HI), axis=-1)
    top, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        top = top / top.sum(-1, keepdims=True)
    top = top * m.routed_scale
    mine = jnp.arange(m.offset, m.offset + m.held)
    weight = jnp.sum(jnp.where(idx[:, :, None] == mine, top[:, :, None], 0.0),
                     axis=1)                              # [T, held]
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, w["w_gate"], precision=HI)) \
        * jnp.einsum("td,edf->etf", x, w["w_up"], precision=HI)
    y = jnp.einsum("etf,efd->etd", h, w["w_down"], precision=HI)
    routed = jnp.einsum("te,etd->td", weight, y, precision=HI)
    return routed + _swiglu(x, w["shared_gate"], w["shared_up"],
                            w["shared_down"])


def _layer_fwd(w, h, cos, sin, *, m: Dims, eps: float, scale: float,
               moe: bool):
    h = h + _attention(w, _rms(h, w["attn_norm"], eps), cos, sin, m=m,
                       eps=eps, scale=scale)
    x = _rms(h, w["mlp_norm"], eps)
    if moe:
        return h + moe_layer(w, x, m)
    return h + _swiglu(x, w["gate"], w["up"], w["down"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _embed_table(key, m: Dims, quant: Optional[str]):
    table = global_weights(key, m)["embed"].astype(jnp.float32)
    return _fp8(table, axis=1) if quant == "fp8" else table


@functools.partial(jax.jit, static_argnums=(1, 2))
def _head_weights(key, m: Dims, quant: Optional[str]):
    g = global_weights(key, m)
    w = g["head"].astype(jnp.float32)
    return (g["final_norm"].astype(jnp.float32),
            _fp8(w, axis=0) if quant == "fp8" else w)


def _last_hidden(hf: dict, key, seqs: Sequence[np.ndarray],
                 quant: Optional[str]) -> List[jax.Array]:
    """Every sequence through every layer (layer-outer: each layer's
    weights are drawn and upcast once); final hidden states [S_padded, d]."""
    m = Dims.from_config(hf)
    eps, scale = float(hf["rms_norm_eps"]), _softmax_scale(hf, m)
    table = _embed_table(key, m, quant)
    lens = [-(-len(t) // PAD) * PAD for t in seqs]
    cos, sin = _rope_tables(max(lens), hf, m)
    hs = [jnp.take(table, jnp.asarray(np.pad(t, (0, n - len(t)))), axis=0)
          for t, n in zip(seqs, lens)]
    del table
    for i in range(m.layers):
        moe = i >= m.first_dense
        w = _draw_layer(key, i, m, moe, quant)
        fwd = _fwd_fn(m, eps, scale, moe)
        hs = [fwd(w, h, cos[:h.shape[0]], sin[:h.shape[0]]) for h in hs]
        del w
    return hs


@functools.lru_cache(maxsize=None)
def _draw_fn(m: Dims, moe: bool, quant: Optional[str]):
    return jax.jit(lambda k, i: _f32(layer_weights(k, i, m, moe), quant))


def _draw_layer(key, i, m, moe, quant):
    return _draw_fn(m, moe, quant)(key, i)


@functools.lru_cache(maxsize=None)
def _fwd_fn(m: Dims, eps: float, scale: float, moe: bool):
    return jax.jit(lambda w, h, c, s: _layer_fwd(w, h, c, s, m=m, eps=eps,
                                                 scale=scale, moe=moe))


def _head(rows, final_norm, out_w, idx, *, eps):
    logits = jnp.dot(_rms(rows, final_norm, eps), out_w, precision=HI)
    return (logits.max(-1), jnp.argmax(logits, -1).astype(jnp.int32),
            jnp.take_along_axis(logits, idx, axis=1))


def reference_logits(hf: dict, seed: int, tokens: np.ndarray) -> np.ndarray:
    """The float32 reference's logits [S, V] of one whole sequence (the
    CPU tests compare the program's logits with these)."""
    m = Dims.from_config(hf)
    key = root_key(seed)
    with jax.default_matmul_precision("highest"):
        h = _last_hidden(hf, key, [np.asarray(tokens, np.int32)], None)[0]
        final, out_w = _head_weights(key, m, None)
        logits = jnp.dot(_rms(h[:len(tokens)], final,
                              float(hf["rms_norm_eps"])), out_w, precision=HI)
    return np.asarray(logits)


def _score(hf, key, seqs, firsts, targets, quant):
    """For each sequence: (best logit, argmax, logits at ``targets``) at
    its scored rows ``firsts[i] ..``, ``len(targets[i])`` of them."""
    m = Dims.from_config(hf)
    eps = float(hf["rms_norm_eps"])
    hs = _last_hidden(hf, key, seqs, quant)
    rows = [h[f:f + t.shape[0]] for h, f, t in zip(hs, firsts, targets)]
    del hs
    final, out_w = _head_weights(key, m, quant)
    head = jax.jit(lambda r, f, w, i: _head(r, f, w, i, eps=eps))
    res = []
    for r, t in zip(rows, targets):
        n = r.shape[0]
        pad = -(-n // HEAD_ROWS) * HEAD_ROWS
        rp = jnp.pad(r, ((0, pad - n), (0, 0)))
        tp = jnp.asarray(np.pad(t, ((0, pad - n), (0, 0))))
        parts = [head(rp[j:j + HEAD_ROWS], final, out_w, tp[j:j + HEAD_ROWS])
                 for j in range(0, pad, HEAD_ROWS)]
        res.append(tuple(np.concatenate([np.asarray(p[x]) for p in parts])[:n]
                         for x in range(3)))
    return res


def reference_gaps(hf: dict, seed: int, prompts: Sequence[np.ndarray],
                   served: Sequence[np.ndarray], control: bool = False
                   ) -> List[Dict[str, np.ndarray]]:
    """Teacher-force each prompt with its served tokens through the
    reference; ``gap`` and ``control_gap`` as in ``qwen.reference_gaps``."""
    key = root_key(seed)
    seqs = [np.concatenate([p, s[:-1]]).astype(np.int32)
            for p, s in zip(prompts, served)]
    firsts = [len(p) - 1 for p in prompts]
    targets = [np.asarray(s, np.int32)[:, None] for s in served]
    with jax.default_matmul_precision("highest"):
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
