"""Latent attention (MLA) and the expert-share layer against the plain
float32 reference of ``bench/models/deepseek_v2.py``, at a small size on the
CPU: d 64, 4 heads, kv_lora_rank 32, rope 16, nope 32, v 32, 8 experts
top-2 of which 4 are held, 1 shared expert, 1 leading dense layer.  Every
comparison is on logits (or layer outputs), never on sampled tokens.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench.spec import load_module  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.models import attention as A  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.moe import moe_fwd  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402

DS = load_module(REPO / "bench" / "models" / "deepseek_v2.py", "model.ds")
SEED = 2 ** 33 + 21
#: engine logits against the reference's, largest absolute difference
#: (logits here are of order 4): the program in float32 differs from the
#: float32 reference only in summation order (absorbed vs expanded, chunk
#: by chunk), about 1e-5; the same program in bf16 is off by about 1e-2
ENGINE_TOL = 1e-3
#: the absorbed decode against the expanded attention, float32, outputs
#: of order 1: only the order of the sums differs
ABSORB_TOL = 1e-5


def tiny_hf(**over) -> dict:
    hf = json.loads(
        (REPO / "bench" / "configs" / "deepseek-v2-lite.json").read_text())
    hf.update(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
              num_key_value_heads=4, intermediate_size=128,
              moe_intermediate_size=32, n_shared_experts=1, vocab_size=512,
              kv_lora_rank=32, qk_rope_head_dim=16, qk_nope_head_dim=32,
              v_head_dim=32, n_routed_experts=4, num_experts_per_tok=2)
    hf["published"] = {"n_routed_experts": 8}
    hf["program"] = dict(hf["program"], use_flash=False, dtype="float32",
                         param_dtype="float32")
    hf.update(over)
    return hf


def _engine_logits(hf, use_flash=False):
    """Serve two prompts through the paged engine (chunked prefill over
    several chunks and blocks, then decode side by side) and return, per
    request, its prompt, its tokens and the logits every program call gave
    it, by position."""
    cfg = ModelConfig(**DS.program_config("tiny", hf)).replace(
        use_flash=use_flash)
    dt = jnp.dtype(cfg.dtype)
    params = jax.tree.map(lambda a: a.astype(dt) if a.dtype == jnp.bfloat16
                          else a, DS.program_params(hf, SEED))
    eng = ServingEngine(build_model(cfg), params, max_batch=2, s_max=128,
                        prefill_token_budget=64, kv_mode="paged",
                        block_size=16, prefill_chunk=32)
    seen = {}
    decode, chunk = eng._decode, eng._prefill_chunk

    def on_decode(*args):
        out = decode(*args)
        logits, pos = np.asarray(out[0], np.float32), np.asarray(args[4])
        for i, req in enumerate(eng.slot_req):
            if req is not None:
                seen.setdefault(req.rid, {})[int(pos[i])] = logits[i, -1]
        return out

    def on_chunk(*args):
        out = chunk(*args)
        toks, start = np.asarray(args[1]["tokens"][0]), int(args[4])
        for rid, prompt in eng.prompts.items():
            if np.array_equal(prompt[start:start + len(toks)], toks):
                seen.setdefault(rid, {})[start + len(toks) - 1] = \
                    np.asarray(out[0], np.float32)[0, -1]
        return out

    eng._decode, eng._prefill_chunk = on_decode, on_chunk
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, n, dtype=np.int32) for n in (70, 40)]
    reqs = [eng.submit(p, b) for p, b in zip(prompts, (9, 14))]
    eng.run_until_drained()
    return [(p, eng.outputs[r.rid], seen[r.rid]) for p, r in zip(prompts,
                                                                 reqs)]


def _worst_engine_error(hf, use_flash=False):
    worst = 0.0
    for prompt, out, by_pos in _engine_logits(hf, use_flash):
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        ref = DS.reference_logits(hf, SEED, seq)
        # the prompt's last position and every decoded one were compared
        assert set(range(len(prompt) - 1, len(seq))) <= set(by_pos)
        for pos, logits in by_pos.items():
            worst = max(worst, float(np.abs(logits - ref[pos]).max()))
    return worst


@pytest.mark.parametrize("use_flash", [False, True], ids=["xla", "kernel"])
def test_engine_through_the_latent_pool_agrees_with_the_reference(use_flash):
    """(a) chunked prefill then paged decode through the latent pool, the
    absorbed decode by XLA or by the Pallas kernel, against the expanded
    float32 reference's full forward pass."""
    assert _worst_engine_error(tiny_hf(), use_flash) < ENGINE_TOL


def test_lower_precision_fails_the_tolerance():
    """(d) the same comparison with the program in bf16 instead of the
    float32 the configuration states fails it."""
    hf = tiny_hf()
    hf["program"] = dict(hf["program"], dtype="bfloat16",
                         param_dtype="bfloat16")
    assert _worst_engine_error(hf) > ENGINE_TOL


def _mla_cfg(**over):
    return ModelConfig(**DS.program_config("tiny", tiny_hf())).replace(
        **over)


def test_absorbed_decode_equals_expanded_attention():
    """(b) one token at a time through the latent cache, absorbed, equals
    the expanded full-sequence attention with the same weights, on the
    contiguous and the paged cache and through the kernel."""
    cfg = _mla_cfg()
    p = A.init_attention(jax.random.PRNGKey(1), cfg, jnp.float32)
    s = 24
    x = jax.random.normal(jax.random.PRNGKey(2), (1, s, cfg.d_model))
    want = A.attention_fwd(p, x, cfg)
    cache = A.init_kv_cache(cfg, 1, 32, jnp.float32)
    for flash in (False, True):
        c = cfg.replace(use_flash=flash)
        pool = A.init_paged_kv_cache(c, 1, 5, 8, jnp.float32)
        table = jnp.asarray([[3, 1, 4, 2]], jnp.int32)
        dense = cache
        for t in range(s):
            got, dense = A.attention_decode(p, x[:, t:t + 1], dense,
                                            jnp.int32(t), c)
            paged, pool = A.attention_paged(
                p, x[:, t:t + 1], pool, 0, table,
                jnp.asarray([t], jnp.int32), c)
            for y in (got, paged):
                np.testing.assert_allclose(np.asarray(y[:, 0]),
                                           np.asarray(want[:, t]),
                                           atol=ABSORB_TOL, rtol=0)


def test_expert_shares_add_up_to_the_uncut_layer():
    """(c) the two disjoint shares of 4 experts, with the shared expert
    counted once, add up to the uncut reference layer over all 8."""
    hf = tiny_hf()
    whole = DS.Dims.from_config(dict(hf, n_routed_experts=8))
    w = DS.layer_weights(DS.root_key(SEED), 1, whole, True)
    w32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = jax.random.normal(jax.random.PRNGKey(4), (40, whole.d))
    with jax.default_matmul_precision("highest"):
        want = DS.moe_layer(w32, x, whole)
        parts = []
        for lo in (0, 4):
            cfg = _mla_cfg(experts_held=4, expert_offset=lo)
            p = {"router": {"w": w32["router"]},
                 "w_gate": w32["w_gate"][lo:lo + 4],
                 "w_up": w32["w_up"][lo:lo + 4],
                 "w_down": w32["w_down"][lo:lo + 4],
                 "shared": {n: {"w": w32["shared_" + n]}
                            for n in ("gate", "up", "down")}}
            parts.append(moe_fwd(p, x, cfg)[0])
        shared = DS.moe_layer(dict(w32, w_gate=w32["w_gate"][:0],
                                   w_up=w32["w_up"][:0],
                                   w_down=w32["w_down"][:0]),
                              x, DS.Dims.from_config(dict(
                                  hf, n_routed_experts=0)))
    got = parts[0] + parts[1] - shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=0)
    # each share alone is short of the whole: the routed parts differ
    assert float(jnp.abs(parts[0] - want).max()) > 1e-2
