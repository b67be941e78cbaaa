"""The benchmark's float32 reference against the program's paged chunked
prefill and decode, at a size the CPU holds, and its fp8 control."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.flops import Dims
from bench.spec import load_module
from benchutil import REPO, tiny_config

QWEN = load_module(REPO / "bench" / "models" / "qwen.py", "model.qwen")
#: limits on the widest logit gap at this size, between the program's
#: readings and the control's (the gap scales with the logits: the tied
#: head's rows have std 0.02, the untied head's 1/sqrt(d))
LIMIT = {"qwen2": 0.01, "qwen3": 0.15}
CONFIGS = {"qwen2": tiny_config("qwen2-1.5b"),
           "qwen3": tiny_config("qwen3-8b-l18")}


def _model(hf):
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    return build_model(ModelConfig(**QWEN.program_config("tiny", hf)))


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_program_params_have_the_programs_layout(name):
    hf = CONFIGS[name]
    mine = jax.eval_shape(lambda: QWEN.program_params(hf, 0))
    theirs = jax.eval_shape(_model(hf).init, jax.random.key(0))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_draws_the_programs_weights_layer_by_layer(name):
    """The reference draws layer i alone from the seed and gets the very
    values the program was given (so it takes nothing the program made)."""
    hf = CONFIGS[name]
    m = Dims.from_config(hf)
    seed = 2 ** 33 + 9
    p = QWEN.program_params(hf, seed)
    key = QWEN.root_key(seed)
    for i in range(m.layers):
        w = QWEN.layer_weights(key, i, m)
        assert np.array_equal(w["wq"], p["blocks"]["attn"]["wq"]["w"][i])
        assert np.array_equal(w["down"], p["blocks"]["mlp"]["down"]["w"][i])
        if m.qk_norm:
            assert np.array_equal(w["k_norm"],
                                  p["blocks"]["attn"]["k_norm"]["scale"][i])
    g = QWEN.global_weights(key, m)
    assert np.array_equal(g["embed"], p["embed"]["table"])
    assert not np.array_equal(
        QWEN.global_weights(QWEN.root_key(seed + 2 ** 32), m)["embed"],
        g["embed"])


def _serve(hf, seed):
    """Prompts through the program's paged engine: chunked prefill (two
    chunks), then decode at several depths side by side."""
    from repro.serving.engine import ServingEngine
    model = _model(hf)
    params = QWEN.program_params(hf, seed)
    eng = ServingEngine(model, params, max_batch=4, s_max=128,
                        prefill_token_budget=64, kv_mode="paged",
                        block_size=16, prefill_chunk=32)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, hf["vocab_size"], n, dtype=np.int32)
               for n in (32, 64, 96, 64)]
    reqs = [eng.submit(p, n) for p, n in zip(prompts, (30, 60, 31, 50))]
    eng.run_until_drained()
    served = [np.asarray(eng.outputs[r.rid], np.int32) for r in reqs]
    assert [len(s) for s in served] == [30, 60, 31, 50]
    return prompts, served


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_agrees_with_the_program_and_the_control_fails(name):
    hf = CONFIGS[name]
    seed = 77
    prompts, served = _serve(hf, seed)
    res = QWEN.reference_gaps(hf, seed, prompts, served, control=True)
    gap = max(float(r["gap"].max()) for r in res)
    control = max(float(r["control_gap"].max()) for r in res)
    assert all((r["gap"] >= 0).all() for r in res)
    assert gap <= LIMIT[name] < control
    assert control >= 3 * gap


def test_fp8_rounding_keeps_three_mantissa_bits():
    x = jnp.asarray([[1.0, 1.0625, 1.125, 1.1875, -448.0, 2.0 ** -9]],
                    jnp.float32).T
    # one scale for the column: 448 maps to 448, so values are unscaled
    q = np.asarray(QWEN._fp8(x, axis=0))[:, 0]
    assert list(q) == [1.0, 1.0, 1.125, 1.25, -448.0, 2.0 ** -9]
