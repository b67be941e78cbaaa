"""The DeepSeek-V2 configuration's benchmark files: its reference against
the program at a tiny size on the CPU (as ``test_bench_reference.py`` does
for qwen), the MLA counts by hand, and the two readers on a small synthetic
trace whose operations carry the kernels' names."""
import json

import jax
import numpy as np
import pytest

from bench import mla_flops
from bench.harness import Context
from bench.spec import Spec, load_module
from bench.trace import Trace
from benchutil import REPO

DS = load_module(REPO / "bench" / "models" / "deepseek_v2.py", "model.ds")
SPEC = Spec(REPO)
#: the widest logit gap at this size, between the readings: the program,
#: serving in bf16, reads about 0.03 (its bf16 weights and activations
#: against the float32 reference), the fp8 control about 0.7
LIMIT = 0.15


def tiny_config(**over) -> dict:
    """DeepSeek-V2-shaped and small: 1 dense + 2 MoE layers, 8 experts
    top-2 of which 4 are held, 1 shared expert."""
    hf = json.loads(
        (REPO / "bench" / "configs" / "deepseek-v2-lite.json").read_text())
    hf.update(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
              num_key_value_heads=4, intermediate_size=128,
              moe_intermediate_size=32, n_shared_experts=1, vocab_size=512,
              kv_lora_rank=32, qk_rope_head_dim=16, qk_nope_head_dim=32,
              v_head_dim=32, n_routed_experts=4, num_experts_per_tok=2)
    hf["published"] = {"n_routed_experts": 8}
    hf["program"] = dict(hf["program"], use_flash=False)
    hf.update(over)
    return hf


def _model(hf):
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    return build_model(ModelConfig(**DS.program_config("tiny", hf)))


def test_program_params_have_the_programs_layout():
    hf = tiny_config()
    mine = jax.eval_shape(lambda: DS.program_params(hf, 0))
    theirs = jax.eval_shape(_model(hf).init, jax.random.key(0))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_a_share_draws_the_whole_layers_experts():
    """Expert e's weights come from its global index: the share 4-7 draws
    what the whole layer draws for those experts."""
    hf = tiny_config()
    whole = DS.Dims.from_config(dict(hf, n_routed_experts=8))
    share = DS.Dims.from_config(dict(
        hf, program=dict(hf["program"], expert_offset=4)))
    key = DS.root_key(2 ** 33 + 1)
    a = DS.layer_weights(key, 1, whole, True)
    b = DS.layer_weights(key, 1, share, True)
    assert np.array_equal(a["w_down"][4:], b["w_down"])
    assert np.array_equal(a["router"], b["router"])


def test_reference_agrees_with_the_program_and_the_control_fails():
    """The paged engine (chunked prefill over several blocks, then decode
    side by side) against the float32 reference, in the bf16 the cell
    serves."""
    from repro.serving.engine import ServingEngine
    hf = tiny_config()
    seed = 2 ** 32 + 77
    eng = ServingEngine(_model(hf), DS.program_params(hf, seed), max_batch=3,
                        s_max=128, prefill_token_budget=64, kv_mode="paged",
                        block_size=16, prefill_chunk=32)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, hf["vocab_size"], n, dtype=np.int32)
               for n in (32, 96, 64)]
    reqs = [eng.submit(p, n) for p, n in zip(prompts, (30, 20, 40))]
    eng.run_until_drained()
    served = [np.asarray(eng.outputs[r.rid], np.int32) for r in reqs]
    res = DS.reference_gaps(hf, seed, prompts, served, control=True)
    gap = max(float(r["gap"].max()) for r in res)
    control = max(float(r["control_gap"].max()) for r in res)
    assert all((r["gap"] >= 0).all() for r in res)
    assert gap <= LIMIT < control, (gap, control)
    assert control >= 3 * gap


def test_random_model_does_not_collapse():
    """The seeded model's greedy tokens spread over the vocabulary: no few
    tokens with wide margins that would hide an error in the logits."""
    hf = tiny_config()
    prompt = np.random.default_rng(5).integers(0, 512, 300, dtype=np.int32)
    logits = DS.reference_logits(hf, 2 ** 31 + 9, prompt)
    top = np.argmax(logits, -1).tolist()
    assert len(set(top)) > 100
    assert max(top.count(t) for t in set(top)) < 0.05 * len(top)


def test_mla_counts_by_hand():
    m = mla_flops.MLADims.from_config(SPEC.config("deepseek-v2-lite"))
    assert (m.layers, m.heads, m.rank, m.rope, m.width) == \
        (27, 16, 512, 64, 576)
    assert mla_flops.latent_bytes_per_token(m) == 31_104 == 27 * 576 * 2
    ctxs = [1, 4096]
    latents = sum(ctxs) * 576 * 2
    q_and_out = len(ctxs) * 16 * (576 + 512) * 2
    assert mla_flops.mla_decode_bytes(m, ctxs) == 27 * (latents + q_and_out)
    assert mla_flops.mla_decode_flops(m, ctxs) == \
        2 * 27 * 16 * (576 + 512) * 4097


class _Trace:
    """A reduced trace of two decode runs and one prefill run on one chip,
    in nanoseconds, each run holding one MLA and one expert kernel op."""
    chips = [0]
    DEC, PRE = "jit_decode_step_paged(1)", "jit_prefill_chunk_paged(2)"

    def __init__(self):
        self.modules = {0: [(0, 100, self.DEC), (200, 300, self.PRE),
                            (400, 500, self.DEC)]}
        self.ops = {0: [(10, 40, "%mla_decode_pallas.3 = bf16[32,16,512]"),
                        (40, 60, "%grouped_swiglu_pallas.1 = bf16[8,32]"),
                        (60, 90, "%fusion.7 = bf16[32,2048]"),
                        (210, 290, "%grouped_swiglu_pallas.2 = bf16[8,512]"),
                        (410, 450, "%mla_decode_pallas.3 = bf16[32,16,512]"),
                        (450, 470, "%grouped_swiglu_pallas.1 = bf16[8,32]")]}
        self.labels = {n: n for *_, n in self.ops[0]}

    def window(self):
        return (0, 600)

    def runs(self, module):
        return sum(1 for *_, n in self.modules[0] if n == module)

    _module_at = Trace._module_at


def _ctx(trace):
    hf = SPEC.config("deepseek-v2-lite")
    return Context(cell={}, hf=hf, dims=None, peaks={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        window=(0.0, 1.0), steps=[], decode_calls=[(0.5, [1000, 3000])],
        prefill_calls=[], requests=[], trace=trace,
        programs={"decode": trace.DEC, "prefill": trace.PRE})


def test_readers_read_the_kernels_inside_the_decode_program():
    ctx = _ctx(_Trace())
    moe = SPEC.reader("moe_ms_per_step.longgen").read(ctx)
    # 20 + 20 ns of the expert kernel in two decode runs; the prefill's is
    # not counted
    assert moe == pytest.approx(20e-9 * 1e3)
    roof = SPEC.reader("mla_decode_roofline.longgen").read(ctx)
    m = mla_flops.MLADims.from_config(ctx.hf)
    need = mla_flops.mla_decode_bytes(m, [1000, 3000]) / 819e9
    assert roof == pytest.approx(100 * need / 70e-9)


def test_readers_give_nothing_without_the_kernels():
    tr = _Trace()
    tr.ops = {0: [(o[0], o[1], "%fusion.1 = f32[8]") for o in tr.ops[0]]}
    tr.labels = {"%fusion.1 = f32[8]": "%fusion.1 = f32[8]"}
    ctx = _ctx(tr)
    assert SPEC.reader("moe_ms_per_step.longgen").read(ctx) is None
    assert SPEC.reader("mla_decode_roofline.longgen").read(ctx) is None
    ctx.programs = {}
    assert SPEC.reader("moe_ms_per_step.longgen").read(ctx) is None
