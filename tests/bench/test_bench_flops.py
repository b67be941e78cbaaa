"""Shape-only operation and byte counts, and the peaks table, checked by
hand arithmetic."""
import json

import pytest

from bench import flops
from bench.spec import Spec
from benchutil import REPO

SPEC = Spec(REPO)
QWEN2 = flops.Dims.from_config(SPEC.config("qwen2-1.5b"))
#: qwen3-8b-l18 has no cell yet; its configuration file is read directly
QWEN3 = flops.Dims.from_config(json.loads(
    (REPO / "bench" / "configs" / "qwen3-8b-l18.json").read_text()))


def test_qwen2_params_and_kv_bytes():
    # embedding 151936*1536; per layer q/o 2*1536^2, k/v 2*1536*256,
    # biases 1536+2*256, MLP 3*1536*8960, two norms 2*1536; final norm
    per_layer = (2 * 1536 * 1536 + 2 * 1536 * 256 + 1536 + 512
                 + 3 * 1536 * 8960 + 2 * 1536)
    assert per_layer == 46_797_824
    assert flops.total_params(QWEN2) == 1_543_714_304 == \
        151936 * 1536 + 28 * per_layer + 1536
    assert flops.kv_bytes_per_token(QWEN2) == 28_672 == 28 * 2 * 256 * 2


def test_qwen3_l18_params_and_kv_bytes():
    per_layer = (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 12288
                 + 2 * 4096 + 2 * 128)
    assert flops.total_params(QWEN3) == \
        2 * 151936 * 4096 + 18 * per_layer + 4096 == 4_717_699_584
    assert flops.kv_bytes_per_token(QWEN3) == 73_728 == 18 * 2 * 1024 * 2


def test_token_flops_by_hand():
    m = QWEN2
    matmul = 2 * 1536 * 1536 + 2 * 1536 * 256 + 3 * 1536 * 8960
    assert m.layer_matmul_params == matmul
    ctx = 1000
    want = 2 * 28 * matmul + 4 * 28 * ctx * 12 * 128
    assert flops.token_flops(m, ctx, logits=False) == want
    assert flops.token_flops(m, ctx, logits=True) == \
        want + 2 * 1536 * 151936


def test_prefill_flops_is_the_sum_over_its_tokens():
    m = QWEN3
    start, n = 512, 256
    assert flops.prefill_flops(m, start, n) == sum(
        flops.token_flops(m, p + 1, logits=False)
        for p in range(start, start + n))


def test_decode_attention_bytes_and_flops_by_hand():
    m = QWEN2
    ctxs = [1, 4096]
    kv = sum(ctxs) * 2 * 256 * 2          # K and V, 2 kv heads x 128, bf16
    qo = len(ctxs) * 2 * 1536 * 2         # query and output rows, bf16
    assert flops.decode_attention_bytes(m, ctxs) == 28 * (kv + qo)
    assert flops.decode_attention_flops(m, ctxs) == \
        4 * 28 * 1536 * sum(ctxs)


def test_peaks_by_device_kind():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        flops.peaks(kind)
