"""Trace reduction, on a small trace recorded on a TPU v5e: two steps of a
two-layer model, each a chunked prefill and a paged flash decode, inside
the benchmark's spans.  Reading it needs only ``jax.profiler``: no chip,
no topology."""
from pathlib import Path

import pytest

from bench.trace import (Trace, clip, merge, overlap, program_name,
                         short_name, total)

DATA = Path(__file__).parent / "data" / "small.xplane.pb"
FLASH = "flash_attention_pallas"
#: the trace's two engine programs, as its XLA Modules line names them
DECODE = "jit__lambda(16406277780530319909)"
PREFILL = "jit__lambda(11105658852612467958)"


@pytest.fixture(scope="module")
def tr():
    return Trace(DATA)


def test_interval_arithmetic_by_hand():
    assert merge([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert total([(0, 4), (5, 6)]) == 5
    assert overlap([(0, 4), (5, 6)], [(3, 5.5)]) == 1.5
    assert overlap([], [(0, 1)]) == 0
    assert clip([(0, 4), (5, 6)], (2, 5.5)) == [(2, 4), (5, 5.5)]


def test_short_names():
    assert short_name("%fusion.152 = bf16[8] fusion(%a), kind=kLoop") == \
        "fusion.152"
    assert program_name(DECODE) == "jit__lambda"
    assert short_name("%flash_attention_pallas.9 = bf16[32] custom-call()") \
        == "flash_attention_pallas.9"


def test_planes_spans_and_window(tr):
    assert tr.chips == [0]
    assert {k: len(v) for k, v in tr.spans.items()} == {
        "bench.window": 1, "bench.step": 2, "bench.prefill": 2,
        "bench.decode": 2}
    w = tr.window()
    for name in ("bench.step", "bench.prefill", "bench.decode"):
        for a, b in tr.spans[name]:
            assert w[0] <= a <= b <= w[1]
    assert (w[1] - w[0]) * 1e-9 == pytest.approx(0.057091165)


def test_busy_time_and_its_parts(tr):
    w = tr.window()
    busy = tr.busy_s(w)
    assert busy == pytest.approx(4.08042e-4, rel=1e-6)
    # two calls of each kind: the programs that ran twice and took the most
    # device time, decode first
    progs = tr.assign({"decode": 2, "prefill": 2})
    assert progs == {"decode": DECODE, "prefill": PREFILL}
    assert tr.runs(DECODE) == tr.runs(PREFILL) == 2
    dec, pre = tr.program_time_s(DECODE), tr.program_time_s(PREFILL)
    assert dec == pytest.approx(2.43076e-4, rel=1e-5)
    assert pre == pytest.approx(1.64342e-4, rel=1e-5)
    # the two programs are nearly all of the window's device time
    assert dec + pre <= busy * (1 + 1e-6)
    assert dec + pre == pytest.approx(busy, rel=0.05)
    # a kind whose count no program matches is left out
    assert tr.assign({"decode": 2, "prefill": 7}) == {"decode": DECODE}


def test_kernel_time(tr):
    w = tr.window()
    flash = tr.op_time_s(lambda label: FLASH in label, w)
    assert flash == pytest.approx(1.05029e-4, rel=1e-6)
    # the kernel runs only in decode (prefill takes the masked XLA path)
    assert flash <= tr.program_time_s(DECODE)
    assert tr.op_time_s(lambda label: "no such kernel" in label, w) == 0


def test_breakdown(tr):
    w = tr.window()
    ops = tr.top_ops(w, tr.assign({"decode": 2, "prefill": 2}))
    assert len(ops) == 10 and ops[0][0].startswith("decode:" + FLASH)
    assert {name.split(":")[0] for name, _ in ops} <= {
        "decode", "prefill", "jit_dynamic_slice", "jit_squeeze",
        "jit_convert_element_type", "jit_add", "none"}
    assert tr.top_ops(w)[0][0].startswith("jit__lambda:" + FLASH)
    assert all(" " not in name and ":while" not in name for name, _ in ops)
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    gaps = tr.idle_gaps(w)
    assert {name for name, _ in gaps} <= {"bench.decode", "bench.prefill",
                                          "bench.step", "host:none"}
    idle = sum(t for _, t in gaps)
    assert idle + tr.busy_s(w) == pytest.approx((w[1] - w[0]) * 1e-9,
                                                rel=1e-6)
