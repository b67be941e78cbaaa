"""Traffic generation and finding things by name, for the chip benchmark."""
import json
from collections import Counter

import numpy as np
import pytest

from bench.spec import Spec
from bench.traffic import Traffic, quantile_lengths, seed_words
from benchutil import REPO, make_root

SPEC = Spec(REPO)
CELLS = [w["name"] for w in SPEC.bench["workloads"]]
BIG = 2 ** 31 + 12345


def traffic(workload: str, seed: int, seconds: float = 40.0) -> Traffic:
    wl = SPEC.workload(workload)
    vocab = SPEC.config(wl["config"])["vocab_size"]
    return Traffic(SPEC.traffic(wl["traffic"]), SPEC.cell(workload), seed,
                   seconds, vocab)


def stream(t: Traffic, n: int = 64):
    """Everything a run would draw: the first wave and the next requests
    (closed loop), or the arrivals (open loop)."""
    if t.loop == "closed":
        return t.first_wave() + [t.next_request() for _ in range(n)]
    return t.arrivals()


@pytest.mark.parametrize("workload", CELLS)
def test_same_seed_same_requests(workload):
    a, b = traffic(workload, BIG), traffic(workload, BIG)
    assert stream(a) == stream(b)
    assert np.array_equal(a.tokens(3, 700), b.tokens(3, 700))


@pytest.mark.parametrize("workload", CELLS)
def test_other_seed_same_sizes_other_order(workload):
    a, b = traffic(workload, BIG), traffic(workload, BIG + 2 ** 32)
    sa, sb = stream(a), stream(b)
    assert sa != sb
    if a.loop == "closed":
        sa, sb = a.first_wave(), b.first_wave()
        whole = [(r.prompt_len, r.output_len) for r in a._pop]
        assert Counter(whole) == Counter((r.prompt_len, r.output_len)
                                         for r in b._pop)
    key = Counter((r.prompt_len, r.output_len) for r in sa)
    assert key == Counter((r.prompt_len, r.output_len) for r in sb)
    assert not np.array_equal(a.tokens(0, 64), b.tokens(0, 64))


def test_seed_words_keep_high_bits():
    assert seed_words(5) != seed_words(5 + 2 ** 32)
    assert seed_words(2 ** 31 + 1) == [2 ** 31 + 1, 0]


@pytest.mark.parametrize("workload", CELLS)
def test_prompts_are_chunk_multiples_and_fit_the_ring(workload):
    t = traffic(workload, 7)
    reqs = stream(t, 512) + t.warmup()
    for r in reqs:
        assert r.prompt_len % t.chunk == 0 and r.prompt_len >= t.chunk
        assert r.output_len >= 1
        assert r.prompt_len + r.output_len <= t.ring
    assert t.warmup()[:len(t.distinct_prompt_lengths())] and \
        {r.prompt_len for r in t.warmup()} == set(t.distinct_prompt_lengths())
    assert len(t.warmup()) >= t.max_batch


def _mean_context_per_output_token(mix: dict) -> float:
    """E[p] + (E[o^2] / E[o] + 1) / 2 over the mix's lengths, uncut."""
    cell = {"s_max": 10 ** 7, "prefill_chunk": 1}
    p = quantile_lengths(mix["prompt"], 100_000, cell).astype(float)
    o = quantile_lengths(mix["output"], 100_000, cell).astype(float)
    return p.mean() + ((o * o).mean() / o.mean() + 1) / 2


def test_longgen_sizes_follow_the_issue():
    """The mix's lengths are in tokens, from its sources, and cut only to
    fit each cell's ring."""
    mix = SPEC.traffic("longgen")
    # the source's mean context per output token (DeepSeek-V3/R1 serving)
    assert _mean_context_per_output_token(mix) == pytest.approx(4989,
                                                                rel=0.005)
    assert mix["prompt"]["median"] == 1020
    t = traffic("qwen2-1.5b.longgen", 1)
    assert t.clients == 40
    assert t.distinct_prompt_lengths() == list(range(512, 3585, 512))
    n = 4096
    prompts = np.minimum(quantile_lengths(mix["prompt"], n, t.cell),
                         t.ring - t.chunk)
    outputs = quantile_lengths(mix["output"], n, t.cell)[
        np.random.default_rng(0).permutation(n)]
    pairs = t._pairs(n)
    assert [r.prompt_len for r in pairs] == list(prompts)
    # an output is cut only where the ring ends it
    assert [r.output_len for r in pairs] == \
        list(np.minimum(outputs, t.ring - prompts))
    cut = sum(1 for r in pairs if r.prompt_len + r.output_len == t.ring)
    assert 0 < cut < n


def test_first_wave_is_the_residual_life():
    """Slots caught mid-flight hold what is left of a request drawn
    length-biased by its output L: the residual R has mean E[L^2] / (2 E[L])
    and CDF P(R <= r) = E[min(L, r)] / E[L]."""
    mix = SPEC.traffic("longgen")
    cell = {"s_max": 4096, "prefill_chunk": 512, "max_batch": 4000}
    t = Traffic(mix, cell, 3, 40, 1000)
    wave = t.first_wave()
    rem = np.array([r.output_len for r in wave], float)
    lengths = np.array([r.output_len for r in t._pairs(4096)], float)
    assert rem.mean() == pytest.approx(
        (lengths ** 2).mean() / (2 * lengths.mean()), rel=0.01)
    for r in (100, 512, 900, 1500):
        assert (rem <= r).mean() == pytest.approx(
            np.minimum(lengths, r).mean() / lengths.mean(), abs=0.01)
    # the part already generated sits in the prompt, in whole chunks, and
    # the ring still holds what is left
    ctx = np.array([r.prompt_len for r in wave])
    assert (ctx % 512 == 0).all() and ctx.max() > 3584 - 512
    assert all(r.prompt_len + r.output_len <= 4096 for r in wave)


def test_open_loop_arrivals_fill_the_window_at_the_rate():
    t = traffic("qwen2-1.5b.code", 11, seconds=40)
    arr = t.arrivals()
    rate = SPEC.cell("qwen2-1.5b.code")["rate_per_s"]
    assert len(arr) == int(rate * 40)
    due = np.array([r.due for r in arr])
    assert (np.diff(due) >= 0).all() and due[0] == 0 and due[-1] < 40
    assert np.diff(due).mean() == pytest.approx(1 / rate, rel=0.1)
    prompts = np.array([r.prompt_len for r in arr])
    assert 512 <= prompts.min() and prompts.max() <= 3584
    assert np.median(prompts) == 1536


def test_quantile_lengths_are_a_fixed_multiset():
    d = {"dist": "lognormal", "median": 13, "sigma": 1.0, "lo": 4, "hi": 128}
    v = quantile_lengths(d, 101, {"s_max": 4096, "prefill_chunk": 512})
    assert v[50] == 13 and v.min() >= 4 and v.max() <= 128
    assert (np.diff(v) >= 0).all()


def test_harness_finds_everything_by_name_from_files_alone(tmp_path):
    """A new configuration, mix, cell and per-layer metric are new files
    and BENCHMARK.json entries; nothing else is edited."""
    root = make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    mix = {"loop": "open", "prompt": {"dist": "uniform", "lo": 32, "hi": 64,
                                      "round_up": "chunk"},
           "output": {"dist": "uniform", "lo": 2, "hi": 4}}
    (root / "bench/traffic/burst.json").write_text(json.dumps(mix))
    cfg = json.loads((root / "bench/configs/tiny.json").read_text())
    cfg["num_hidden_layers"] = 3
    (root / "bench/configs/tiny3.json").write_text(json.dumps(cfg))
    cell = json.loads((root / "bench/cells/tiny.code.json").read_text())
    (root / "bench/cells/tiny3.burst.json").write_text(json.dumps(cell))
    metrics = tmp_path / "metrics2"
    metrics.mkdir()
    (root / "bench/metrics").unlink()
    for f in (REPO / "bench/metrics").glob("*.py"):
        (metrics / f.name).write_text(f.read_text())
    (metrics / "steps.burst.py").write_text(
        "def read(ctx):\n    return float(len(ctx.steps))\n")
    (root / "bench/metrics").symlink_to(metrics)
    bench["configs"].append({"name": "tiny3", "source": "test",
                             "file": "bench/configs/tiny3.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny3.burst", "config": "tiny3",
                               "traffic": "burst", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "steps.burst", "unit": "steps",
                               "better": "higher", "source": "program_counter",
                               "layer": "engine", "moves": "tbt_p95_ms",
                               "workloads": ["tiny3.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = Spec(root)
    assert spec.config("tiny3")["num_hidden_layers"] == 3
    assert spec.traffic("burst")["loop"] == "open"
    assert spec.cell("tiny3.burst")["max_batch"] == 4
    assert [m["name"] for m in spec.per_layer("tiny3.burst")] == \
        ["steps.burst"]
    assert spec.reader("steps.burst").read(type("C", (), {"steps": [1]})) \
        == 1.0
    assert {m["name"] for m in spec.end_to_end("tiny3.burst")} == \
        {"tbt_p95_ms", "setup_s"}
    assert hasattr(spec.model_module("tiny3"), "reference_gaps")
    with pytest.raises(KeyError):
        spec.workload("nope")
