"""The engine's profiler spans, request stamps, program names and compile
counter, read back from a ``jax.profiler`` trace the way an operator reads
one (``docs/serving.md``, "Tracing the engine")."""
import re
import time
from collections import Counter
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config, scale_down
from repro.models import build_model
from repro.serving import ServingEngine, Speculator

KEY = jax.random.PRNGKey(0)
#: a step's phases, in the order ``ServingEngine.step`` runs them
PHASES = re.compile(r"plan (prefill )*(speculate )?(blocks )?"
                    r"(decode wait commit )?$")
NAMES = ("prefill", "decode_step", "prefill_chunk_paged",
         "insert_prefill_paged", "decode_step_paged")


def _serve_spans(path: Path):
    """``(start_ns, end_ns, name, stats)`` of every ``serve.*`` host event,
    in start order (ties: the enclosing span first)."""
    data = ProfileData.from_file(str(next(path.rglob("*.xplane.pb"))))
    out = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
            dict(ev.stats))
           for plane in data.planes if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith("serve.")]
    return sorted(out, key=lambda s: (s[0], -s[1]))


def _traced_serve(tmp_path, chunk, spec=False, lens=(8, 8, 8),
                  max_batch=2, s_max=64):
    """Serve ``lens`` prompts on a fresh tiny paged engine (fresh model
    functions, so every program compiles once here) under a profiler trace,
    recording what each program call and each executed chunk saw."""
    cfg = scale_down(get_config("qwen2-1.5b"))
    model = build_model(cfg)
    params = model.init(KEY)
    eng = ServingEngine(model, params, max_batch=max_batch, s_max=s_max,
                        prefill_chunk=chunk, prefill_token_budget=16,
                        speculator=Speculator(model, params, k=2)
                        if spec else None)
    rec = {"chunks": [], "decode": [], "lives": [], "clock": [],
           "decode_args": None}

    complete = eng.batcher.complete_prefill_chunk

    def on_chunk(req, tokens):
        rec["chunks"].append((req.rid, req.prefilled, tokens))
        return complete(req, tokens)

    decode = eng._decode

    def on_decode(*args):
        rows = [r for r in eng.slot_req if r is not None]
        lives = [r.prompt_len + len(eng.outputs[r.rid]) for r in rows]
        rec["decode"].append((len(rows), sum(lives)))
        rec["lives"].append(lives)
        rec["decode_args"] = rec["decode_args"] or args
        return decode(*args)

    def clock():
        t = time.monotonic()
        rec["clock"].append((t, {rid: len(o)
                                 for rid, o in eng.outputs.items()}))
        return t

    eng.batcher.complete_prefill_chunk = on_chunk
    eng._decode = on_decode
    eng.batcher.now = clock
    rng = np.random.default_rng(0)
    with jax.profiler.trace(str(tmp_path)):
        reqs = [eng.submit(rng.integers(0, cfg.vocab_size, n),
                           max_new_tokens=4) for n in lens]
        eng.run_until_drained()
    assert all(r.state.name == "DONE" for r in reqs)
    return model, eng, reqs, rec, _serve_spans(tmp_path)


@pytest.mark.parametrize("chunk", [8, None], ids=["chunked", "whole"])
def test_step_spans_enclose_phases_in_order(tmp_path, chunk):
    _, eng, _, rec, spans = _traced_serve(tmp_path, chunk)
    steps = [s for s in spans if s[2] == "serve.step"]
    assert len(steps) == eng.batcher.metrics["steps"] > 0
    assert [s[3]["step_num"] for s in steps] == list(range(len(steps)))
    assert {s[3]["device"] for s in steps} == {jax.devices()[0].id}
    phases = [s for s in spans if s[2] != "serve.step"]
    inside = 0
    for a, b, _, _ in steps:
        mine = [p for p in phases if a <= p[0] and p[1] <= b]
        inside += len(mine)
        seq = "".join(p[2][len("serve."):] + " " for p in mine)
        assert PHASES.match(seq), seq
        for p, q in zip(mine, mine[1:]):
            assert p[1] <= q[0]            # one phase after another
    assert inside == len(phases)           # no phase outside a step
    # one prefill span per executed chunk, carrying its rid/start/tokens
    assert [(p[3]["rid"], p[3]["start"], p[3]["tokens"])
            for p in phases if p[2] == "serve.prefill"] == rec["chunks"]
    # the decode span's rows and live tokens: the slots the call stepped
    assert [(p[3]["rows"], p[3]["live_tokens"])
            for p in phases if p[2] == "serve.decode"] == rec["decode"]


def test_decode_span_counts_the_kv_tiles_flash_decode_fetches(tmp_path):
    """``kv_tiles``: the flash decode kernel's tiles that the rows' live
    lengths span, sum(ceil(min(pos+1, cap) / bk)), at mixed depths on both
    sides of a tile boundary."""
    _, eng, _, rec, spans = _traced_serve(tmp_path, None, lens=(8, 509, 700),
                                          max_batch=3, s_max=1024)
    assert eng.kv_tile == 512
    tiles = [p[3]["kv_tiles"] for p in spans if p[2] == "serve.decode"]
    want = [sum(-(-n // 512) for n in lives) for lives in rec["lives"]]
    assert tiles == want
    # a row of 512 live tokens is one tile, a row of 701 two
    assert {512, 701} <= {n for lives in rec["lives"] for n in lives}
    assert any(t > len(lives) for t, lives in zip(tiles, rec["lives"]))


def test_commit_reads_the_steps_tokens_back_once(tmp_path):
    """Every decode step brings its tokens to the host in one transfer,
    however many rows it decoded."""
    _, eng, _, _, spans = _traced_serve(tmp_path, 8, lens=(8, 8, 8, 8),
                                        max_batch=4)
    decodes = [s[3] for s in spans if s[2] == "serve.decode"]
    commits = [s[3] for s in spans if s[2] == "serve.commit"]
    assert max(d["rows"] for d in decodes) >= 3
    assert [c["rows"] for c in commits] == [d["rows"] for d in decodes]
    assert all(c["reads"] == 1 for c in commits)
    assert eng.batcher.metrics["decode_host_reads"] == len(decodes) \
        < sum(d["rows"] for d in decodes)


def test_speculation_round_is_its_own_phase(tmp_path):
    _, eng, _, _, spans = _traced_serve(tmp_path, 8, spec=True)
    names = Counter(s[2] for s in spans)
    assert names["serve.speculate"] == names["serve.step"]
    assert eng.spec_stats["rounds"] > 0


@pytest.mark.parametrize("chunk", [8, None], ids=["chunked", "whole"])
def test_admission_and_first_token_stamps(tmp_path, chunk):
    _, eng, reqs, rec, _ = _traced_serve(tmp_path, chunk)
    seen = dict(rec["clock"])
    for r in reqs:
        assert r.arrival <= r.admitted_at <= r.first_token_at \
            <= r.finished_at
        # stamped once the first token is on the host, in the outputs
        assert seen[r.first_token_at][r.rid] == 1
        assert seen[r.admitted_at][r.rid] == 0


def test_preempted_request_keeps_admission_stamp_and_replay_clears_it():
    cfg = scale_down(get_config("qwen2-1.5b"))
    model = build_model(cfg)
    eng = ServingEngine(model, model.init(KEY), max_batch=2, s_max=32,
                        num_blocks=5, block_size=8, prefill_chunk=8)
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, 8), max_new_tokens=12,
                       priority=p) for p in (2.0, 1.0)]
    first = {}
    for _ in range(200):
        eng.step()
        for r in reqs:
            if r.admitted_at is not None:
                first.setdefault(r.rid, r.admitted_at)
        if all(r.state.name == "DONE" for r in reqs):
            break
    assert eng.batcher.metrics["preempted"] > 0
    # counted with no profiler running too: chunk program and decode, and
    # the chunk program again for the folded prompt's shorter last chunk
    assert eng.batcher.metrics["compiles"] >= 2
    assert all(r.state.name == "DONE" for r in reqs)
    assert {r.rid: r.admitted_at for r in reqs} == first
    reqs[0].reset_for_replay()
    assert reqs[0].admitted_at is None and reqs[0].first_token_at is None


def test_engine_programs_carry_the_models_function_names(tmp_path):
    model, _, _, rec, _ = _traced_serve(tmp_path, 8)
    for name in NAMES:
        assert getattr(model, name).__name__ == name
    lowered = jax.jit(model.decode_step_paged).lower(*rec["decode_args"])
    assert "jit_decode_step_paged" in lowered.as_text()


@pytest.mark.parametrize("chunk,programs", [(8, 2), (None, 3)],
                         ids=["chunked", "whole"])
def test_compile_counter_counts_each_programs_first_call(tmp_path, chunk,
                                                         programs):
    """Equal prompt lengths: every program's first call compiles, no later
    call does.  Chunked: the chunk program and decode; whole prompts: the
    dense prefill, its scatter into the pool, and decode."""
    _, eng, _, _, spans = _traced_serve(tmp_path, chunk)
    assert eng.batcher.metrics["compiles"] == programs
    calls = [s for s in spans if s[2] in ("serve.prefill", "serve.decode")]
    first = [next(i for i, s in enumerate(calls) if s[2] == name)
             for name in ("serve.prefill", "serve.decode")]
    # the first prefill span holds one or two compiling calls
    assert [i for i, s in enumerate(calls)
            if s[3].get("compiled") == 1] == first
