"""Serving engine: continuous batching with per-request strategies."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, scale_down
from repro.core.device.request_scheduler import Request
from repro.models import build_model
from repro.serving import ServingEngine

KEY = jax.random.PRNGKey(0)


def _engine(max_batch=3, s_max=48, name="qwen2-1.5b"):
    cfg = scale_down(get_config(name))
    model = build_model(cfg)
    params = model.init(KEY)
    return cfg, model, params, ServingEngine(model, params,
                                             max_batch=max_batch,
                                             s_max=s_max)


def test_engine_completes_all_requests():
    cfg, model, params, eng = _engine()
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, ln), max_new_tokens=4)
            for ln in (5, 9, 13, 7, 3)]
    outs = eng.run_until_drained()
    for r in reqs:
        assert r.state.name == "DONE"
        assert len(outs[r.rid]) == 4
    assert eng.batcher.metrics["merged_prefills"] >= 1


def test_engine_matches_sequential_generation():
    """Continuous batching must not change what a request generates."""
    cfg, model, params, eng = _engine(max_batch=2, s_max=32)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, 6),
               rng.integers(0, cfg.vocab_size, 11)]
    reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
    outs = eng.run_until_drained()

    for p, r in zip(prompts, reqs):
        toks = jnp.asarray(p[None, :])
        logits, cache = model.prefill(params, {"tokens": toks}, 32)
        seq = [int(jnp.argmax(logits[0, -1]))]
        pos = len(p)
        for _ in range(2):
            lg, cache = model.decode_step(
                params, jnp.asarray([[seq[-1]]], jnp.int32), cache,
                jnp.int32(pos))
            seq.append(int(jnp.argmax(lg[0, -1])))
            pos += 1
        assert outs[r.rid] == seq, (outs[r.rid], seq)


def test_engine_priority_order_under_contention():
    cfg, model, params, eng = _engine(max_batch=1, s_max=32)
    rng = np.random.default_rng(2)
    lo = eng.submit(rng.integers(0, cfg.vocab_size, 4), 6, priority=5.0)
    hi = eng.submit(rng.integers(0, cfg.vocab_size, 4), 6, priority=0.0)
    eng.step()   # admits exactly one request: must be `hi`
    assert hi.state.name in ("RUNNING", "PREFILL", "DONE")
    assert lo.state.name == "WAITING"
    eng.run_until_drained()
    assert hi.finished_at <= lo.finished_at


def test_engine_serves_through_flash_kernels():
    """Serving smoke over the Pallas path: prefill uses the flash kernel,
    decode the kv_valid flash-decode path (interpret mode on CPU), and
    batching must still not change what a request generates."""
    cfg = scale_down(get_config("qwen2-1.5b")).replace(use_flash=True)
    model = build_model(cfg)
    params = model.init(KEY)
    eng = ServingEngine(model, params, max_batch=2, s_max=32)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, 6),
               rng.integers(0, cfg.vocab_size, 11)]
    reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
    outs = eng.run_until_drained()
    for r in reqs:
        assert r.state.name == "DONE"
        assert len(outs[r.rid]) == 3

    # sequential flash-path generation must match the batched engine
    for p, r in zip(prompts, reqs):
        toks = jnp.asarray(p[None, :])
        logits, cache = model.prefill(params, {"tokens": toks}, 32)
        seq = [int(jnp.argmax(logits[0, -1]))]
        pos = len(p)
        for _ in range(2):
            lg, cache = model.decode_step(
                params, jnp.asarray([[seq[-1]]], jnp.int32), cache,
                jnp.int32(pos))
            seq.append(int(jnp.argmax(lg[0, -1])))
            pos += 1
        assert outs[r.rid] == seq, (outs[r.rid], seq)


def test_engine_cancellation_is_dead_task():
    cfg, model, params, eng = _engine(max_batch=1, s_max=32)
    rng = np.random.default_rng(3)
    a = eng.submit(rng.integers(0, cfg.vocab_size, 4), 2)
    b = eng.submit(rng.integers(0, cfg.vocab_size, 4), 2)
    b.cancel()
    eng.run_until_drained()
    assert a.state.name == "DONE"
    assert b.state.name == "CANCELLED"
    assert eng.batcher.metrics["evicted_dead"] >= 1
    if eng.paged:
        eng.alloc.check()                 # cancelled request freed its blocks
        assert eng.alloc.num_requests == 0


# ------------------------------------------------------------- paged KV
def _model(name="qwen2-1.5b", **repl):
    cfg = scale_down(get_config(name)).replace(**repl)
    model = build_model(cfg)
    return cfg, model, model.init(KEY)


def _drain(model, params, prompts, max_new=4, **kw):
    eng = ServingEngine(model, params, **kw)
    reqs = [eng.submit(p, max_new_tokens=max_new, priority=float(i % 2))
            for i, p in enumerate(prompts)]
    outs = eng.run_until_drained()
    assert all(r.state.name == "DONE" for r in reqs)
    if eng.paged:
        eng.alloc.check()
        assert eng.alloc.num_requests == 0, "drained engine leaked blocks"
    return [outs[r.rid] for r in reqs], eng


def test_paged_engine_matches_contiguous_engine():
    """The paged engine must generate exactly what the contiguous engine
    generates — same gathered widths, masks and values."""
    cfg, model, params = _model()
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, n)
               for n in (25, 6, 17, 3, 30, 9)]
    ref, _ = _drain(model, params, prompts, max_batch=2, s_max=48,
                    kv_mode="contiguous")
    got, eng = _drain(model, params, prompts, max_batch=2, s_max=48,
                      kv_mode="paged")
    assert got == ref
    assert eng.paged and eng.kv_mode == "paged"


def test_paged_chunked_prefill_matches_and_counts_chunks():
    cfg, model, params = _model()
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (25, 30, 6)]
    ref, _ = _drain(model, params, prompts, max_batch=2, s_max=48,
                    kv_mode="contiguous")
    got, eng = _drain(model, params, prompts, max_batch=2, s_max=48,
                      kv_mode="paged", prefill_chunk=8, block_size=8)
    assert [len(o) for o in got] == [len(o) for o in ref]
    assert got == ref                      # bf16: bit-identical in practice
    m = eng.batcher.metrics
    assert m["prefill_chunks"] > len(prompts)   # long prompts were split


def test_paged_engine_matches_contiguous_past_ring_wrap():
    """Decode past the ring capacity (pos >= cap): the paged slot mapping
    ``pos % cap`` must wrap exactly like the dense ring buffer.  Wrapping a
    full-attention ring is an explicit opt-in now (``overflow="allow"``) —
    default admission rejects it as self-corrupting."""
    cfg, model, params = _model()
    rng = np.random.default_rng(16)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (28, 30)]
    # prompt_len + max_new > cap=32 for every request
    ref, _ = _drain(model, params, prompts, max_new=8, max_batch=2,
                    s_max=32, kv_mode="contiguous", overflow="allow")
    got, eng = _drain(model, params, prompts, max_new=8, max_batch=2,
                      s_max=32, kv_mode="paged", block_size=8,
                      overflow="allow")
    assert got == ref
    assert all(len(p) + 8 > eng.cap for p in prompts)   # wrap exercised


def test_paged_pool_pressure_preempts_and_completes():
    """A pool far smaller than the worst case forces recompute preemption;
    every request still finishes with exactly its token budget and the
    allocator ends clean."""
    cfg, model, params = _model()
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (40, 38, 36, 35)]
    got, eng = _drain(model, params, prompts, max_new=6, max_batch=3,
                      s_max=48, kv_mode="paged", prefill_chunk=8,
                      block_size=8, num_blocks=9)
    assert all(len(o) == 6 for o in got)
    assert eng.batcher.metrics["preempted"] > 0


def test_paged_kv_migrates_with_stolen_chunk_request():
    """A partially-prefilled request stolen from one engine resumes on the
    thief from the chunk boundary (prefix KV travels) and generates the
    same tokens as an undisturbed run."""
    cfg, model, params = _model()
    rng = np.random.default_rng(14)
    long_p = rng.integers(0, cfg.vocab_size, 40)
    kw = dict(s_max=48, kv_mode="paged", prefill_chunk=8, block_size=8)
    victim = ServingEngine(model, params, max_batch=1, **kw)
    victim.submit(rng.integers(0, cfg.vocab_size, 4), 2, priority=0.0)
    req = victim.submit(long_p, 3, priority=1.0)
    for _ in range(3):
        victim.step()
    assert req.prefilled > 0 and req.state.name == "WAITING"
    (stolen, payload), = victim.export_waiting(target_weight=10_000)
    assert stolen is req and isinstance(payload, dict) and "kv" in payload
    victim.alloc.check()

    thief = ServingEngine(model, params, max_batch=2, **kw)
    thief.submit_request(req, payload)
    assert req.prefilled > 0               # prefix adopted, not recomputed
    outs = thief.run_until_drained()
    thief.alloc.check()

    ref, _ = _drain(model, params, [long_p], max_new=3, max_batch=1, **kw)
    assert outs[req.rid] == ref[0]


def test_preempted_request_migrates_with_emitted_tokens():
    """Preempt-then-steal: a recompute-preempted request's already-emitted
    tokens (folded into its prompt) must travel with the migration — the
    client-visible stream survives intact."""
    cfg, model, params = _model()
    rng = np.random.default_rng(21)
    kw = dict(s_max=48, kv_mode="paged", prefill_chunk=8, block_size=8)
    victim_eng = ServingEngine(model, params, max_batch=2, num_blocks=9,
                               **kw)
    reqs = [victim_eng.submit(rng.integers(0, cfg.vocab_size, 30), 6)
            for _ in range(2)]
    for _ in range(6):
        victim_eng.step()
    running = [r for r in reqs if r.state.name == "RUNNING"]
    if running:
        victim_eng._preempt_running(running[0])    # force a fold
    stolen = victim_eng.export_waiting(target_weight=10_000)
    thief = ServingEngine(model, params, max_batch=2, **kw)
    for r, payload in stolen:
        thief.submit_request(r, payload)
    outs = thief.run_until_drained()
    victim_eng.run_until_drained()
    for r in reqs:
        stream = outs.get(r.rid) or victim_eng.outputs.get(r.rid)
        assert r.state.name == "DONE" and len(stream) == 6, \
            (r.rid, r.state, stream)


def test_kv_import_from_larger_ring_recomputes():
    """A prefix exported from a victim with a larger ring than the thief's
    must be rejected (recompute), not crash the thief's block table."""
    cfg, model, params = _model()
    rng = np.random.default_rng(22)
    kw = dict(kv_mode="paged", prefill_chunk=8, block_size=8)
    victim_eng = ServingEngine(model, params, max_batch=1, s_max=48, **kw)
    victim_eng.submit(rng.integers(0, cfg.vocab_size, 4), 2, priority=0.0)
    big = victim_eng.submit(rng.integers(0, cfg.vocab_size, 40), 3,
                            priority=1.0)
    for _ in range(4):
        victim_eng.step()
    assert big.prefilled > 0 and big.state.name == "WAITING"
    (r, payload), = victim_eng.export_waiting(target_weight=10_000)
    # the 40-token prompt exceeds the thief's 32-token ring: a migrated
    # request is already accepted by the cluster, so even a rejecting
    # thief serves it degraded (legacy ring-aligning wrap) over dropping it
    thief = ServingEngine(model, params, max_batch=1, s_max=32, **kw)
    thief.submit_request(r, payload, migrated=True)
    assert thief.batcher.metrics["wrapped_oversize"] == 1
    assert r.prefilled == 0                         # rejected → recompute
    outs = thief.run_until_drained()
    assert r.state.name == "DONE" and len(outs[r.rid]) == 3
    thief.alloc.check()


def test_preemption_never_inverts_priority():
    """Pool pressure from a bulk request must not recompute-preempt a more
    urgent holder (it defers instead)."""
    cfg, model, params = _model()
    rng = np.random.default_rng(23)
    eng = ServingEngine(model, params, max_batch=2, s_max=48,
                        kv_mode="paged", prefill_chunk=8, block_size=8,
                        num_blocks=9)
    urgent = eng.submit(rng.integers(0, cfg.vocab_size, 30), 6,
                        priority=0.0)
    bulk = eng.submit(rng.integers(0, cfg.vocab_size, 40), 6, priority=1.0)
    eng.run_until_drained()
    assert urgent.state.name == "DONE" and bulk.state.name == "DONE"
    # any preemption under pressure must have landed on the bulk request
    assert urgent.prompt_len == 30          # never folded/preempted
    assert urgent.finished_at <= bulk.finished_at


def test_paged_engine_hybrid_family():
    """Hybrid (Jamba) pages its attention KV; Mamba states stay slot-dense.
    Whole-prompt prefill (no chunk path), paged decode."""
    cfg, model, params = _model("jamba-v0.1-52b", ssm_chunk=4)
    rng = np.random.default_rng(15)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (9, 14)]
    ref, _ = _drain(model, params, prompts, max_batch=2, s_max=32,
                    kv_mode="contiguous")
    got, eng = _drain(model, params, prompts, max_batch=2, s_max=32,
                      kv_mode="paged", block_size=8)
    assert got == ref
    assert eng.batcher.prefill_chunk is None   # chunking auto-disabled


def test_admission_rejects_ring_wrapping_requests():
    """Regression: the paged chunk-prefill contract requires
    ``start + c <= cap`` (no ring wrap mid-prompt), but nothing used to
    validate ``prompt_len + max_new_tokens`` against capacity at admission —
    a long request silently corrupted its own earliest blocks.  Default
    policy rejects with a telemetry counter; ``truncate`` clamps the token
    budget instead."""
    cfg, model, params = _model()
    rng = np.random.default_rng(24)
    eng = ServingEngine(model, params, max_batch=2, s_max=32,
                        kv_mode="paged", block_size=8)
    with pytest.raises(ValueError):
        eng.submit(rng.integers(0, cfg.vocab_size, 30), 8)   # 38 > 32
    assert eng.batcher.metrics["rejected"] == 1
    with pytest.raises(ValueError):
        eng.submit(rng.integers(0, cfg.vocab_size, 40), 1)   # prompt > cap
    assert eng.batcher.metrics["rejected"] == 2
    ok = eng.submit(rng.integers(0, cfg.vocab_size, 28), 4)  # 32 == cap
    eng.run_until_drained()
    assert ok.state.name == "DONE"

    # first placements through submit_request (cluster routing) reject the
    # same way; only an actual steal migration downgrades to truncation
    fresh = Request(prompt_len=30, max_new_tokens=8)
    with pytest.raises(ValueError):
        eng.submit_request(fresh, rng.integers(0, cfg.vocab_size, 30))
    moved = Request(prompt_len=30, max_new_tokens=8)
    eng.submit_request(moved, rng.integers(0, cfg.vocab_size, 30),
                       migrated=True)
    assert moved.max_new_tokens == 2
    eng.run_until_drained()
    assert moved.state.name == "DONE"

    # a preempted-then-migrated request has its emitted tokens folded into
    # the prompt; only the REMAINING budget needs ring space, so a request
    # that fits exactly must not be over-truncated (silent output loss)
    folded = Request(prompt_len=30, max_new_tokens=8)
    folded.generated = 6                   # 30 + (8 - 6) = 32 == cap
    eng.submit_request(folded, rng.integers(0, cfg.vocab_size, 30),
                       migrated=True)
    assert folded.max_new_tokens == 8      # budget untouched
    eng.run_until_drained()
    assert folded.state.name == "DONE"

    trunc = ServingEngine(model, params, max_batch=2, s_max=32,
                          kv_mode="paged", block_size=8, overflow="truncate")
    req = trunc.submit(rng.integers(0, cfg.vocab_size, 30), 8)
    assert req.max_new_tokens == 2                   # clamped to capacity
    assert trunc.batcher.metrics["truncated"] == 1
    outs = trunc.run_until_drained()
    assert req.state.name == "DONE" and len(outs[req.rid]) == 2

    # the contiguous engine has the same ring — same check
    cont = ServingEngine(model, params, max_batch=2, s_max=32,
                         kv_mode="contiguous")
    with pytest.raises(ValueError):
        cont.submit(rng.integers(0, cfg.vocab_size, 30), 8)


def test_hybrid_midprefill_steal_restarts_from_chunk0():
    """A mid-prefill *hybrid* request stolen to another replica cannot
    resume at the chunk boundary: only attention KV is exportable and the
    Mamba state is not.  The export path must reset the prefill progress
    (restart from chunk 0 on the thief) rather than ship bookkeeping that
    claims a resumable prefix."""
    cfg, model, params = _model("jamba-v0.1-52b", ssm_chunk=4)
    rng = np.random.default_rng(25)
    prompt = rng.integers(0, cfg.vocab_size, 14)
    kw = dict(s_max=32, kv_mode="paged", block_size=8)
    victim = ServingEngine(model, params, max_batch=1, **kw)
    req = victim.submit(prompt, 3)
    # manufacture a parked mid-prefill state (no hybrid code path parks one
    # today — this pins the export contract against future chunk paths)
    victim.alloc.ensure(req.rid, 8)
    req.prefilled = 8
    (r, payload), = victim.export_waiting(target_weight=10_000)
    assert r is req
    assert r.prefilled == 0                # restart from chunk 0
    assert not (isinstance(payload, dict) and "kv" in payload)
    victim.alloc.check()

    thief = ServingEngine(model, params, max_batch=1, **kw)
    thief.submit_request(r, payload)
    outs = thief.run_until_drained()
    ref, _ = _drain(model, params, [prompt], max_new=3, max_batch=1, **kw)
    assert outs[r.rid] == ref[0]           # full, uncorrupted generation


def test_prefix_cache_evicts_cached_tail_before_preempting():
    """Pool pressure drains unreferenced cached blocks (LRU) before it
    recompute-preempts anyone: cached-but-idle prefixes are strictly
    cheaper to reclaim than live work."""
    cfg, model, params = _model()
    rng = np.random.default_rng(26)
    sysp = rng.integers(0, cfg.vocab_size, 16)
    eng = ServingEngine(model, params, max_batch=2, s_max=48,
                        kv_mode="paged", block_size=8, prefill_chunk=8,
                        prefix_cache=True, num_blocks=8)
    a = eng.submit(np.concatenate([sysp, rng.integers(0, cfg.vocab_size, 6)]),
                   3)
    eng.run_until_drained()
    assert a.state.name == "DONE"
    assert eng.alloc.num_cached > 0        # prefix survives the request
    # a big cold request needs more than the free list: the cached tail is
    # evicted, nobody is preempted
    b = eng.submit(rng.integers(0, cfg.vocab_size, 40), 4)
    outs = eng.run_until_drained()
    assert b.state.name == "DONE" and len(outs[b.rid]) == 4
    assert eng.alloc.cache_evictions > 0
    assert eng.batcher.metrics["preempted"] == 0
    eng.alloc.check()


def test_ssm_family_falls_back_to_contiguous():
    cfg, model, params = _model("rwkv6-3b", ssm_chunk=4)
    eng = ServingEngine(model, params, max_batch=2, s_max=32)
    assert eng.kv_mode == "contiguous" and not eng.paged
    with pytest.raises(ValueError):
        ServingEngine(model, params, max_batch=2, s_max=32, kv_mode="paged")


def test_serve_driver_exit_code_counts_unfinished_requests(monkeypatch):
    """launch.serve's single engine exits 0 only when every request is
    DONE; --prompt-lens fixes the prompt lengths, cycled over requests."""
    from repro.launch import serve
    args = serve.parse_args(["--smoke", "--requests", "3",
                             "--max-new-tokens", "2", "--s-max", "64",
                             "--prompt-lens", "20,40"])
    cfg, model, params = serve.build(args)
    assert [len(p) for p in serve.make_prompts(args, cfg)] == [20, 40, 20]
    res = serve.serve_single(args, model, params, cfg)
    assert res.rc == 0 and [len(t) for t in res.tokens] == [2, 2, 2]
    monkeypatch.setattr(ServingEngine, "run_until_drained",
                        lambda self, max_steps=0: self.outputs)
    assert serve.serve_single(args, model, params, cfg).rc == 1


def test_engine_keeps_params_and_pool_on_its_device():
    cfg, model, params, _ = _engine()
    dev = jax.devices()[-1]
    eng = ServingEngine(model, params, max_batch=2, s_max=32, device=dev)
    eng.submit(np.arange(1, 9), max_new_tokens=3)
    eng.run_until_drained()
    homes = {d for leaf in jax.tree.leaves((eng.params, eng.cache))
             for d in leaf.devices()}
    assert homes == {dev}


def test_compile_cache_dir_env_or_fixed_checkout_path(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads it
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == str(Path(__file__).resolve().parents[1] / ".jax_cache")
