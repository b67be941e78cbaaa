"""Speculative decoding: draft/verify as composed scheduling strategies.

Two invariant families:

* greedy equivalence — with speculation on, the emitted token stream is
  bit-identical to plain decode (self-draft = full acceptance, cross-draft
  = rejection/correction path), and the paged allocator's invariants hold
  after every rollback;
* strategy composition — verify tasks outrank request tasks outrank
  drafts in one ``StrategyTaskStorage``; drafts are stolen first and shed
  first; a cleared slot (steal/preemption) drops its spec state and the
  request resumes non-speculatively.
"""
import jax
import numpy as np
import pytest

from repro.cluster import ClusterTelemetry, StealPolicy, run_cluster_sim
from repro.configs import get_config, scale_down
from repro.core.device.request_scheduler import Request, RequestStrategy
from repro.core.task import FinishRegion, Task
from repro.core.task_storage import StrategyTaskStorage
from repro.models import build_model
from repro.serving import ServingEngine, Speculator
from repro.serving.speculative import (DraftStrategy, VerifyStrategy,
                                       _AdaptiveK, accept_longest_prefix)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def dense():
    cfg = scale_down(get_config("qwen2-1.5b"))
    model = build_model(cfg)
    return cfg, model, model.init(KEY)


def _prompts(cfg, n=3, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, int(rng.integers(4, 14)))
            for _ in range(n)]


def _run(model, params, prompts, max_new=6, spec=None, **kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("s_max", 48)
    eng = ServingEngine(model, params, speculator=spec, **kw)
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    outs = eng.run_until_drained()
    assert all(r.state.name == "DONE" for r in reqs)
    return eng, [outs[r.rid] for r in reqs]


# -- accept rule --------------------------------------------------------------

def test_accept_longest_prefix():
    acc, m = accept_longest_prefix([1, 2, 3], [1, 2, 3, 4])
    assert (acc, m) == ([1, 2, 3, 4], 3)       # all drafts + bonus token
    acc, m = accept_longest_prefix([1, 2, 3], [9, 8, 7, 6])
    assert (acc, m) == ([9], 0)                # full reject still emits 1
    acc, m = accept_longest_prefix([1, 2, 3], [1, 9, 7, 6])
    assert (acc, m) == ([1, 9], 1)             # partial + correction
    acc, m = accept_longest_prefix([], [5])
    assert (acc, m) == ([5], 0)


# -- greedy equivalence -------------------------------------------------------

def test_self_draft_bit_identical(dense):
    """Self-draft (draft == target) accepts everything, and the stream must
    equal plain decode exactly; merged draft chains must have fired."""
    cfg, model, params = dense
    prompts = _prompts(cfg)
    _, base = _run(model, params, prompts)
    spec = Speculator(model, params, k=3)
    eng, outs = _run(model, params, prompts, spec=spec)
    assert outs == base
    s = eng.spec_stats
    assert s["rounds"] > 0 and s["wasted"] == 0
    assert s["acceptance_rate"] == 1.0
    assert s["merged_drafts"] >= 1             # concurrent slots coalesced
    eng.alloc.check()


def test_cross_draft_bit_identical(dense):
    """A disagreeing draft (same arch, different weights) exercises the
    reject/correction path and the KV rollback — output must still be
    bit-identical, and the allocator must pass its invariant check."""
    cfg, model, params = dense
    dparams = model.init(jax.random.PRNGKey(7))
    prompts = _prompts(cfg, seed=1)
    _, base = _run(model, params, prompts, max_new=8)
    spec = Speculator(model, dparams, k=3, adaptive=False)
    eng, outs = _run(model, params, prompts, max_new=8, spec=spec)
    assert outs == base
    s = eng.spec_stats
    assert s["rounds"] > 0 and s["wasted"] > 0  # rejections happened
    eng.alloc.check()


def test_verify_consumes_the_pool(dense):
    """The verify program takes the pool donated, as decode does: after
    every step the pool held before it is deleted, and the tokens are
    those of plain decode."""
    cfg, model, params = dense
    prompts = _prompts(cfg)
    _, base = _run(model, params, prompts)
    eng = ServingEngine(model, params, max_batch=3, s_max=48,
                        speculator=Speculator(model, params, k=3))
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    while any(r.state.name != "DONE" for r in reqs):
        before = jax.tree.leaves(eng.cache)
        eng.step()
        assert all(a.is_deleted() for a in before)
        assert not any(a.is_deleted() for a in jax.tree.leaves(eng.cache))
    assert eng.batcher.metrics["spec_verify_calls"] > 0
    assert [eng.outputs[r.rid] for r in reqs] == base


def test_spec_with_prefix_cache_warm(dense):
    """Speculation over COW-shared prefix blocks: the reserve path must
    fork before writing, never corrupting published blocks."""
    cfg, model, params = dense
    rng = np.random.default_rng(2)
    shared = rng.integers(0, cfg.vocab_size, 16)
    prompts = [np.concatenate([shared,
                               rng.integers(0, cfg.vocab_size, 5 + i)])
               for i in range(3)]
    kw = dict(prefill_chunk=8, prefix_cache=True)
    _, base = _run(model, params, prompts, **kw)

    spec = Speculator(model, params, k=3)
    eng = ServingEngine(model, params, max_batch=3, s_max=48,
                        speculator=spec, **kw)
    _run_eng = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_drained()                     # warm pass publishes
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    outs = eng.run_until_drained()
    assert all(r.state.name == "DONE" for r in reqs)
    assert [outs[r.rid] for r in reqs] == base
    assert eng.cache_stats["hit_tokens"] > 0    # cache actually engaged
    eng.alloc.check()


@pytest.mark.slow
def test_spec_through_flash_kernels():
    """Interpret-mode Pallas path.  Verify is always the masked XLA path
    (like chunked prefill: the flash kernel's q_offset is static per
    shape), so against flash decode the gate is the chunked-prefill one —
    every request completes with the same token count, speculation
    actually engaged, allocator invariants hold."""
    cfg = scale_down(get_config("qwen2-1.5b")).replace(use_flash=True)
    model = build_model(cfg)
    params = model.init(KEY)
    prompts = _prompts(cfg, n=2, seed=3)
    _, base = _run(model, params, prompts)
    spec = Speculator(model, params, k=3)
    eng, outs = _run(model, params, prompts, spec=spec)
    assert [len(o) for o in outs] == [len(o) for o in base]
    assert eng.spec_stats["rounds"] > 0
    eng.alloc.check()


@pytest.mark.slow
def test_spec_moe_family():
    cfg = scale_down(get_config("mixtral-8x22b"))
    model = build_model(cfg)
    params = model.init(KEY)
    prompts = _prompts(cfg, n=2, seed=4)
    _, base = _run(model, params, prompts)
    spec = Speculator(model, params, k=3)
    eng, outs = _run(model, params, prompts, spec=spec)
    assert outs == base
    eng.alloc.check()


# -- strategy composition -----------------------------------------------------

def _mk_task(strategy):
    return Task(lambda: None, (), {}, strategy, FinishRegion())


def test_pop_order_verify_request_draft():
    """In one storage, composed order is: verify (class -1) before the
    ordinary request (class 0) before the draft (huge class)."""
    storage = StrategyTaskStorage(0)
    req = Request(prompt_len=4, max_new_tokens=4, priority=0.0)
    storage.push(_mk_task(DraftStrategy("propose", 0, k=4)))
    storage.push(_mk_task(RequestStrategy(req, lambda: 0.0)))
    storage.push(_mk_task(VerifyStrategy(1, [1, 2])))
    order = [type(storage.pop_local().strategy).__name__ for _ in range(3)]
    assert order == ["VerifyStrategy", "RequestStrategy", "DraftStrategy"]
    assert storage.pop_local() is None


def test_steal_order_drafts_before_verifies():
    d = DraftStrategy("propose", 0, k=2)
    v = VerifyStrategy(0, [1])
    assert d.steal_prioritize(v)        # drafts are cheap to lose
    assert not v.steal_prioritize(d)    # verifies are steal-resistant


def test_shed_drafts_pruned_never_verifies():
    pruned = []
    storage = StrategyTaskStorage(0, on_prune=pruned.append)
    d1, d2 = DraftStrategy("propose", 0, k=2), DraftStrategy("warm", 1)
    storage.push(_mk_task(d1))
    storage.push(_mk_task(d2))
    storage.push(_mk_task(VerifyStrategy(2, [5])))
    d1.shed = True
    d2.shed = True
    first = storage.pop_local()
    assert isinstance(first.strategy, VerifyStrategy)
    assert storage.pop_local() is None          # both drafts pruned
    assert len(pruned) == 2


def test_pool_pressure_sheds_drafts_not_correctness(dense):
    """With every block allocated (zero free, zero cached), the round sheds
    all drafts before spending compute — requests decode plain and the
    stream stays correct."""
    cfg, model, params = dense
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, 8) for _ in range(2)]
    _, base = _run(model, params, prompts, max_new=4)
    spec = Speculator(model, params, k=3)
    # sink + 2 usable blocks of 16 tokens: both usable blocks are claimed
    # by the two prompts, so num_free + num_cached == 0 for the whole run
    eng, outs = _run(model, params, prompts, max_new=4, spec=spec,
                     max_batch=2, s_max=32, block_size=16, num_blocks=3)
    assert outs == base
    s = eng.spec_stats
    assert s["shed"] > 0 and s["rounds"] == 0   # never speculated
    eng.alloc.check()


def test_cleared_slot_drops_spec_state(dense):
    """Steal/preemption clears the slot: spec state dies with it, the next
    round re-warms from scratch, and the output is still exact."""
    cfg, model, params = dense
    prompts = _prompts(cfg, n=1, seed=6)
    _, base = _run(model, params, prompts, max_new=8)
    spec = Speculator(model, params, k=2)
    eng = ServingEngine(model, params, max_batch=3, s_max=48,
                        speculator=spec)
    req = eng.submit(prompts[0], max_new_tokens=8)
    eng.step()                                  # prefill (+ warm)
    eng.step()                                  # first speculation round
    assert spec._state[0].warm
    warms_before = eng.spec_stats["warms"]
    spec.on_clear(0)                            # what _clear_slot invokes
    assert not spec._state[0].warm              # state gone
    eng.run_until_drained()
    assert req.state.name == "DONE"
    assert eng.outputs[req.rid] == base[0]
    assert eng.spec_stats["warms"] == warms_before + 1   # re-warmed
    eng.alloc.check()


# -- adaptive depth -----------------------------------------------------------

def test_adaptive_k_tracks_acceptance():
    a = _AdaptiveK(4, 1, 8)
    for _ in range(6):
        a.update(1, 4, 4)                       # full acceptance
    assert a.k_for(1) == 8
    for _ in range(10):
        a.update(1, 0, 4)                       # full rejection
    assert a.k_for(1) == 1
    a.drop(1)
    assert a.k_for(1) == 4                      # back to the default


# -- validation ---------------------------------------------------------------

def test_speculator_rejects_bad_configs(dense):
    cfg, model, params = dense
    with pytest.raises(ValueError):
        Speculator(model, params, k=0)
    with pytest.raises(ValueError):
        Speculator(model, params, k=4, k_min=5)
    ssm = build_model(scale_down(get_config("rwkv6-3b")))
    with pytest.raises(ValueError, match="positional"):
        Speculator(ssm, None)


def test_speculator_rejects_vocab_mismatch(dense):
    cfg, model, params = dense
    dcfg = scale_down(get_config("qwen2-1.5b"), vocab=1024)
    dmodel = build_model(dcfg)
    spec = Speculator(dmodel, dmodel.init(KEY), k=2)
    with pytest.raises(ValueError, match="vocab"):
        ServingEngine(model, params, max_batch=2, s_max=32, speculator=spec)


def test_speculator_rejects_contiguous_engine(dense):
    cfg, model, params = dense
    spec = Speculator(model, params, k=2)
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(model, params, max_batch=2, s_max=32,
                      kv_mode="contiguous", speculator=spec)


# -- cluster telemetry + sim --------------------------------------------------

def test_spec_telemetry_dedup():
    tel = ClusterTelemetry(2)
    tel.record_spec(0, 10, 5, key=(0, 1))
    tel.record_spec(1, 10, 5, key=(0, 1))       # replay: ignored
    tel.record_spec(0, 8, 2, key=(1, 1))        # same rid, other origin
    tel.record_spec(0, 0, 0)                    # never drafted: ignored
    assert tel.spec_drafted_tokens == 18
    assert tel.spec_accepted_tokens == 7
    s = tel.summary()["spec"]
    assert s["requests"] == 2
    assert s["wasted_tokens"] == 11
    assert s["per_request_rate"]["min"] == 0.25
    assert s["per_request_rate"]["max"] == 0.5


def test_sim_spec_improves_latency():
    off = run_cluster_sim(2, 300, StealPolicy(amount="half_work"),
                          spec_k=0, seed=3)
    on = run_cluster_sim(2, 300, StealPolicy(amount="half_work"),
                         spec_k=4, spec_accept=0.8, seed=3)
    assert off.summary()["spec"]["drafted_tokens"] == 0
    s = on.summary()["spec"]
    assert s["drafted_tokens"] > 0
    assert 0.0 < s["acceptance_rate"] < 1.0
    for slo, hist in off.per_class.items():
        if hist.total == 0:
            continue
        assert on.per_class[slo].mean <= hist.mean * 1.01
