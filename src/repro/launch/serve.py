"""Serving driver: strategy-scheduled continuous batching over paged KV.

Single replica (paged KV + chunked prefill by default where supported):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --requests 16

Multi-replica (cluster router with configurable steal policy):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --replicas 2 --requests 16 --steal half_work

CI equality gate (paged and contiguous KV must generate identical tokens):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --requests 8 --check-paged-equality

Chaos smoke (kill one live engine mid-run; exit 1 unless every request
finishes and replayed counts match telemetry — see docs/operations.md):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --replicas 2 --requests 12 --chaos kill-one

Full width on a TPU (what ``chip_smoke.py`` drives): drop ``--smoke``.
Replica ``i`` of a cluster run holds its params and KV pool on
``jax.devices()[i % device_count]``.  JAX's persistent compilation cache
is on (``compile_cache.enable_compile_cache``).
"""
from __future__ import annotations

import argparse
import sys
import time
from collections import Counter
from typing import List, NamedTuple, Optional, Sequence

import jax
import numpy as np

from ..cluster import (ClusterRouter, ClusterTelemetry, EngineReplica,
                       StealPolicy)
from ..configs import get_config, scale_down
from ..core.device.request_scheduler import Request
from ..kernels.compat import has_tpu
from ..models import build_model
from ..runtime import (Autoscaler, AutoscalePolicy, HeartbeatMonitor,
                       StragglerDetector)
from ..serving import ServingEngine, Speculator
from .compile_cache import enable_compile_cache

#: tokens of the system prefix that every other prompt shares
SYS_PREFIX = 16


class ServeResult(NamedTuple):
    rc: int                            # the driver's exit code
    tokens: List[List[int]]            # generated tokens, submission order
    engines: List[ServingEngine]       # one per replica


def make_prompts(args, cfg) -> List[np.ndarray]:
    """Mixed traffic: half the prompts share a 16-token system prefix (the
    shared-prefix caching shape), half are cold.  ``--prompt-lens`` fixes
    the total prompt lengths, cycled over the requests; otherwise the
    private tails are 4-31 tokens."""
    rng = np.random.default_rng(args.seed)
    sys_prefix = rng.integers(0, cfg.vocab_size, SYS_PREFIX)
    out = []
    for i in range(args.requests):
        shared = i % 2 == 0
        if args.prompt_lens:
            n = args.prompt_lens[i % len(args.prompt_lens)] \
                - (SYS_PREFIX if shared else 0)
        else:
            n = int(rng.integers(4, 32))
        tail = rng.integers(0, cfg.vocab_size, n)
        out.append(np.concatenate([sys_prefix, tail]) if shared else tail)
    return out


def _engine_kw(args):
    admission = args.admission
    if args.prefix_cache and args.cache_policy == "aware" \
            and admission == "strategy":
        admission = "cache_aware"
    return dict(max_batch=args.max_batch, s_max=args.s_max,
                kv_mode=args.kv, block_size=args.block_size,
                num_blocks=args.num_blocks,
                prefill_chunk=args.prefill_chunk,
                admission=admission,
                prefix_cache=args.prefix_cache,
                overflow=args.overflow)


def _build_draft(args, model, params, cfg):
    """Resolve ``--spec-draft`` into a ``(model, params)`` pair, failing
    fast — unknown zoo name, vocab mismatch, or a family that cannot draft
    is a clear error *before* any engine or cache is built."""
    name = args.spec_draft
    if name is None:
        return None
    if name == "self":
        return model, params
    try:
        dcfg = get_config(name)
    except KeyError:
        print(f"--spec-draft {name!r}: unknown zoo config", file=sys.stderr)
        raise SystemExit(2)
    tcfg = get_config(args.arch)
    if dcfg.vocab_size != tcfg.vocab_size:
        print(f"--spec-draft {name!r}: vocab {dcfg.vocab_size} != target "
              f"{args.arch!r} vocab {tcfg.vocab_size} — draft and target "
              f"must share a tokenizer", file=sys.stderr)
        raise SystemExit(2)
    if dcfg.family not in ("dense", "moe", "vlm"):
        print(f"--spec-draft {name!r}: family {dcfg.family!r} cannot draft "
              f"(speculation needs a positional KV cache for rollback)",
              file=sys.stderr)
        raise SystemExit(2)
    if args.smoke:
        dcfg = scale_down(dcfg, layers=2, d_model=256, d_ff=1024,
                          vocab=cfg.vocab_size)
    dcfg = dcfg.replace(use_flash=cfg.use_flash)
    dmodel = build_model(dcfg)
    dparams = dmodel.init(jax.random.PRNGKey(args.seed + 1))
    return dmodel, dparams


def _make_spec(args, draft) -> "Speculator | None":
    """One Speculator per engine: it owns a per-slot draft cache sized to
    the engine it attaches to, so replicas cannot share an instance."""
    if draft is None:
        return None
    dmodel, dparams = draft
    return Speculator(dmodel, dparams, k=args.spec_k,
                      adaptive=args.spec_adaptive)


def _run_engine(eng, prompts, args):
    """Submit ``prompts`` (SLO class ``i % 3``) and drain the engine."""
    reqs = [eng.submit(p, max_new_tokens=args.max_new_tokens,
                       priority=float(i % 3))
            for i, p in enumerate(prompts)]
    outs = eng.run_until_drained()
    return reqs, outs


def serve_single(args, model, params, cfg, draft=None) -> ServeResult:
    """One engine serves ``make_prompts``; exit code 1 unless every request
    finished."""
    eng = ServingEngine(model, params, speculator=_make_spec(args, draft),
                        **_engine_kw(args))
    t0 = time.perf_counter()
    reqs, outs = _run_engine(eng, make_prompts(args, cfg), args)
    dt = time.perf_counter() - t0
    done = sum(1 for r in reqs if r.state.name == "DONE")
    toks = sum(len(outs[r.rid]) for r in reqs)
    m = eng.batcher.metrics
    print(f"completed {done}/{len(reqs)} requests, {toks} tokens in "
          f"{dt:.2f}s host wall clock, compiles included [kv={eng.kv_mode}]")
    print(f"scheduler: steps={m['steps']} merged_prefills="
          f"{m['merged_prefills']} prefill_chunks={m['prefill_chunks']} "
          f"evicted_dead={m['evicted_dead']} preempted={m['preempted']}")
    if eng.paged:
        eng.alloc.check()
        print(f"paged kv: {eng.alloc.total_blocks} blocks x "
              f"{eng.alloc.block_size} tokens, "
              f"{eng.alloc.free_tokens} tokens free at drain")
    if eng.prefix_cache:
        s = eng.cache_stats
        print(f"prefix cache: hit_rate={eng.cache_hit_rate():.2f} "
              f"({s['hit_tokens']} hit / {s['miss_tokens']} miss tokens), "
              f"{eng.alloc.cached_tokens} tokens cached at drain, "
              f"evictions={eng.alloc.cache_evictions} "
              f"cow_forks={eng.alloc.cow_forks}")
    if eng.speculator is not None:
        s = eng.spec_stats
        print(f"speculative: rounds={s['rounds']} drafted={s['drafted']} "
              f"accepted={s['accepted']} "
              f"acceptance={s['acceptance_rate']:.2f} "
              f"merged_drafts={s['merged_drafts']} shed={s['shed']} "
              f"verify_calls={s['verify_calls']}")
    rc = 0
    if done != len(reqs):
        print(f"FAIL: {len(reqs) - done}/{len(reqs)} requests did not "
              f"finish", file=sys.stderr)
        rc = 1
    return ServeResult(rc, [outs[r.rid] for r in reqs], [eng])


def check_paged_equality(args, model, params, cfg, draft=None) -> int:
    """CI gate: the paged engine must generate exactly what the contiguous
    engine generates (fp32 bit-identical; bf16 identical in practice since
    the gathered logical views match the dense cache bit-for-bit).  Also
    runs the chunked-prefill paged engine — numerics-gated: every request
    must finish with the same token count, and token mismatches (argmax
    tie flips at chunk boundaries) are reported."""
    prompts = make_prompts(args, cfg)
    results = {}
    cache_eng = None
    modes = [
        ("contiguous", dict(kv_mode="contiguous", prefill_chunk=None,
                            prefix_cache=False)),
        ("paged", dict(kv_mode="paged", prefill_chunk=None,
                       prefix_cache=False)),
        ("paged+chunked", dict(kv_mode="paged",
                               prefill_chunk=args.prefill_chunk or 8,
                               prefix_cache=False)),
        ("paged+cache", dict(kv_mode="paged",
                             prefill_chunk=args.prefill_chunk or 8,
                             prefix_cache=True))]
    if draft is not None:
        # speculative decode must be greedy-exact: accepted tokens are
        # bit-identical to what the non-speculative engine emits
        modes.append(("paged+spec", dict(kv_mode="paged",
                                         prefill_chunk=None,
                                         prefix_cache=False)))
    for mode, over in modes:
        if mode != "contiguous" and not model.supports_paged:
            print(f"{mode}: family {cfg.family!r} has no paged path — skip")
            continue
        if mode == "paged+spec" and not model.supports_speculation:
            print(f"{mode}: family {cfg.family!r} has no verify kernel "
                  f"— skip")
            continue
        kw = dict(_engine_kw(args), **over)   # --num-blocks etc. flow in
        spec = _make_spec(args, draft) if mode == "paged+spec" else None
        eng = ServingEngine(model, params, speculator=spec, **kw)
        if mode == "paged+cache" and not eng.prefix_cache:
            print(f"{mode}: family {cfg.family!r} has no chunk kernel — skip")
            continue
        if mode == "paged+cache":
            # warm pass publishes the shared prefixes; the measured pass
            # below adopts them (requests admitted together in one plan
            # cannot hit each other's not-yet-published blocks)
            _run_engine(eng, prompts, args)
        reqs, outs = _run_engine(eng, prompts, args)
        assert all(r.state.name == "DONE" for r in reqs), mode
        if eng.paged:
            eng.alloc.check()
        if mode == "paged+cache":
            cache_eng = eng
        results[mode] = [outs[r.rid] for r in reqs]
        print(f"{mode}: {sum(len(o) for o in results[mode])} tokens")
    if "paged" not in results:
        return 0
    if results["paged"] != results["contiguous"]:
        bad = sum(1 for a, b in zip(results["paged"],
                                    results["contiguous"]) if a != b)
        print(f"FAIL: paged vs contiguous decode mismatch on {bad}/"
              f"{len(prompts)} requests", file=sys.stderr)
        return 1
    print("OK: paged decode == contiguous decode "
          f"({len(prompts)} requests)")
    chunked = results.get("paged+chunked")
    if chunked is not None:
        lens_ok = [len(a) for a in chunked] == \
            [len(a) for a in results["contiguous"]]
        if not lens_ok:
            print("FAIL: chunked prefill changed token counts",
                  file=sys.stderr)
            return 1
        same = chunked == results["contiguous"]
        print(f"OK: chunked prefill token counts match "
              f"(token-exact: {same})")
    cached = results.get("paged+cache")
    if cached is not None:
        if [len(a) for a in cached] != \
                [len(a) for a in results["contiguous"]]:
            print("FAIL: prefix cache changed token counts",
                  file=sys.stderr)
            return 1
        if cache_eng.cache_stats["hit_tokens"] == 0:
            print("FAIL: shared-prefix prompts produced zero cache hits",
                  file=sys.stderr)
            return 1
        same = cached == results["contiguous"]
        print(f"OK: prefix-cached prefill token counts match "
              f"(token-exact: {same}, hit_rate="
              f"{cache_eng.cache_hit_rate():.2f})")
    spec_outs = results.get("paged+spec")
    if spec_outs is not None:
        if spec_outs != results["contiguous"]:
            bad = sum(1 for a, b in zip(spec_outs, results["contiguous"])
                      if a != b)
            print(f"FAIL: speculative vs contiguous decode mismatch on "
                  f"{bad}/{len(prompts)} requests", file=sys.stderr)
            return 1
        print(f"OK: speculative decode == contiguous decode "
              f"(draft={args.spec_draft}, k={args.spec_k})")
    return 0


def serve_cluster(args, model, params, cfg, draft=None) -> ServeResult:
    """``--replicas`` engines behind a ``ClusterRouter`` serve
    ``make_prompts``; replica ``i`` lives on ``jax.devices()[i % n]``."""
    devices = jax.devices()

    def make_engine(i):
        return ServingEngine(model, params,
                             speculator=_make_spec(args, draft),
                             device=devices[i % len(devices)],
                             **_engine_kw(args))

    chaotic = args.chaos is not None or args.autoscale
    replicas = [EngineReplica(i, make_engine(i))
                for i in range(args.replicas)]
    policy = StealPolicy(amount=args.steal, placement=args.placement)
    # Chaos/autoscale runs get liveness + speed tracking: a killed engine
    # stops responding, the heartbeat declares it dead, and the router
    # replays its in-flight requests elsewhere (docs/operations.md).
    heartbeat = (HeartbeatMonitor(timeout_s=args.heartbeat_timeout)
                 if chaotic else None)
    straggler = (StragglerDetector(num_hosts=args.replicas)
                 if chaotic else None)
    router = ClusterRouter(replicas, policy=policy,
                           telemetry=ClusterTelemetry(args.replicas),
                           heartbeat=heartbeat, straggler=straggler)
    autoscaler = None
    if args.autoscale:
        ceiling = args.max_replicas or 2 * args.replicas
        autoscaler = Autoscaler(AutoscalePolicy(
            min_replicas=args.replicas, max_replicas=ceiling,
            target_backlog=args.autoscale_target,
            up_ticks=2, down_ticks=8, cooldown_s=0.5))
    t0 = time.perf_counter()
    reqs = []
    for i, prompt in enumerate(make_prompts(args, cfg)):
        req = Request(prompt_len=len(prompt),
                      max_new_tokens=args.max_new_tokens,
                      priority=float(i % 3))
        router.submit(req, tokens=prompt)
        reqs.append(req)
    submitted = [r for r in reqs if r.state.name != "CANCELLED"]

    if not chaotic:
        router.run_until_drained()
    else:
        tel = router.telemetry
        kill_after = max(1, len(submitted) // 4)
        killed = None
        for step in range(200_000):
            router.step()
            if (args.chaos == "kill-one" and killed is None
                    and tel.finished >= kill_after and router.outstanding):
                # Kill the engine that owns the most in-flight work so the
                # crash actually displaces something worth replaying.
                owners = Counter(o for o in router._owner.values()
                                 if o in router.placeable)
                if owners:
                    killed = owners.most_common(1)[0][0]
                    router.replicas[killed].dead = True
                    print(f"[chaos] killed replica {killed} after "
                          f"{tel.finished} finishes "
                          f"({owners[killed]} requests in flight on it)")
            if autoscaler is not None and step % 4 == 0:
                alive = router.placeable
                backlog = sum(router.replicas[i].backlog_weight()
                              for i in alive)
                delta = autoscaler.observe(time.monotonic(), len(alive),
                                           backlog)
                if delta > 0:
                    for _ in range(delta):
                        idx = len(router.replicas)
                        router.add_replica(
                            EngineReplica(idx, make_engine(idx)))
                        print(f"[autoscale] added replica {idx}")
                    tel.record_scale(time.perf_counter() - t0, delta,
                                     len(router.placeable))
                    router.steal_tick()
                elif delta < 0:
                    victim = min(alive,
                                 key=lambda i:
                                 (router.replicas[i].backlog_weight(), i))
                    if router.retire_replica(victim):
                        tel.record_scale(time.perf_counter() - t0, -1,
                                         len(router.placeable))
                        print(f"[autoscale] retiring replica {victim}")
            if router.drained():
                break
        else:
            print("FAIL: cluster did not drain within step budget",
                  file=sys.stderr)
            return ServeResult(1, [], [])

    dt = time.perf_counter() - t0
    done = sum(1 for r in reqs if r.state.name == "DONE")
    toks = sum(r.generated for r in reqs)
    print(f"completed {done}/{len(reqs)} requests, {toks} tokens in "
          f"{dt:.2f}s host wall clock, compiles included, on "
          f"{args.replicas} replicas")
    tel = router.telemetry
    print(tel.report())
    summary = tel.summary()
    spec = summary["spec"]
    if spec["drafted_tokens"]:
        print(f"speculative: drafted={spec['drafted_tokens']} "
              f"accepted={spec['accepted_tokens']} "
              f"acceptance={spec['acceptance_rate']:.2f} "
              f"requests={spec['requests']}")
    if chaotic:
        ch, auto = summary["chaos"], summary["autoscale"]
        print(f"chaos: crashes={ch['crashes']} "
              f"replayed={ch['requests_replayed']} "
              f"recoveries={ch['recoveries']} "
              f"recovery_mean={ch['recovery_mean_s']:.3f}s "
              f"p99_under_failure={ch['p99_under_failure_s']:.3f}s")
        print(f"autoscale: ups={auto['scale_ups']} "
              f"downs={auto['scale_downs']} peak={auto['replicas_peak']} "
              f"final={auto['replicas_final']}")
    for h in router.health():
        if h.get("dead"):
            print(f"  replica {h['replica_id']}: dead")
            continue
        print(f"  replica {h['replica_id']}: backlog={h['backlog_weight']} "
              f"waiting={h['waiting']} active={h['active']}"
              + (f" free_kv={h['free_kv_tokens']}"
                 if "free_kv_tokens" in h else ""))

    # Acceptance gates: every request reaches a terminal state with nothing
    # silently lost; under chaos, replayed counts match what telemetry
    # recorded at each crash, and per-SLO-class telemetry accounts for
    # every finish.
    ok = True
    if done != len(submitted):
        print(f"FAIL: {len(submitted) - done} submitted requests did "
              f"not finish", file=sys.stderr)
        ok = False
    if chaotic:
        if args.chaos == "kill-one":
            if killed is None:
                print("FAIL: chaos kill never triggered", file=sys.stderr)
                ok = False
            displaced = sum(e.get("displaced", 0) for e in summary["events"]
                            if e["kind"] == "crash")
            replayed = summary["chaos"]["requests_replayed"]
            if replayed != displaced:
                print(f"FAIL: telemetry replay mismatch: replayed="
                      f"{replayed} displaced-at-crash={displaced}",
                      file=sys.stderr)
                ok = False
            if killed is not None and displaced == 0:
                print("FAIL: crash displaced no requests", file=sys.stderr)
                ok = False
        want = Counter(r.priority for r in reqs if r.state.name == "DONE")
        for prio, n in sorted(want.items()):
            got = summary["per_class"].get(str(prio), {}).get("count", 0)
            if got != n:
                print(f"FAIL: SLO class {prio}: telemetry counted {got} "
                      f"finishes, engines report {n}", file=sys.stderr)
                ok = False
        if ok:
            print(f"OK: chaos/autoscale smoke — {done}/{len(submitted)} "
                  f"finished, replay bookkeeping consistent")
    # a stolen request's tokens live on the engine that finished it; a dead
    # engine's copies are stale (its requests replayed elsewhere)
    outs = {}
    for rep in router.replicas:
        if not rep.dead:
            outs.update(rep.engine.outputs)
    return ServeResult(0 if ok else 1, [outs.get(r.rid, []) for r in reqs],
                       [rep.engine for rep in router.replicas])


def _int_list(text: str) -> List[int]:
    return [int(x) for x in text.split(",") if x]


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="repro.launch.serve")
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--s-max", type=int, default=128)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--prompt-lens", type=_int_list, default=None,
                    help="comma-separated total prompt lengths, cycled "
                         "over the requests (default: 4-31 token tails, "
                         "every other one behind a 16-token shared prefix)")
    ap.add_argument("--steal", default="half_work",
                    choices=["half_work", "half_count", "none"])
    ap.add_argument("--placement", default="round_robin",
                    choices=["round_robin", "random", "least_of_d",
                             "least_work", "slo_aware", "cache_affinity",
                             "cost_model"])
    ap.add_argument("--chaos", default=None, choices=["kill-one"],
                    help="fault injection: kill-one marks the busiest "
                         "engine dead mid-run; the heartbeat declares it, "
                         "its requests replay elsewhere, and the run exits "
                         "1 unless every request finishes with consistent "
                         "replay telemetry")
    ap.add_argument("--heartbeat-timeout", type=float, default=2.0,
                    help="seconds without a step response before a replica "
                         "is declared dead (chaos/autoscale cluster runs)")
    ap.add_argument("--autoscale", action="store_true",
                    help="scale the live fleet from telemetry backlog "
                         "(queue depth weighted by cache-hit-adjusted "
                         "remaining work); --replicas is the floor")
    ap.add_argument("--max-replicas", type=int, default=None,
                    help="autoscale ceiling (default: 2x --replicas)")
    ap.add_argument("--autoscale-target", type=float, default=256.0,
                    help="backlog weight per replica the autoscaler aims "
                         "to hold (token-units of remaining work)")
    # Paged KV: the default "auto" pages every family with a paged decode
    # path (dense/MoE/VLM/hybrid) and falls back to the dense per-slot
    # cache elsewhere (SSM, enc-dec).
    ap.add_argument("--kv", default="auto",
                    choices=["auto", "paged", "contiguous"])
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: tokens per chunk task "
                         "(paged mode, chunk-capable families)")
    ap.add_argument("--admission", default="strategy",
                    choices=["strategy", "fifo", "cache_aware"],
                    help="fifo = arrival-ordered admission baseline; "
                         "cache_aware = priority/steal weight use uncached "
                         "remaining work")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="prefix caching: refcounted copy-on-write KV "
                         "block sharing keyed by chained content hashes "
                         "(paged, chunk-capable families)")
    ap.add_argument("--cache-policy", default="aware",
                    choices=["aware", "oblivious"],
                    help="aware = scheduling sees the cache (cache-aware "
                         "admission + steal weights); oblivious = cache on "
                         "but strategies keep the cold cost model")
    ap.add_argument("--overflow", default="reject",
                    choices=["reject", "truncate", "allow"],
                    help="requests whose prompt+budget exceed the KV ring: "
                         "reject at submit (default), truncate the token "
                         "budget, or allow the legacy self-corrupting wrap")
    ap.add_argument("--spec-draft", default=None,
                    help="speculative decoding: zoo config to draft with "
                         "('self' = the target drafts for itself); the "
                         "draft must share the target's vocab and have a "
                         "positional KV cache (dense/moe/vlm)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per speculation round")
    ap.add_argument("--spec-adaptive", dest="spec_adaptive",
                    action="store_true", default=True,
                    help="adapt per-request k from the acceptance-rate EMA "
                         "(default on)")
    ap.add_argument("--no-spec-adaptive", dest="spec_adaptive",
                    action="store_false")
    ap.add_argument("--check-paged-equality", action="store_true",
                    help="CI gate: paged and contiguous engines must "
                         "generate identical tokens (exit 1 on mismatch)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    # Pallas kernels on the hot path: flash prefill/decode + grouped-matmul
    # MoE + WKV.  Default: compiled kernels when JAX's backend is the TPU,
    # XLA otherwise; --use-flash forces the kernels (interpret mode on the
    # CPU, which validates the path but is far slower than XLA) and
    # --no-use-flash forces XLA.
    ap.add_argument("--use-flash", dest="use_flash", action="store_true",
                    default=None)
    ap.add_argument("--no-use-flash", dest="use_flash", action="store_false")
    args = ap.parse_args(argv)
    if args.prompt_lens and min(args.prompt_lens) <= SYS_PREFIX:
        ap.error(f"--prompt-lens must exceed the {SYS_PREFIX}-token shared "
                 f"prefix")
    return args


def build(args) -> tuple:
    """``(cfg, model, params)`` for ``args``: the published config (or its
    ``--smoke`` cut), kernels per ``--use-flash``, params from ``--seed``."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = scale_down(cfg, layers=4, d_model=256, d_ff=1024,
                         vocab=min(cfg.vocab_size, 32768))
    use_flash = has_tpu() if args.use_flash is None else args.use_flash
    cfg = cfg.replace(use_flash=use_flash)
    model = build_model(cfg)
    # one compiled program: op-by-op init of a full-width model is slow
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    return cfg, model, params


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = parse_args(argv)
    enable_compile_cache()
    cfg, model, params = build(args)
    draft = _build_draft(args, model, params, cfg)
    if args.check_paged_equality:
        return check_paged_equality(args, model, params, cfg, draft)
    if args.replicas > 1:
        return serve_cluster(args, model, params, cfg, draft).rc
    return serve_single(args, model, params, cfg, draft).rc


if __name__ == "__main__":
    sys.exit(main())
