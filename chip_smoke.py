#!/usr/bin/env python3
"""Chip smoke: serve qwen2-1.5b at its full published width on a TPU through
``repro.launch.serve``, and check what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four one-chip replicas vs one engine

Params are bf16 and drawn from ``--seed``: there are no weights to load.
The traffic is ``WORKLOAD``: 16 requests with prompts of 256, 512, 768 and
1024 tokens (every other one behind a 16-token shared prefix), 32 new
tokens each, 8 decode slots, 2048-token KV rings of 16-token blocks.

One chip, in order:

1. whole-prompt prefill (flash kernel) and paged flash decode: every
   request finishes, the block allocator's invariants hold, and the
   compiled decode step holds a Mosaic kernel (``tpu_custom_call``);
2. chunked prefill with the prefix cache on: every request finishes and
   the shared prefix is hit;
3. the kernel path against the XLA path on the same params and prompts:
   prefill last-position logits and one B=8 paged decode step's logits
   agree within ``LOGIT_RTOL``;
4. on the XLA path, paged decode emits exactly the greedy tokens of the
   contiguous cache (the ``--check-paged-equality`` gate).

Four chips (``--four-chips``, and nothing else): the same prompts through
``--replicas 4 --steal half_work``, replica ``i`` on ``jax.devices()[i]``,
with chunked prefill so that stolen requests carry their prefix KV; every
request's greedy tokens must equal those of one engine on one chip.

Lines before the last are host-side counts and host wall-clock seconds
(compiles included), not device metrics.  The last line is one JSON object
naming the device; a failed phase, or a backend other than the TPU, exits
nonzero without it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

WORKLOAD = ["--arch", "qwen2-1.5b", "--requests", "16", "--max-batch", "8",
            "--s-max", "2048", "--block-size", "16",
            "--prompt-lens", "256,512,768,1024", "--max-new-tokens", "32"]
#: chunk size of the chunked-prefill phases (prompt lengths are multiples,
#: so one chunk shape serves every cold prompt)
CHUNK = ["--prefill-chunk", "256"]

#: kernel-vs-XLA tolerance on ||kernel - xla||_2 / ||xla||_2 of the logits.
#: bf16 keeps 8 significant bits (rounding ~2e-3 per op); the two paths
#: round at different points (XLA casts the softmax weights to bf16, the
#: flash kernel keeps them in fp32), and the difference compounds over 28
#: layers.  A masking or indexing fault moves the logits by O(1).
LOGIT_RTOL = 5e-2


def _alloc_check(eng) -> str:
    """The block allocator's no-leak / refcount invariants, as a message
    ("" when they hold)."""
    try:
        eng.alloc.check()
    except AssertionError as e:
        return f"violated: {e}"
    return ""


def _rel_l2(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


class Smoke:
    """Phase bookkeeping: every check prints one line; any failure makes
    the run fail at the end."""

    def __init__(self):
        self.failed = []
        self.t0 = time.perf_counter()

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"[{'ok' if ok else 'FAIL'}] {name}" + (f": {detail}"
                                                      if detail else ""),
              flush=True)
        if not ok:
            self.failed.append(name)
        return ok

    def lap(self, what: str) -> None:
        print(f"{what}: {time.perf_counter() - self.t0:.1f} s host wall "
              f"clock since start, compiles included", flush=True)


def _prefill_into_pool(model, params, prompts, s_max: int, block: int):
    """Prefill each prompt alone and scatter its KV into a paged pool, one
    request per batch slot.  Returns (prefill last-position logits [B, V],
    pool, block table [B, s_max // block]).  The jitted functions are the
    model's own, so the serving engines' compiled programs are reused."""
    import jax
    import jax.numpy as jnp
    prefill = jax.jit(model.prefill, static_argnums=2)
    insert = jax.jit(model.insert_prefill_paged)
    b, per = len(prompts), s_max // block
    pool = model.init_paged_cache(b, b * per + 1, block)
    table = 1 + np.arange(b * per, dtype=np.int32).reshape(b, per)
    last = []
    for i, p in enumerate(prompts):
        logits, dense = prefill(params, {"tokens": jnp.asarray(p[None])},
                                s_max)
        pool = insert(pool, dense, jnp.asarray(table[i]), i)
        last.append(logits[0, -1])
    last = jnp.stack(last)
    return last, pool, table


def run_one_chip(base: list, smoke: Smoke) -> None:
    import jax
    import jax.numpy as jnp
    from repro.launch import serve
    from repro.models import build_model

    args = serve.parse_args(base + ["--use-flash"])
    cfg, model, params = serve.build(args)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    smoke.lap(f"set-up: {cfg.name} d_model={cfg.d_model} "
              f"layers={cfg.num_layers} vocab={cfg.vocab_size}, "
              f"{n_params} params from seed {args.seed}")

    # 1. whole-prompt prefill + paged decode, kernels compiled
    res = serve.serve_single(args, model, params, cfg)
    eng = res.engines[0]
    done = sum(1 for t in res.tokens if len(t) == args.max_new_tokens)
    smoke.check("kernels: every request finished", res.rc == 0
                and done == args.requests,
                f"{done}/{args.requests} requests, "
                f"{sum(map(len, res.tokens))} tokens")
    bad = _alloc_check(eng)
    smoke.check("kernels: block allocator invariants (alloc.check)",
                not bad, bad or f"{eng.alloc.free_tokens} of "
                f"{eng.alloc.total_blocks * eng.alloc.block_size} tokens "
                f"free at drain")
    hlo = jax.jit(model.decode_step_paged).lower(
        eng.params, eng.last_token, eng.cache, jnp.asarray(eng.table),
        jnp.asarray(eng.slot_pos, jnp.int32)).compile().as_text()
    smoke.check("kernels: compiled decode step holds tpu_custom_call",
                "tpu_custom_call" in hlo)
    del res, eng
    smoke.lap("phase 1 (whole-prompt prefill, flash decode) done")

    # 2. chunked prefill behind the prefix cache
    args_c = serve.parse_args(base + CHUNK + ["--use-flash",
                                              "--prefix-cache"])
    res = serve.serve_single(args_c, model, params, cfg)
    eng = res.engines[0]
    done = sum(1 for t in res.tokens if len(t) == args_c.max_new_tokens)
    smoke.check("chunked+cache: every request finished", res.rc == 0
                and done == args_c.requests,
                f"{done}/{args_c.requests} requests, "
                f"{eng.batcher.metrics['prefill_chunks']} prefill chunks")
    bad = _alloc_check(eng)
    smoke.check("chunked+cache: block allocator invariants (alloc.check)",
                not bad, bad or f"{eng.alloc.cached_tokens} tokens cached "
                f"at drain")
    hits = eng.cache_stats["hit_tokens"]
    smoke.check("chunked+cache: shared prefix hit", hits > 0,
                f"{hits} hit / {eng.cache_stats['miss_tokens']} miss "
                f"prompt tokens")
    del res, eng
    smoke.lap("phase 2 (chunked prefill, prefix cache) done")

    # 3. kernel path vs XLA path, same params and prompts
    model_x = build_model(cfg.replace(use_flash=False))
    prompts = serve.make_prompts(args, cfg)[:args.max_batch]
    pos = jnp.asarray([len(p) for p in prompts], jnp.int32)
    k_last, k_pool, table = _prefill_into_pool(
        model, params, prompts, args.s_max, args.block_size)
    x_last, x_pool, _ = _prefill_into_pool(
        model_x, params, prompts, args.s_max, args.block_size)
    rel = _rel_l2(k_last, x_last)
    smoke.check("kernel vs XLA: prefill last-position logits",
                rel <= LOGIT_RTOL,
                f"rel_l2={rel:.3e} (tol {LOGIT_RTOL:.0e}) max_abs="
                f"{float(jnp.max(jnp.abs(k_last - x_last))):.3e} over "
                f"{len(prompts)} prompts of {sorted({len(p) for p in prompts})}"
                f" tokens")
    tok = jnp.argmax(x_last, axis=-1).astype(jnp.int32)[:, None]
    k_dec, _ = jax.jit(model.decode_step_paged)(
        params, tok, k_pool, jnp.asarray(table), pos)
    x_dec, _ = jax.jit(model_x.decode_step_paged)(
        params, tok, x_pool, jnp.asarray(table), pos)
    rel = _rel_l2(k_dec, x_dec)
    agree = int(jnp.sum(jnp.argmax(k_dec[:, -1], -1)
                        == jnp.argmax(x_dec[:, -1], -1)))
    smoke.check("kernel vs XLA: one B=8 paged decode step's logits",
                rel <= LOGIT_RTOL,
                f"rel_l2={rel:.3e} (tol {LOGIT_RTOL:.0e}) max_abs="
                f"{float(jnp.max(jnp.abs(k_dec - x_dec))):.3e}, greedy "
                f"agreement {agree}/{len(prompts)}")
    del k_pool, x_pool, k_last, x_last, k_dec, x_dec
    smoke.lap("phase 3 (kernel vs XLA logits) done")

    # 4. paged == contiguous greedy tokens on the XLA path
    args_x = serve.parse_args(base + CHUNK + ["--no-use-flash"])
    rc = serve.check_paged_equality(args_x, model_x, params,
                                    cfg.replace(use_flash=False))
    smoke.check("XLA: paged decode == contiguous decode "
                "(--check-paged-equality)", rc == 0)
    smoke.lap("phase 4 (paged vs contiguous, XLA) done")


def run_four_chips(base: list, smoke: Smoke) -> None:
    import jax
    from repro.launch import serve

    devices = jax.devices()
    if not smoke.check("four chips: at least 4 devices", len(devices) >= 4,
                       f"found {len(devices)}"):
        return
    args = serve.parse_args(base + CHUNK)
    cfg, model, params = serve.build(args)
    jax.block_until_ready(params)
    smoke.lap(f"set-up: {cfg.name} d_model={cfg.d_model} "
              f"layers={cfg.num_layers}, kernels "
              f"{'on' if cfg.use_flash else 'off'}")

    ref = serve.serve_single(args, model, params, cfg)
    smoke.check("one engine on one chip: every request finished",
                ref.rc == 0, f"{sum(map(len, ref.tokens))} tokens")
    del ref.engines[:]      # free its KV pool before four more are built
    smoke.lap("one-engine reference done")

    args_4 = serve.parse_args(base + CHUNK + ["--replicas", "4",
                                              "--steal", "half_work"])
    res = serve.serve_cluster(args_4, model, params, cfg)
    smoke.check("four replicas: every request finished", res.rc == 0,
                f"{sum(map(len, res.tokens))} tokens")
    for i, eng in enumerate(res.engines):
        homes = {d for leaf in jax.tree.leaves((eng.params, eng.cache))
                 for d in leaf.devices()}
        smoke.check(f"replica {i}: params and KV pool on devices[{i}]",
                    homes == {devices[i]},
                    f"{sorted(str(d) for d in homes)}, finished "
                    f"{len(eng.outputs)} requests")
    same = sum(1 for a, b in zip(res.tokens, ref.tokens) if a == b)
    smoke.check("four replicas == one engine, greedy tokens per request",
                same == len(ref.tokens) == args.requests,
                f"{same}/{args.requests} requests identical")
    smoke.lap("four-replica phase done")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="four one-chip replicas behind the router, "
                         "compared with one engine on one chip")
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e}); run it from a checkout of the repository",
              file=sys.stderr)
        return 2
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU; this smoke runs only on "
              f"the chip", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    counts = {"cache_hits": 0, "cache_misses": 0}

    def on_event(event: str, **_):
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and name in counts:
            counts[name] += 1

    jax.monitoring.register_event_listener(on_event)
    smoke = Smoke()
    base = WORKLOAD + ["--seed", str(opts.seed)]
    if opts.four_chips:
        run_four_chips(base, smoke)
    else:
        run_one_chip(base, smoke)
    print(f"compile cache {cache_dir}: {counts['cache_hits']} hits, "
          f"{counts['cache_misses']} misses")
    if smoke.failed:
        print(f"chip_smoke: {len(smoke.failed)} check(s) failed: "
              f"{smoke.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
