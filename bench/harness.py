"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

The window drives the program's normal serving path through its public
API: ``build_model(cfg)``, parameters made on the device from the seed,
``ServingEngine`` on the paged pool with chunked prefill, then ``submit``,
``step`` and ``outputs``.  Nothing is added to the program: the spans and
counters of a traced run are wrappers on the engine *instance*.

A run, in order:

1. set-up: weights, engine, warm-up traffic from its own seed stream (one
   request per slot, every distinct prompt length), and, for a closed loop,
   the first wave prefilled into every slot;
2. the window: ``--seconds`` of traffic.  Every token is stamped when the
   ``step()`` that produced it returns (its value is on the host by then);
3. the check: a closed loop keeps stepping, with no new requests, until
   enough finished requests are at hand; an open loop until every request
   due in the window has finished (at most ``DRAIN_S`` past the close);
4. the peak device memory is read, the program's state is freed, and a
   sample of finished requests, drawn from the seed with the longest among
   them, is teacher-forced through the float32 reference.  ``correct``
   holds when the widest gap by which a served token's reference logit lies
   below the reference's best is within the cell's limit, and every sampled
   request got all its tokens.

End-to-end metrics (host clock, ``--trace 0``):

* ``setup_s``: process start to window start;
* ``ttft_p{90,..}_ms``: due time to first token on the host, over every
  request due in the window (one that never gets one counts with the time
  it waited);
* ``tbt_p{95,..}_ms``: gap between consecutive tokens of a request, both in
  the window;
* ``output_tokens_per_s``: tokens produced by the window's steps over the
  time those steps took.

With ``--trace 1`` the window is profiled and the per-layer metrics come
from their readers (``bench/metrics/<name>.py``), each given a ``Context``.
"""
from __future__ import annotations

import gc
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import flops
from bench.spec import Spec
from bench.traffic import RequestSpec, Traffic, seed_words

__all__ = ["Run", "main", "Context", "NoChip", "percentile", "judge"]

#: served tokens the correctness sample aims for
CHECK_TOKENS = 384
#: most requests in the correctness sample
CHECK_MAX_REQUESTS = 32
#: how long the check waits past the window's close
DRAIN_S = 60.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


_MODELS: Dict[str, object] = {}


def _model(kwargs: dict):
    """The program's model for a configuration, built once per process (so
    several runs in one process share its compiled programs)."""
    key = json.dumps(kwargs, sort_keys=True)
    if key not in _MODELS:
        from repro.configs.base import ModelConfig
        from repro.models import build_model
        _MODELS[key] = build_model(ModelConfig(**kwargs))
    return _MODELS[key]


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p``% of
    the values at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    return float(v[max(0, math.ceil(p / 100.0 * len(v)) - 1)])


def judge(checks: Dict[str, dict]) -> bool:
    """``correct``: every number compared is there and within its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


@dataclass
class Live:
    """One request as the harness sees it."""
    spec: RequestSpec
    prompt: np.ndarray
    due: float                      # host clock; None-free
    kind: str                       # warmup | first | window
    client: int = -1
    req: object = None
    stamps: List[float] = field(default_factory=list)
    first_plan: Optional[float] = None
    done_at: Optional[float] = None
    rejected: bool = False

    @property
    def rid(self) -> int:
        return self.req.rid


@dataclass
class Context:
    """What a per-layer metric's reader is given."""
    cell: dict
    hf: dict
    dims: flops.Dims
    peaks: dict
    window: tuple                   # (t0, t1) host clock, seconds
    steps: List[tuple]              # (start, end, active) per step
    #: (host time, live lengths of the active rows) per decode call
    decode_calls: List[tuple]
    #: (host time, start, length) per prefill chunk
    prefill_calls: List[tuple]
    requests: List[Live]
    trace: object = None            # bench.trace.Trace of the window
    #: the program each kind of call ran while traced (``Trace.assign``)
    programs: Dict[str, str] = field(default_factory=dict)

    def in_window(self, calls: List[tuple]) -> List[tuple]:
        return [c for c in calls if self.window[0] <= c[0] <= self.window[1]]

    def prefill_chunks(self) -> Optional[List[tuple]]:
        """(start, length) of the window's prefill chunks; None where a
        call's shape could not be read."""
        out = [(start, n) for _, start, n in self.in_window(self.prefill_calls)]
        return None if any(n is None for _, n in out) else out


class Run:
    """One run of one cell.  ``require_chip=False`` and ``engine_hook``
    exist for the tests: they drive a run on the CPU, and break the timed
    path underneath to see ``correct`` come out false."""

    def __init__(self, root: Path, workload: str, seed: int, seconds: float,
                 trace: bool, *, t_start: Optional[float] = None,
                 require_chip: bool = True,
                 engine_hook: Optional[Callable] = None,
                 log: Callable[[str], None] = None):
        self.spec = Spec(root)
        self.root = Path(root)
        self.wl = self.spec.workload(workload)
        self.name = workload
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.require_chip = require_chip
        self.engine_hook = engine_hook
        self.log = log or (lambda s: print(s, file=sys.stderr, flush=True))
        self.hf = self.spec.config(self.wl["config"])
        self.mix = self.spec.traffic(self.wl["traffic"])
        self.cell = self.spec.cell(workload)
        self.model = self.spec.model_module(self.wl["config"])
        self.dims = flops.Dims.from_config(self.hf)
        self.steps: List[tuple] = []
        self.decode_calls: List[List[int]] = []
        self.prefill_calls: List[tuple] = []
        self.requests: List[Live] = []
        self.compiles = 0
        self.cache_hits = 0

    # -- set-up ---------------------------------------------------------------
    def _devices(self):
        import jax
        devs = jax.devices()
        need = int(self.wl["chips"])
        if self.require_chip:
            if devs[0].platform != "tpu":
                raise NoChip(f"JAX found platform {devs[0].platform!r} "
                             f"({devs[0].device_kind}), not a TPU")
            if len(devs) < need:
                raise NoChip(f"the cell needs {need} chips, JAX found "
                             f"{len(devs)}")
        return devs[:need]

    def _count_compiles(self):
        """``compiles`` counts every program compiled or read from the
        persistent cache (JAX times both as one event); ``cache_hits`` the
        ones read from the cache."""
        import jax

        def on_duration(event, *_a, **_k):
            if event == COMPILE_EVENT:
                self.compiles += 1

        def on_event(event, **_k):
            if event == CACHE_HIT_EVENT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def _build(self):
        import jax
        from repro.serving.engine import ServingEngine
        model = _model(self.model.program_config(self.wl["config"], self.hf))
        params = self.model.program_params(self.hf, self.seed)
        jax.block_until_ready(params)
        c = self.cell
        eng = ServingEngine(
            model, params, max_batch=c["max_batch"], s_max=c["s_max"],
            prefill_token_budget=c["prefill_token_budget"], kv_mode="paged",
            block_size=c["block_size"], prefill_chunk=c["prefill_chunk"],
            admission="strategy")
        return eng

    def _instrument(self, eng) -> None:
        """Traced runs only: spans around the engine's step, plan, prefill
        and decode calls, and the shapes of each call for the FLOP and byte
        counts.  A span adds no wait: it closes when the call returns, so
        the device runs as it does untraced, and device time is given to
        decode and prefill by the programs they launch (``bench/trace.py``).
        An engine without one of these calls runs with that span left out."""
        from jax.profiler import TraceAnnotation
        from repro.core.device.request_scheduler import RequestState

        def rows():
            """Live lengths of the rows a decode call attends over: the
            prompt and every token so far, of each request holding a slot."""
            out = []
            for live in self._active:
                n = len(eng.outputs[live.rid])
                if n and live.req.state is RequestState.RUNNING:
                    out.append(live.spec.prompt_len + n)
            return out

        def span(name, call, record):
            def wrapped(*args, **kwargs):
                record(args, kwargs)
                with TraceAnnotation(name):
                    return call(*args, **kwargs)
            return wrapped

        def on_decode(args, kwargs):
            self.decode_calls.append((time.perf_counter(), rows()))

        def on_prefill(args, kwargs):
            try:
                start, n = int(args[4]), int(args[1]["tokens"].shape[1])
            except (IndexError, KeyError, TypeError, AttributeError):
                start = n = None        # another signature: no FLOP count
            self.prefill_calls.append((time.perf_counter(), start, n))

        if getattr(eng, "_decode", None) is not None:
            eng._decode = span("bench.decode", eng._decode, on_decode)
        if getattr(eng, "_prefill_chunk", None) is not None:
            eng._prefill_chunk = span("bench.prefill", eng._prefill_chunk,
                                      on_prefill)
        plan = eng.batcher.plan_step

        def plan_span():
            with TraceAnnotation("bench.plan"):
                p = plan()
            t = time.perf_counter()
            for r in p.prefill:
                live = self._by_rid.get(r.rid)
                if live is not None and live.first_plan is None:
                    live.first_plan = t
            return p

        eng.batcher.plan_step = plan_span
        eng.step = span("bench.step", eng.step, lambda a, k: None)

    # -- driving the engine ---------------------------------------------------
    def _submit(self, eng, spec: RequestSpec, kind: str, due: float,
                client: int = -1, stream: int = 2) -> Live:
        from repro.core.device.request_scheduler import AdmissionRejected
        idx = len(self.requests)
        live = Live(spec, self.traffic.tokens(idx, spec.prompt_len, stream),
                    due, kind, client)
        self.requests.append(live)
        try:
            live.req = eng.submit(live.prompt, spec.output_len)
        except AdmissionRejected:
            live.rejected = True
            return live
        self._by_rid[live.req.rid] = live
        self._active.append(live)
        return live

    def _step(self, eng) -> List[Live]:
        """One engine step; stamps its tokens and returns the requests it
        finished."""
        from repro.core.device.request_scheduler import RequestState
        t0 = time.perf_counter()
        n = eng.step()
        t = time.perf_counter()
        self.steps.append((t0, t, n))
        done = []
        keep = []
        for live in self._active:
            k = len(eng.outputs[live.rid])
            if k > len(live.stamps):
                live.stamps.extend([t] * (k - len(live.stamps)))
            if live.req.state is RequestState.DONE:
                live.done_at = t
                done.append(live)
            else:
                keep.append(live)
        self._active = keep
        return done

    def _drain(self, eng, until: Callable[[], bool]) -> None:
        while self._active and not until():
            self._step(eng)

    def _warmup(self, eng) -> None:
        for spec in self.traffic.warmup():
            self._submit(eng, spec, "warmup", time.perf_counter(),
                         stream=3)
        self._drain(eng, lambda: False)

    def _window(self, eng) -> tuple:
        """Drive the window; returns (t0, t_end, t_last)."""
        closed = self.traffic.loop == "closed"
        t0 = time.perf_counter()
        t_end = t0 + self.seconds
        if closed:
            for c in range(len(self._first), self.traffic.clients):
                self._submit(eng, self.traffic.next_request(), "window", t0,
                             client=c)
            pending: List[RequestSpec] = []
        else:
            pending = self.traffic.arrivals()
        nxt = 0
        t_last = t0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            while nxt < len(pending) and t0 + pending[nxt].due <= now:
                self._submit(eng, pending[nxt], "window",
                             t0 + pending[nxt].due)
                nxt += 1
            if not self._active:
                wake = t0 + pending[nxt].due if nxt < len(pending) else t_end
                time.sleep(max(0.0, min(wake, t_end) - now))
                continue
            for live in self._step(eng):
                if closed and live.client >= 0 and live.done_at < t_end:
                    self._submit(eng, self.traffic.next_request(), "window",
                                 live.done_at, client=live.client)
            t_last = self.steps[-1][1]
        return t0, t_end, max(t_last, t_end)

    # -- metrics --------------------------------------------------------------
    def _end_to_end(self, t0: float, t1: float, setup_s: float) -> Dict:
        names = {m["name"]: m["unit"] for m in self.spec.end_to_end(self.name)}
        out: Dict[str, dict] = {}
        window = [r for r in self.requests if r.kind == "window"]
        for name, unit in names.items():
            if name == "setup_s":
                v = setup_s
            elif name.startswith("ttft_p"):
                p = float(name[len("ttft_p"):].split("_")[0])
                lat = [((r.stamps[0] if r.stamps else self._t_check) - r.due)
                       for r in window]
                v = percentile(lat, p) * 1e3 if lat else None
            elif name.startswith("tbt_p"):
                p = float(name[len("tbt_p"):].split("_")[0])
                gaps = [b - a for r in self.requests
                        for a, b in zip(r.stamps, r.stamps[1:])
                        if a >= t0 and b <= t1]
                v = percentile(gaps, p) * 1e3 if gaps else None
            elif name == "output_tokens_per_s":
                n = sum(1 for r in self.requests for s in r.stamps
                        if t0 < s <= t1)
                v = n / (t1 - t0)
            else:
                raise KeyError(f"no harness rule for end-to-end metric "
                               f"{name!r}")
            if v is not None:
                out[name] = {"value": v, "unit": unit}
        return out

    def _per_layer(self, ctx: Context) -> Dict:
        out = {}
        for m in self.spec.per_layer(self.name):
            reader = self.spec.reader(m["name"])
            if reader is None:
                raise FileNotFoundError(f"bench/metrics/{m['name']}.py")
            v = reader.read(ctx)
            if v is not None:
                out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        return out

    # -- correctness ----------------------------------------------------------
    def _sample(self) -> List[Live]:
        """Finished window-phase and first-wave requests: the longest, then
        others in an order drawn from the seed, up to ``CHECK_TOKENS``
        served tokens or ``CHECK_MAX_REQUESTS`` requests."""
        done = [r for r in self.requests
                if r.kind != "warmup" and r.done_at is not None]
        if not done:
            return []
        longest = max(done, key=lambda r: (r.spec.prompt_len
                                           + len(r.stamps), -r.rid))
        rest = [r for r in done if r is not longest]
        order = np.random.default_rng([4] + seed_words(self.seed))
        rest = [rest[i] for i in order.permutation(len(rest))]
        pick, n = [longest], len(longest.stamps)
        for r in rest:
            if n >= CHECK_TOKENS or len(pick) >= CHECK_MAX_REQUESTS:
                break
            pick.append(r)
            n += len(r.stamps)
        return pick

    def _check(self, eng, control: bool = False) -> tuple:
        """(checks, control checks): each check is a number and the upper
        limit it must not pass.  With ``control`` the second are the same
        checks with the control's tokens in the program's place (the
        reference at the precision below the configuration's); else None."""
        sample = self.sample = self._sample()
        prompts = [r.prompt for r in sample]
        served = [np.asarray(eng.outputs[r.rid], np.int32) for r in sample]
        short = sum(1 for r, s in zip(sample, served)
                    if len(s) != r.spec.output_len)
        self._free(eng)
        limit = float(self.cell["check"]["logit_gap"])
        pairs = [(p, s) for p, s in zip(prompts, served) if len(s)]
        self.checked_tokens = sum(len(s) for _, s in pairs)
        checks = {"short_outputs": {"value": short, "limit": 0}}
        if not pairs:
            checks["logit_gap"] = {"value": None, "limit": limit}
            return checks, None
        res = self.model.reference_gaps(
            self.hf, self.seed, [p for p, _ in pairs], [s for _, s in pairs],
            control=control)
        checks["logit_gap"] = {
            "value": max(float(r["gap"].max()) for r in res),
            "limit": limit}
        if not control:
            return checks, None
        ctl = {"short_outputs": {"value": 0, "limit": 0},
               "logit_gap": {"value": max(float(r["control_gap"].max())
                                          for r in res), "limit": limit}}
        return checks, ctl

    def _free(self, eng) -> None:
        """Drop the program's parameters, pool and compiled state before
        the reference runs."""
        for attr in ("cache", "params", "last_token", "_table_dev"):
            setattr(eng, attr, None)
        self._engine = None
        gc.collect()

    # -- the run --------------------------------------------------------------
    def _lap(self, what: str) -> None:
        self.log(f"set-up: {what} at {time.perf_counter() - self.t_start:.3f}"
                 f" s")

    def execute(self, control: bool = False) -> dict:
        devs = self._devices()
        import jax
        self._lap("devices found")
        self._count_compiles()
        self._by_rid: Dict[int, Live] = {}
        self._active: List[Live] = []
        eng = self._build()
        self._lap("weights and engine built")
        self._engine = eng
        if self.trace:
            self._instrument(eng)
        if self.engine_hook is not None:
            self.engine_hook(eng)
        self.traffic = Traffic(self.mix, self.cell, self.seed, self.seconds,
                               self.dims.vocab)
        self._warmup(eng)
        self._lap(f"warm-up served ({self.compiles} programs compiled or "
                  f"loaded so far, {self.cache_hits} from the cache)")
        self._first = []
        if self.traffic.loop == "closed":
            for c, spec in enumerate(self.traffic.first_wave()):
                self._first.append(self._submit(
                    eng, spec, "first", time.perf_counter(), client=c))
            self._drain(eng, lambda: all(r.stamps for r in self._first))
            self._lap("first wave in every slot")
        tracedir = None
        if self.trace:
            tracedir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(tracedir, profiler_options=opts)
            traced = [time.perf_counter()]
        compiles0 = self.compiles
        from jax.profiler import TraceAnnotation
        setup_s = time.perf_counter() - self.t_start
        with TraceAnnotation("bench.window"):
            t0, t_end, t1 = self._window(eng)
        compiles = self.compiles - compiles0
        if self.trace:
            traced.append(time.perf_counter())
            jax.profiler.stop_trace()
        # the check: finish what the window started
        deadline = time.perf_counter() + DRAIN_S
        if self.traffic.loop == "closed":
            def enough():
                return (sum(len(r.stamps) for r in self.requests
                            if r.kind != "warmup" and r.done_at)
                        >= CHECK_TOKENS or time.perf_counter() > deadline)
        else:
            def enough():
                return time.perf_counter() > deadline
        self._drain(eng, enough)
        self._t_check = time.perf_counter()
        mem = [d.memory_stats() or {} for d in devs]
        peak = max(s.get("peak_bytes_in_use", 0) for s in mem)
        window = [r for r in self.requests if r.kind == "window"]
        attempted = len(window) + len(self._first)
        # an open loop's request that never got a token failed; a closed
        # loop's last requests may still wait for a slot at the close
        failed = sum(1 for r in window + self._first
                     if r.rejected or (self.traffic.loop == "open"
                                       and not r.stamps))
        if self.trace:
            metrics = {}
        else:
            metrics = self._end_to_end(t0, t1, setup_s)
        steps_in = [s for s in self.steps if s[0] >= t0 and s[0] < t_end]
        self.log(f"window: {t1 - t0:.3f} s, {len(steps_in)} steps, "
                 f"{sum(1 for r in window if r.stamps)}/{len(window)} "
                 f"window requests served, {compiles} compiles in the "
                 f"window, {self.compiles} programs compiled or loaded in "
                 f"all, {self.cache_hits} from the cache, set-up "
                 f"{setup_s:.3f} s, peak device memory "
                 f"{peak} B")
        t_ref = time.perf_counter()
        checks, self.control = self._check(eng, control=control)
        self.log(f"check: reference took {time.perf_counter() - t_ref:.3f} s")
        self.log(f"check: {self.checked_tokens} served tokens of "
                 f"{len(self.sample)} requests against the reference")
        breakdown = None
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": int(peak)}
        if self.trace:
            from bench.trace import Trace, find_xplane
            tr = Trace(find_xplane(Path(tracedir)))
            shutil.rmtree(tracedir, ignore_errors=True)
            win = tr.window()

            def n_traced(calls):
                return sum(1 for c in calls if traced[0] <= c[0] <= traced[1])
            programs = tr.assign({"decode": n_traced(self.decode_calls),
                                  "prefill": n_traced(self.prefill_calls)})
            ctx = Context(self.cell, self.hf, self.dims,
                          flops.peaks(devs[0].device_kind)
                          if self.require_chip else {}, (t0, t1),
                          steps_in, self.decode_calls, self.prefill_calls,
                          self.requests, tr, programs)
            metrics = self._per_layer(ctx)
            device["busy_s"] = tr.busy_s(win)
            device["window_s"] = (win[1] - win[0]) * 1e-9
            breakdown = {
                "device_ops": [list(x) for x in tr.top_ops(win, programs)],
                "idle_gaps": [list(x) for x in tr.idle_gaps(win)]}
        result = {"correct": judge(checks), "attempted": attempted,
                  "failed": failed, "metrics": metrics, "device": device}
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["compiles_in_window"] = compiles
        result["checks"] = checks
        return result


def main(root: Path, args, t_start: float) -> int:
    try:
        run = Run(root, args.workload, args.seed, args.seconds,
                  bool(args.trace), t_start=t_start)
        result = run.execute()
    except NoChip as e:
        print(f"bench: {e}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
