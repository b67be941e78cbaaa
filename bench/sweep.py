#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: the highest arrival rate at
which the backlog does not grow over a window.

    python3 bench/sweep.py --workload qwen2-1.5b.code --rates 2,3,4,5,6 \\
        --seconds 20

One process, one engine: after the warm-up, each rate gets a window of its
own traffic (the cell's mix at that rate) and a drain.  Per rate it prints
one JSON line: requests due, time to first token (median and p90) of the
first and the last third of them by due time, and the backlog (due but
without a first token) at the window's close.  A backlog that grows shows
as a last third much slower than the first.  The cell's ``rate_per_s`` is
set to about four fifths of the knee.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda s: [float(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench.harness import Run, percentile
    from bench.traffic import Traffic
    run = Run(ROOT, args.workload, args.seed, args.seconds, False)
    run._devices()
    run._count_compiles()
    run._by_rid, run._active, run._first = {}, [], []
    eng = run._build()
    run.traffic = Traffic(run.mix, run.cell, args.seed, args.seconds,
                          run.dims.vocab)
    run._warmup(eng)
    for rate in args.rates:
        run.cell = dict(run.cell, rate_per_s=rate)
        run.traffic = Traffic(run.mix, run.cell, args.seed, args.seconds,
                              run.dims.vocab)
        run.requests, run.steps = [], []
        run._by_rid, run._active = {}, []
        t0, t_end, _ = run._window(eng)
        backlog = sum(1 for r in run.requests if not r.stamps
                      or r.stamps[0] > t_end)
        deadline = time.perf_counter() + 60.0
        run._drain(eng, lambda: time.perf_counter() > deadline)
        reqs = sorted(run.requests, key=lambda r: r.due)
        third = max(1, len(reqs) // 3)

        def ttft(rs, p):
            lat = [(r.stamps[0] if r.stamps else deadline) - r.due
                   for r in rs]
            return round(percentile(lat, p) * 1e3, 1)

        print(json.dumps({
            "rate_per_s": rate, "due": len(reqs), "backlog_at_close": backlog,
            "ttft_p50_ms_first_third": ttft(reqs[:third], 50),
            "ttft_p50_ms_last_third": ttft(reqs[-third:], 50),
            "ttft_p90_ms": ttft(reqs, 90),
            "steps": len([s for s in run.steps if t0 <= s[0] < t_end])}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
