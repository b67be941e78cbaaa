#!/usr/bin/env python3
"""Readings behind a cell's correctness limit, on the chip, in one process.

    python3 bench/calibrate.py --workload qwen2-1.5b.longgen \\
        --seeds 2001-2012 --control-seeds 2001-2003 --seconds 10

For every seed it makes one run of the cell as ``run.py`` does (a short
window, then the check on as many served tokens as a full run compares)
and prints one JSON line: the program's widest logit gap against the
float32 reference and its ``correct``, and, for the control seeds, the
control's: the control's tokens (those the fp8-weight reference puts first
at the same positions) judged in the program's place, by the same checks
and limits, with ``control_correct``.  The limit in
``bench/cells/<workload>.json`` lies between the largest program reading
and the smallest control reading.

    python3 bench/calibrate.py --workload qwen2-1.5b.longgen \
        --seeds 2101 --fault state_unchanged --seconds 10

runs with a fault of ``bench/faults.py`` planted under the timed path,
which has to read ``correct`` false.  The benchmark's own runs never run
the control or a fault.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def seeds(text: str):
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, type=seeds)
    ap.add_argument("--control-seeds", default="", type=seeds)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench.faults import FAULTS
    from bench.harness import Run, judge
    hook = FAULTS[args.fault] if args.fault else None
    worst, least = 0.0, float("inf")
    for seed in args.seeds:
        ctl = seed in args.control_seeds
        run = Run(ROOT, args.workload, seed, args.seconds, False,
                  t_start=time.perf_counter(), engine_hook=hook)
        res = run.execute(control=ctl)
        gap = res["checks"]["logit_gap"]["value"]
        worst = max(worst, gap if gap is not None else float("inf"))
        line = {"seed": seed, "fault": args.fault, "logit_gap": gap,
                "correct": res["correct"],
                "checked_tokens": run.checked_tokens}
        if ctl:
            cg = run.control["logit_gap"]["value"]
            least = min(least, cg)
            line.update(control_gap=cg, control_correct=judge(run.control))
        line["metrics"] = res["metrics"]
        print(json.dumps(line), flush=True)
    print(json.dumps({"workload": args.workload, "program_max": worst,
                      "control_min": least if args.control_seeds else None}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
