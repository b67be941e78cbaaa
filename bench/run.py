#!/usr/bin/env python3
"""Serving benchmark on the chip: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload qwen2-1.5b.longgen --seed 7 \\
        --seconds 40 --trace 0

Runs from the root of a checkout, on a machine with the TPU chips the cell
asks for; it exits 1 and prints no result where JAX finds none.  The last
line of standard output is the result as one JSON object; the last lines of
standard error are the numbers the correctness check compared, each with
its limit.  JAX's persistent compilation cache lives in ``.jax_cache`` at
the root of the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro.serving.engine  # noqa: F401  the system under test
    except ImportError as e:
        print(f"bench: the program (src/repro) is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import harness
    return harness.main(ROOT, args, T_START)


if __name__ == "__main__":
    sys.exit(main())
