#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is ``BENCHMARK.json``'s workload ``<name>``. With ``--trace 0`` the
last line of standard output is one JSON object with the cell's end-to-end
metrics; with ``--trace 1``, its per-layer metrics, read from a profiler
trace of the window. Either way the run checks a sample of what it served
against the plain reference, and prints each number compared beside its
limit, last on standard error and under ``checks`` in the result. Without
the TPU chips the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def setup_paths() -> None:
    """Import the harness from ``bench/`` and the program from ``src/``, and
    keep JAX's compilation cache at a fixed path inside the checkout (set
    before JAX is imported, so JAX reads it)."""
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    setup_paths()
    import harness
    spec = harness.load_spec(args.workload)
    line, _, _ = harness.run_cell(spec, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    print(json.dumps(line), flush=True)
    harness.log_checks(line["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
