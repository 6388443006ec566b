"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, where jax finds no TPU or fewer
chips than the cell asks for, or where the program is not beside the
benchmark.  The persistent compilation cache is the checkout's
``.jax_cache/``, the directory ``runtime/compile_cache.py`` gives, and
it keeps every program, however short its compile, so that a second run
of a cell compiles nothing.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import harness
    cell = harness.load_cell(args.workload, ROOT)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
