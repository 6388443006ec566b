"""The readings a cell's limit is set from, in one process on the chip.

    python bench/readings.py --workload <cell> --seeds <n> [<n> ...]

For every seed: the traces of that seed, one campaign through the
program's batched entry at the cell's own sizes, then the comparison
with the plain reference (the program's reading) and the same
comparison with the control, the reference in float32, in the
program's place (the control's reading), with the number of distinct
lane results and the least and most L3 evictions of a lane.  One JSON
line per seed.
The benchmark's own runs do not run this.
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import harness
    cell = harness.load_cell(args.workload, ROOT)
    device = harness.device_info(cell.chips)
    for seed in args.seeds:
        traffic = cell.traffic(seed)
        program = harness.Program(cell, traffic)
        t = time.perf_counter()
        camp = program.campaign(harness.no_annotation)
        took = time.perf_counter() - t
        got = harness.check(cell, traffic, [camp])
        ctl = harness.control_check(cell, traffic, [camp])
        l3_evictions = [int(oi[20]) for oi, _ in camp.outs]
        print(json.dumps({"cell": cell.name, "seed": seed,
                          "device": device["kind"],
                          "campaign_s": took,
                          "l3_evictions": [min(l3_evictions),
                                           max(l3_evictions)],
                          "program": got, "control": ctl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
