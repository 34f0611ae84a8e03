"""One run of one cell of the benchmark:

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's configuration, traffic mix, rate and per-layer metrics
by the names in BENCHMARK.json (bench/harness/spec.py), starts the
program's runtime, and drives the cell through the entry points a user
calls.  The last line of stdout is the result object and nothing else;
causes, schedules and phase reports go on earlier lines.  Without the
cell's chips: a non-zero exit and no result.  This process never
initialises a JAX backend.
"""
from __future__ import annotations

import time

T_START = time.time()           # set-up is everything from here to the window

import argparse                 # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from bench.harness import runtime, serve_cell, spec, train_cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: declares the chips instead of "
                         "detecting them, runs the same control flow, and "
                         "always ends non-zero at the device check")
    ap.add_argument("--root", default=spec.ROOT,
                    help="where BENCHMARK.json and its paths are (the "
                         "tests point this at a tiny tree)")
    args = ap.parse_args()
    drivers = {"serve": serve_cell.run, "train": train_cell.run}
    try:
        cell = spec.load_cell(args.workload, args.root)
        code = drivers[cell.config["kind"]](
            cell, seed=args.seed, seconds=args.seconds,
            traced=bool(args.trace), rehearse=args.rehearse,
            t_start=T_START)
    except spec.SpecError as e:
        print(f"bench: fault in a data file: {e}", file=sys.stderr)
        return 2
    except runtime.NoChips as e:
        print(f"bench: no result: {e}", file=sys.stderr)
        return 3
    return code


if __name__ == "__main__":
    sys.exit(main())
