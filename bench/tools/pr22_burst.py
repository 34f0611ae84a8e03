"""What sank PR 22: its long-prompt mix again, with every cause printed.

32 seeded prompts of 2048-4064 tokens, 64 new tokens each, all sent at
t = 0 to the `mistral-7b-serve-1chip` replica -- a hand-made schedule
that skips the engine-limit check on purpose (the check would refuse
it).  Results go to PERF.md, "What failed in PR 22".

    python bench/tools/pr22_burst.py [--seed N] [--trace 0|1]
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse     # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import numpy as np

    from bench.harness import schedule, serve_cell, spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="mistral7b-longprompt")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--root", default=spec.ROOT)
    ap.add_argument("--lengths", type=int, nargs=2, default=(2048, 4064))
    ap.add_argument("--new-tokens", type=int, default=64)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload, args.root)
    cell.traffic = {"loop": "open", "temperature": 0.0}
    rng = np.random.default_rng(args.seed)
    lengths = rng.integers(args.lengths[0], args.lengths[1] + 1, 32)
    limit = spec.request_limit(cell.config["engine"])
    requests = [schedule.Request(
        i, 0.0, int(n), args.new_tokens,
        rng.integers(1, cell.config["vocab_size"], int(n)).tolist())
        for i, n in enumerate(lengths)]
    over = [r.index for r in requests
            if r.prompt_len + r.max_tokens > limit]
    print({"pr22_burst": "schedule", "lengths": sorted(map(int, lengths)),
           "engine_limit": limit, "requests_over_limit": over}, flush=True)
    return serve_cell.run(
        cell, seed=args.seed, seconds=1.0, traced=bool(args.trace),
        rehearse=args.rehearse, t_start=T_START, requests=requests,
        keep_trace=os.path.join(spec.ROOT, "chiprun_out", "pr22_burst")
        if args.trace else None)


if __name__ == "__main__":
    sys.exit(main())
