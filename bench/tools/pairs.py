"""Two trees on one machine, as the driver compares them: a cell's
untraced runs from a parent checkout and from this one in turn (parent,
change, change, parent, ...), both runs of a pair on one seed, and at the
end each end-to-end metric's medians, spreads (quartile distance over
median) and the median of the pairs' relative differences.  The parent is
a checkout unpacked into a git-ignored directory of this one, since a
chip call copies this tree only:

    mkdir -p chiprun_in/parent && git archive HEAD | tar -x -C chiprun_in/parent
    chiprun --timeout 3000 -- python bench/tools/pairs.py \
        --parent chiprun_in/parent --workload mixtral-chat --pairs 6

Every run's output stays under `chiprun_out/pairs/`; the summary is the
last line and `chiprun_out/pairs/<workload>.json`.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(tree: str, workload: str, seed: int, seconds: float, log: str):
    """One `bench/run.py` from `tree`; its result line's metrics, or None."""
    cmd = [sys.executable, "bench/run.py", "--workload", workload, "--seed",
           str(seed), "--seconds", str(seconds), "--trace", "0"]
    with open(log + ".out", "w") as out, open(log + ".err", "w") as err:
        rc = subprocess.call(cmd, cwd=tree, stdout=out, stderr=err)
    with open(log + ".out") as f:
        last = ([ln for ln in f.read().splitlines() if ln.strip()] or [""])[-1]
    if rc or not last.startswith('{"correct"'):
        return None
    line = json.loads(last)
    values = {k: v["value"] for k, v in line["metrics"].items()}
    return dict(values, correct=line["correct"], failed=line["failed"])


def _spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--pairs", type=int, default=6)
    ap.add_argument("--seed", type=int, default=3_700_000_001)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out", "pairs")
    os.makedirs(out_dir, exist_ok=True)
    trees = {"parent": os.path.abspath(args.parent), "change": ROOT}
    runs = []
    for i in range(args.pairs):
        pair = {"seed": args.seed + i}
        for side in (("parent", "change"), ("change", "parent"))[i % 2]:
            pair[side] = _run(trees[side], args.workload, pair["seed"],
                              args.seconds, os.path.join(
                                  out_dir, f"{args.workload}-{i}-{side}"))
            print(json.dumps({"pair": i, "side": side, **(pair[side] or {})}),
                  flush=True)
        runs.append(pair)
    whole = [p for p in runs if p["parent"] and p["change"]]
    summary = {"workload": args.workload, "pairs": len(whole),
               "lost": len(runs) - len(whole), "runs": runs, "metrics": {}}
    for name in (whole[0]["parent"] if whole else {}):
        if name in ("correct", "failed"):
            continue
        a = [p["parent"][name] for p in whole]
        b = [p["change"][name] for p in whole]
        summary["metrics"][name] = {
            "parent_median": statistics.median(a),
            "change_median": statistics.median(b),
            "parent_spread": _spread(a) if len(a) > 1 else None,
            "change_spread": _spread(b) if len(b) > 1 else None,
            "paired_diff_median": statistics.median(
                (y - x) / x for x, y in zip(a, b)),
            "change_higher_in": sum(y > x for x, y in zip(a, b))}
    with open(os.path.join(out_dir, args.workload + ".json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({k: v for k, v in summary.items() if k != "runs"}))
    ok = all(r and r["correct"] and not r["failed"]
             for p in runs for r in (p["parent"], p["change"]))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
