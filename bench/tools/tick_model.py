"""A tick model of `PagedLLMEngine._loop`, on the host: why `--seed` moves
no length, order or arrival in an open-loop cell (PERF.md, "Operation
accounting"; ISSUE 23 section 3 wanted the seed to permute the lengths
and draw the due times).

    python3 bench/tools/tick_model.py [--seconds 51] [--mix chat-steady]

Per tick the engine admits waiting requests into free slots, runs one
burst of `max_burst` decode steps over the decoding lanes, then prefills
at most one chunk of prompt tokens, first come first served.  The step
times per width tier and the chunk time are the ones measured on the
chip at Mistral-7B widths, depth 8 (my chip runs, PR 23: the sweep's
`tpot`, 33 / 45 / 60 ms a step at widths 4 / 8 / 16; 27 ms a 128-token
chunk).  Everything printed is a simulation, never a device number: it
answers one question, how far the medians and tails of the 20-36 requests
of a window move with the seed ALONE, the system being the same, under

    seed     the seed permutes the lengths and draws the due times
    order    due times fixed, the seed permutes the lengths
    blocks-k due times fixed, the seed permutes lengths inside blocks of k

as the quartile distance over the median of 6 seeds (the contract's
spread), median of 8 such sets.  A bound is at most 10% and five times
the spread, so a spread over 2% carries none.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.harness import schedule, spec  # noqa: E402
from bench.harness.stats import pct  # noqa: E402

STEP_S = {4: 0.0333, 8: 0.045, 16: 0.060}      # by width tier
CHUNK_S, CHUNK, BURST, SLOTS = 0.0268, 128, 8, 16
LAUNCH_S = 0.002                                # host, a launch


def simulate(requests):
    """`requests`: (due_s, prompt_len, max_tokens).  Returns each one's
    (ttft_s, tpot_s)."""
    pending, t, nxt = sorted(requests), 0.0, 0
    queue, active, prefilling, done = [], [], [], []
    while nxt < len(pending) or queue or active:
        while nxt < len(pending) and pending[nxt][0] <= t:
            queue.append(pending[nxt])
            nxt += 1
        while queue and len(active) < SLOTS:
            due, p, o = queue.pop(0)
            r = {"due": due, "p": p, "o": o, "pos": 0, "out": 0}
            active.append(r)
            prefilling.append(r)
        worked = False
        decoding = [r for r in active if r not in prefilling]
        if decoding:
            tier = next(w for w in sorted(STEP_S) if len(decoding) <= w)
            t += BURST * STEP_S[tier]
            worked = True
            for r in decoding:
                r["out"], r["last"] = min(r["o"], r["out"] + BURST), t
                if r["out"] >= r["o"]:
                    active.remove(r)
                    done.append(r)
        budget = CHUNK
        while prefilling and budget > 0:
            r = prefilling[0]
            n = min(budget, r["p"] - r["pos"])
            tier = next(c for c in (32, 64, 128) if n <= c)
            t += CHUNK_S * tier / CHUNK + LAUNCH_S
            r["pos"] += n
            budget -= n
            worked = True
            if r["pos"] >= r["p"]:
                prefilling.pop(0)
                r["out"], r["first"], r["last"] = 1, t, t
        if not worked:
            t = max(t + 0.02, pending[nxt][0]) \
                if nxt < len(pending) and not queue else t + 0.02
    return [(r["first"] - r["due"], (r["last"] - r["first"]) / (r["o"] - 1))
            for r in done]


def design(kind: str, block: int, mix: dict, rate: float, seconds: float,
           seed: int):
    n = round(rate * seconds)
    pairs = schedule._pairs(mix, n)
    fixed, rng = np.random.default_rng(12345), np.random.default_rng(seed)
    if kind == "seed":
        order, due = rng.permutation(n), np.sort(rng.uniform(0, seconds, n))
    else:
        due, order = np.sort(fixed.uniform(0, seconds, n)), fixed.permutation(n)
        for lo in range(0, n, block or n):
            part = slice(lo, min(lo + (block or n), n))
            order[part] = rng.permutation(order[part])
    return [(float(due[i]),) + pairs[int(order[i])] for i in range(n)]


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--mix", default="chat-steady")
    args = ap.parse_args()
    with open(os.path.join(spec.BENCH_DIR, "traffic",
                           args.mix + ".json")) as f:
        mix = json.load(f)
    designs = [("seed", 0), ("order", 0)] + [("blocks", k) for k in (2, 4, 8)]
    for rate in (0.4, 0.5, 0.6, 0.7):
        for kind, block in designs:
            sets = []
            for trial in range(8):
                runs = []
                for s in range(6):
                    got = simulate(design(kind, block, mix, rate,
                                          args.seconds, 1000 * trial + s))
                    ttft, tpot = [g[0] for g in got], [g[1] for g in got]
                    runs.append((pct(ttft, 0.5), pct(ttft, 0.9),
                                 pct(tpot, 0.5)))
                sets.append([spread([r[i] for r in runs]) for i in range(3)])
            p50, p90, tpot = np.median(np.array(sets), axis=0)
            print(f"rate {rate} n={round(rate * args.seconds)} "
                  f"{kind + (f'-{block}' if block else ''):9s} spread of "
                  f"ttft_p50 {100 * p50:5.1f}%  ttft_p90 {100 * p90:5.1f}%  "
                  f"tpot_p50 {100 * tpot:4.1f}%")


if __name__ == "__main__":
    main()
