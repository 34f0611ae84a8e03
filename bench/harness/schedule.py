"""The schedule: a pure function of (mix file, cell rate, seconds, seed),
of which the seed draws the token ids and nothing else.

Open loop   N = round(rate x seconds) requests.  Their (prompt_len,
            max_tokens) pairs are the N-point quantile grid of the mix's
            distributions; their due times are the sorted draws of N
            uniforms on the window (a Poisson process given its count);
            pairs, order and due times come from one constant, so they
            are the same in every run of a cell.
Closed loop `clients` callers that each wait for their reply.  Requests
            come in blocks of `block` pairs, each block the same
            block-point quantile grid in an order drawn from the same
            constant: any run of consecutive requests holds nearly the
            same lengths.
Train       rows of `seq_len` + 1 seeded token ids.

Why the seed moves no length, order or arrival (PR 23, PERF.md
"Operation accounting"): this engine serves some tens of requests in a
window, and a tick model of it with the measured step times
(bench/tools/tick_model.py) shows the medians and tails of 20-36
requests moving by 28-48% with the order of the lengths alone, and by
4-40% when the seed permutes only within blocks of 2 to 8 neighbours.  A yardstick whose own runs differ by that much can hold
no later PR to anything.  The seed still changes every token id and
every weight, so no run can be answered from another's.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

_SCHEDULE_SEED = 20260927    # lengths, order and arrivals; not the run's seed


@dataclasses.dataclass
class Request:
    index: int
    due_s: float              # open loop: offset in the window; closed: 0
    prompt_len: int
    max_tokens: int
    tokens: List[int]


def quantile_grid(dist: Dict[str, Any], n: int) -> List[int]:
    """The n-point quantile grid of a length distribution: its inverse
    CDF at (i + 0.5) / n, clipped to [min, max]."""
    kind = dist["kind"]
    if kind == "const":
        return [int(dist["value"])] * n
    us = [(i + 0.5) / n for i in range(n)]
    if kind == "uniform":
        xs = [dist["min"] + u * (dist["max"] - dist["min"]) for u in us]
    elif kind == "lognormal":
        z = NormalDist()
        xs = [dist["median"] * np.exp(dist["sigma"] * z.inv_cdf(u))
              for u in us]
    else:
        raise ValueError(f"unknown distribution kind {kind!r}")
    return [int(min(max(round(x), dist["min"]), dist["max"])) for x in xs]


def _pairs(traffic: Dict[str, Any], n: int) -> List[tuple]:
    prompts = quantile_grid(traffic["prompt_len"], n)
    outs = quantile_grid(traffic["max_tokens"], n)
    order = np.random.default_rng(_SCHEDULE_SEED).permutation(n)
    return [(prompts[i], outs[int(order[i])]) for i in range(n)]


def _tokens(rng, n: int, vocab: int) -> List[int]:
    return rng.integers(1, vocab, n, dtype=np.int64).tolist()


def open_schedule(traffic: Dict[str, Any], rate: float, seconds: float,
                  seed: int, vocab: int) -> List[Request]:
    n = int(round(rate * seconds))
    if n < 1:
        raise ValueError(f"rate {rate} x {seconds}s gives no request")
    fixed = np.random.default_rng(_SCHEDULE_SEED + 1)
    pairs = _pairs(traffic, n)
    order = fixed.permutation(n)
    due = np.sort(fixed.uniform(0.0, seconds, n))
    rng = np.random.default_rng(seed)
    return [Request(i, float(due[i]), *pairs[int(order[i])],
                    tokens=_tokens(rng, pairs[int(order[i])][0], vocab))
            for i in range(n)]


def closed_schedule(traffic: Dict[str, Any], seed: int, vocab: int):
    """Requests in order of issue, without end: the driver takes as many
    as its clients start inside the window.  Every block holds the same
    multiset of pairs, so checking one block checks them all."""
    block = int(traffic["block"])
    fixed = np.random.default_rng(_SCHEDULE_SEED + 2)
    rng = np.random.default_rng(seed)
    pairs = _pairs(traffic, block)
    index = 0
    while True:
        for j in fixed.permutation(block):
            p, m = pairs[int(j)]
            yield Request(index, 0.0, p, m, _tokens(rng, p, vocab))
            index += 1


def train_rows(traffic: Dict[str, Any], seed: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    rows = int(traffic["rows"])
    return rng.integers(0, vocab, (rows, int(traffic["seq_len"]) + 1),
                        dtype=np.int32)
