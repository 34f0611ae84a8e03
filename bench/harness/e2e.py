"""End-to-end metrics: what a user of the system sees, taken by the
benchmark itself on the host's clock.  A name is parsed, not looked up:
`ttft_p<q>_ms`, `ttft_mean_ms`, `tpot_p<q>_ms`, `train_tok_s`,
`setup_s`."""
from __future__ import annotations

import re
from typing import Any, Dict, Optional

from bench.harness.stats import censored, mean, pct

_LATENCY = re.compile(r"^(ttft|tpot)_(?:p(\d+)|(mean))_ms$")


def _ttft(o) -> Optional[float]:
    """Due (open loop) or sent (closed loop, where they coincide) to the
    first streamed token at the client."""
    return None if o.cause or o.first is None else o.first - o.due


def _tpot(o) -> Optional[float]:
    """(t_last - t_first) / (n_out - 1): tokens arrive in bursts of
    `max_burst`, so single gaps are 0 or a burst."""
    if o.cause or o.tokens < 2:
        return None
    return (o.last - o.first) / (o.tokens - 1)


def value(name: str, run: Dict[str, Any]) -> Optional[float]:
    """`run`: what a cell's driver measured (serve: outcomes, gave_up_s;
    train: tokens, window_s; both: setup_s)."""
    if name == "setup_s":
        return run["setup_s"]
    if name == "train_tok_s":
        return run["tokens"] / run["window_s"] if "tokens" in run else None
    m = _LATENCY.match(name)
    if m and "outcomes" in run:
        take = _ttft if m.group(1) == "ttft" else _tpot
        # A failed request misses every latency: it enters at the time
        # the client stopped waiting, above any latency that finished.
        vals = censored([take(o) for o in run["outcomes"]],
                        never=run["gave_up_s"])
        if m.group(3):
            return 1000.0 * mean(vals)
        return 1000.0 * pct(vals, int(m.group(2)) / 100.0)
    return None
