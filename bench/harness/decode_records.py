"""Readers of what the engine's phase clock leaves in its records (PR 38):
the decode half of `request_phases` and `starved_s` of `tick_log`.

At a request's end the engine completes its `request_phases` record with
`decode_s` (first token -> the request's end, on the engine's clock),
`n_out`, and what its loop thread did meanwhile, from two readings of the
phase clock: `burst_read_s` (waiting for a burst's tokens: the device's
turn), `first_read_s` (waiting for other prompts' first tokens), `host_s`
(every other leaf: launches, emit, admit, book, wait); the three sum to
`decode_s`.  `lanes_seen` is the mean lanes of the bursts it was read
from.  A tick's `starved_s` is the time in which the host knew the
device's queue to be empty with work in hand.

The window's requests are `engine_records.window_requests` (by id); of
them a request enters here if it succeeded at the client with at least
two tokens.  A program without the fields (a parent commit), or such a
request whose record never finished, gives None: a metric is left out,
never computed from a part of its window.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from bench.harness.engine_records import window_requests, window_ticks
from bench.harness.stats import median


def window_decodes(ctx) -> Optional[List[Tuple[dict, object]]]:
    """(the engine's record, the client's outcome) of every request of
    the window that streamed two tokens or more to its end."""
    reqs = window_requests(ctx)
    if reqs is None:
        return None
    # window_requests keeps the order of the outcomes it took
    got = [o for o in ctx["run"]["outcomes"]
           if not o.cause and o.first is not None]
    pairs = [(r, o) for r, o in zip(reqs, got) if o.tokens >= 2]
    if any(r.get("decode_s") is None or r["n_out"] < 2 for r, _ in pairs):
        return None
    return pairs or None


def _engine_tpot(rec: dict) -> float:
    return rec["decode_s"] / (rec["n_out"] - 1)


def engine_tpot(ctx, scale: float = 1000.0):
    """Median over the window's requests of the engine's own time per
    output token: `decode_s / (n_out - 1)`."""
    pairs = window_decodes(ctx)
    return scale * median([_engine_tpot(r) for r, _ in pairs]) \
        if pairs else None


def front_tpot(ctx, scale: float = 1000.0):
    """Median over the same requests of the client's
    `(t_last - t_first) / (n_out - 1)` less the engine's: what replica,
    handle, proxy and the stream add to a token."""
    pairs = window_decodes(ctx)
    if not pairs:
        return None
    return scale * median([(o.last - o.first) / (o.tokens - 1)
                           - _engine_tpot(r) for r, o in pairs])


def decode_share(ctx, field: str):
    """100 x the requests' `field` summed over their `decode_s` summed:
    the share of the window's decode seconds that the engine's thread
    spent so."""
    pairs = window_decodes(ctx)
    total = sum(r["decode_s"] for r, _ in pairs) if pairs else 0.0
    return 100.0 * sum(r[field] for r, _ in pairs) / total \
        if total else None


def lanes_seen(ctx):
    """Median over the window's requests of `lanes_seen`."""
    pairs = window_decodes(ctx)
    return median([r["lanes_seen"] for r, _ in pairs]) if pairs else None


def starved_share(ctx):
    """100 x the window's ticks' `starved_s` over their `tick_s`."""
    ticks = window_ticks(ctx)
    if not ticks or "starved_s" not in ticks[0]:
        return None
    total = sum(t["tick_s"] for t in ticks)
    return 100.0 * sum(t["starved_s"] for t in ticks) / total \
        if total else None
