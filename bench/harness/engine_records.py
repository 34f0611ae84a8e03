"""Readers of the engine's own records: `request_phases` and `tick_log`
of `PagedLLMEngine.engine_stats()`, which `bench_report` returns as
`ctx["replica"]["stats"]`.

`request_phases`: one dict per request that got a first token, `id` the
client's `X-Request-Id` (`run["outcomes"]` carries the same id), the
edges of its phases on one clock: `queue_wait_s + prefill_wait_s +
prefill_span_s = ttft_s`.  `tick_log`: one tuple per engine tick that
progressed, its fields named by `tick_fields`.  Both are logs since the
process began, so a reader takes the window's requests by id (the
warm-up's are `bench-warm-*`) and the window's ticks by their `start`,
from the `submitted` of the window's first request to the first token of
its last.

A program without the records (a parent commit) gives None, and so does
a window request that the record has lost: a metric is left out, never
computed from a part of its window.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from bench.harness.stats import mean, median

_STATS = {"median": median, "mean": mean}


def window_requests(ctx) -> Optional[List[dict]]:
    """The records of the window's requests that got a first token at
    the client, in the order they were sent."""
    phases = ctx["replica"].get("stats", {}).get("request_phases")
    if not phases:
        return None
    by_id = {r["id"]: r for r in phases}
    out = []
    for o in ctx["run"]["outcomes"]:
        if o.cause or o.first is None:
            continue
        if o.request_id not in by_id:
            return None
        out.append(by_id[o.request_id])
    return out or None


def _ticks(ctx) -> Optional[List[Dict[str, float]]]:
    """Every tick of the log, each as a dict by `tick_fields`."""
    stats = ctx["replica"].get("stats", {})
    log, fields = stats.get("tick_log"), stats.get("tick_fields")
    return [dict(zip(fields, t)) for t in log] if log and fields else None


def window_ticks(ctx) -> Optional[List[Dict[str, float]]]:
    """The ticks that started while a request of the window was on its
    way to its first token."""
    ticks, reqs = _ticks(ctx), window_requests(ctx)
    if not ticks or not reqs:
        return None
    lo = min(r["submitted"] for r in reqs)
    hi = max(r["submitted"] + r["ttft_s"] for r in reqs)
    return [t for t in ticks if lo <= t["start"] <= hi] or None


def request_stat(ctx, field: str, stat: str = "median",
                 scale: float = 1000.0):
    reqs = window_requests(ctx)
    return scale * _STATS[stat]([r[field] for r in reqs]) if reqs else None


def prefill_interleave(ctx):
    """100 x the part of the window's prefill spans (launch of a
    prompt's first chunk -> its first token) that lies inside ticks
    which also ran a decode burst (`lanes` > 0), over the spans.  The
    rest lies in ticks that ran chunks alone.  From the ticks' own
    starts and lengths, so the depth of the runtime's launch queue does
    not enter; a tick that decoded counts whole, its own chunk's time
    on the device (a tenth of such a tick) included."""
    reqs = window_requests(ctx)
    ticks = _ticks(ctx)
    if not reqs or not ticks:
        return None
    inside = spans = 0.0
    for r in reqs:
        lo = r["submitted"] + r["queue_wait_s"] + r["prefill_wait_s"]
        hi = lo + r["prefill_span_s"]
        spans += hi - lo
        inside += sum(
            max(0.0, min(hi, t["start"] + t["tick_s"]) - max(lo, t["start"]))
            for t in ticks if t["lanes"])
    return 100.0 * inside / spans if spans else None


def _widest(ticks):
    """The ticks at the widest decode tier the window reached."""
    top = max(t["width"] for t in ticks)
    return [t for t in ticks if t["width"] == top] if top else []


def tick_stat(ctx, field: str, less: Sequence[str] = (),
              stat: str = "median", scale: float = 1.0,
              weight: Optional[str] = None, widest: bool = False):
    """A statistic over the window's ticks (`widest`: over those at the
    widest decode tier the window reached) of `field` less the fields
    of `less`.  With `weight` it is the mean weighted by that field
    (`lanes`: the tick as its decoding requests meet it, each of them
    waiting its whole length for a burst's tokens)."""
    ticks = window_ticks(ctx)
    if ticks and widest:
        ticks = _widest(ticks)
    if not ticks:
        return None
    vals = [t[field] - sum(t[k] for k in less) for t in ticks]
    if weight is None:
        return scale * _STATS[stat](vals)
    weights = [t[weight] for t in ticks]
    total = sum(weights)
    return scale * sum(v * w for v, w in zip(vals, weights)) / total \
        if total else None


def tick_share_widest(ctx, weight: str = "lanes"):
    """100 x the `weight` of the window's ticks that falls to those at
    the widest decode tier the window reached."""
    ticks = window_ticks(ctx)
    total = sum(t[weight] for t in ticks) if ticks else 0
    return 100.0 * sum(t[weight] for t in _widest(ticks)) / total \
        if total else None
