"""`state_reset_ms`: the mean of the tick log's `reset_s` over the
window's ticks that reset any slot's state (`tick_stat` cannot filter).
None where the program's tick log has no such field, or no tick of the
window reset anything."""
from bench.harness.engine_records import window_ticks
from bench.harness.stats import mean


def read(ctx, scale: float = 1000.0):
    ticks = window_ticks(ctx)
    if not ticks or "reset_s" not in ticks[0]:
        return None
    vals = [t["reset_s"] for t in ticks if t["reset_s"] > 0]
    return scale * mean(vals) if vals else None
