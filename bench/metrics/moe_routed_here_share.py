"""`moe_routed_here_share`: 100 x the top-k choices of the window's
ticks that fell on experts held here (`routed_here` of the tick log, the
program's own count over the ticks' prefill rows and their bursts' lanes
and steps) over all the choices those rows made: `prefill_tokens` +
`lanes` x the engine's `max_burst` rows, each the family's
`routed_choices_per_row` (top_k in every expert layer).  None where the
program's tick log has no such field, the family no such function, or
the window's ticks no rows."""
from bench.harness.engine_records import window_ticks
from bench.harness.spec import family


def read(ctx):
    cfg = ctx["cell"].config
    per_row = getattr(family(cfg), "routed_choices_per_row", None)
    ticks = window_ticks(ctx)
    if per_row is None or not ticks or "routed_here" not in ticks[0]:
        return None
    burst = cfg["engine"]["max_burst"]
    rows = sum(t["prefill_tokens"] + t["lanes"] * burst for t in ticks)
    if not rows:
        return None
    return 100.0 * sum(t["routed_here"] for t in ticks) \
        / (rows * per_row(cfg))
