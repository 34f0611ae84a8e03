"""`moe_group_open_share.decode`: 100 x the rows of the window's ticks
whose kept groups of experts hold an expert held here (`group_open_rows`
of the tick log: the program's own count, over the ticks' prefill rows and
their bursts' lanes and steps, summed over the expert layers) over those
rows in every expert layer: `prefill_tokens` + `lanes` x the engine's
`max_burst` rows, each once an expert layer (the family's
`routed_choices_per_row` over its top-k).  None where the program's tick
log has no such field (a model without groups, a parent commit), the
family no such function, or the window's ticks no rows."""
from bench.harness.engine_records import window_ticks
from bench.harness.spec import family


def read(ctx):
    cfg = ctx["cell"].config
    per_row = getattr(family(cfg), "routed_choices_per_row", None)
    ticks = window_ticks(ctx)
    if per_row is None or not ticks or "group_open_rows" not in ticks[0]:
        return None
    burst = cfg["engine"]["max_burst"]
    rows = sum(t["prefill_tokens"] + t["lanes"] * burst for t in ticks)
    layers = per_row(cfg) // cfg["num_experts_per_tok"]
    if not rows or not layers:
        return None
    return 100.0 * sum(t["group_open_rows"] for t in ticks) / (rows * layers)
