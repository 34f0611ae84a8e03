"""`dsa_selected_share`: 100 x the positions the window's ticks' full
layers attended (`kv_selected_tokens` of the tick log: at most
`index_topk` a row) over the positions their indexer scored
(`index_scored_tokens`: every position a row sees), the program's own
count over the ticks' prefill rows and their bursts' lanes and steps.
None where the program's tick log has no such fields (a program without
the selection) or the window's ticks scored nothing."""
from bench.harness.engine_records import window_ticks


def read(ctx):
    ticks = window_ticks(ctx)
    if not ticks or "index_scored_tokens" not in ticks[0]:
        return None
    scored = sum(t["index_scored_tokens"] for t in ticks)
    if not scored:
        return None
    return 100.0 * sum(t["kv_selected_tokens"] for t in ticks) / scored
