"""`loop_attn_roofline.decode`: bytes one attention read of a decode step
must move (the family's `attn_kv_bytes_per_launch`: K and V of the lanes'
live positions in one plane of the pool), at peak bandwidth, over the
device time of one launch of the ops of `program` named for `kernel`
(`loop_attn_share.decode`'s `kernel_seconds`: their seconds over their own
count, so a burst that the traced window cuts in two counts for what it
ran).  The positions are the trace's own counter's (`kv_tokens` at a
burst's launch, half a burst's growth added: `decode_roofline`'s count).
A family that gives no such function, a program without such ops, a run
without a trace and a trace without the counter give None."""
from bench.harness.peaks import peaks
from bench.harness.spec import BENCH_DIR, family, load_file, metric_file
from bench.harness.stats import mean


def read(ctx, program: str, kernel: str, counter: str):
    cfg = ctx["cell"].config
    per_launch = getattr(family(cfg), "attn_kv_bytes_per_launch", None)
    found = load_file(
        metric_file(BENCH_DIR, "loop_attn_share.decode", ".py"),
        "bench_metric_").kernel_seconds(ctx, program, kernel)
    if per_launch is None or found is None:
        return None
    seconds, launches, _ = found
    c = ctx["trace"]["counters"].get(counter)
    if not launches or not c or not c.get("each"):
        return None
    burst = cfg["engine"]["max_burst"]
    least = mean([per_launch(cfg, ev["kv_tokens"]
                             + ev["lanes"] * (burst - 1) / 2)
                  for ev in c["each"]]) \
        / peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / launches)
