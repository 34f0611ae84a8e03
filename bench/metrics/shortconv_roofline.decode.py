"""`shortconv_roofline.decode`: `ssm_state_roofline`'s shape
(bench/metrics/ssm_state_roofline.py) with the family's
`conv_bytes_per_step` and `mixer_operand` in place of the state's.  Bytes
the conv mixers of one decode step must move, at peak bandwidth, over the
device time of the ops of `program` whose HLO text shows an operand of a
conv layer's mixer (`shortconv_step_share.decode`'s `mixer_seconds`: loops
left out).  A family that gives neither function, a program without such
ops, a run without a trace and a trace without the counter give None."""
from bench.harness.peaks import peaks
from bench.harness.spec import BENCH_DIR, family, load_file, metric_file
from bench.harness.stats import mean


def read(ctx, program: str, counter: str):
    cfg = ctx["cell"].config
    per_step = getattr(family(cfg), "conv_bytes_per_step", None)
    found = load_file(
        metric_file(BENCH_DIR, "shortconv_step_share.decode", ".py"),
        "bench_metric_").mixer_seconds(ctx, program)
    if per_step is None or found is None:
        return None
    seconds, p = found
    c = ctx["trace"]["counters"].get(counter)
    if not p.get("count") or not c or not c.get("each"):
        return None
    steps = p["count"] * cfg["engine"]["max_burst"]
    least = mean([per_step(cfg, ev["lanes"]) for ev in c["each"]]) \
        / peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)
