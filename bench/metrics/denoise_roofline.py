"""`denoise_roofline`: 100 x the least time of one forward pass of
`program` (the family's `denoise_pass_bytes` at the chip's peak HBM
bandwidth, mean over the launches `counter` counted: their lanes, and
their live K / V positions taken at the middle of the call, the lanes'
lengths at the launch plus the blocks the call has filled by then; the
experts a pass reads as the program counted them, `experts_read` of the
window's ticks, since a block's open rows route alike and an expectation
under independent routing counts half as many again) over a pass's device
time (`denoise_pass_dev_ms.pass_seconds`).  A family without the
function, a trace without the program or the counter, a tick log without
the count give None."""
from bench.harness.engine_records import tick_stat
from bench.harness.peaks import peaks
from bench.harness.spec import BENCH_DIR, family, load_file, metric_file
from bench.harness.stats import mean


def experts_read(ctx):
    """Distinct experts a pass of the window's bursts read, a layer: the
    program's own count, mean weighted by lanes; None without it."""
    try:
        return tick_stat(ctx, field="experts_read", weight="lanes") or None
    except KeyError:
        return None


def launch_roofline(ctx, program: str, counter: str, cost, seconds):
    """100 x mean over the counter's events of `cost(cfg, event, experts
    read)` bytes at peak bandwidth, over `seconds` (a pass's); None where
    any of them is missing."""
    c = (ctx.get("trace") or {}).get("counters", {}).get(counter)
    read = experts_read(ctx)
    if seconds is None or read is None or not c or not c.get("each"):
        return None
    cfg = ctx["cell"].config
    least = mean([cost(cfg, ev, read) for ev in c["each"]]) \
        / peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / seconds


def read(ctx, program: str, counter: str):
    cfg = ctx["cell"].config
    bytes_of = getattr(family(cfg), "denoise_pass_bytes", None)
    if bytes_of is None:
        return None
    b = cfg["assumed"]["diffusion_block"]
    blocks = cfg["engine"]["max_burst"] // b

    def cost(cfg, ev, read):
        # block n of the call reads the lanes' lengths + (n + 1) x B
        live = ev["kv_tokens"] + ev["lanes"] * b * (blocks + 1) / 2.0
        return bytes_of(cfg, live, ev["lanes"], read)

    per_pass = load_file(
        metric_file(BENCH_DIR, "denoise_pass_dev_ms", ".py"), "bench_metric_")
    return launch_roofline(ctx, program, counter, cost,
                           per_pass.pass_seconds(ctx, program))
