"""General readers for per-layer metrics.  `metrics/<name>.json` names
one (`"reader"`) and its arguments (`"args"`); a metric that none of
these covers brings `metrics/<name>.py` with its own `read(ctx, **args)`.
A reader that finds nothing to read returns None and the harness leaves
the metric out of the line.

`ctx` holds: `cell` (spec.Cell), `run` (the driver's measurements:
outcomes, setup_s, ...), `replica` (the replica's / worker's report:
engine_spans, stats, host times), `trace` (xplane.reduce_planes' result)
and `device` (platform, kind, count).

The operations and bytes a step needs are the cell's family's to say
(bench/families/<family>.py): the rooflines here hand it the
configuration and the counter's event as they have it.
"""
from __future__ import annotations

from typing import Dict, Optional

from bench.harness.peaks import peaks
from bench.harness.spec import family
from bench.harness.stats import mean, median


def _program(ctx, name: str) -> Dict[str, float]:
    try:
        return ctx["trace"]["programs"][name]
    except KeyError:
        raise LookupError(f"no jitted program {name!r} in the trace; it "
                          f"has {sorted(ctx['trace']['programs'])}") from None


def _counter(ctx, name: str) -> Optional[Dict[str, float]]:
    return ctx["trace"]["counters"].get(name)


def end_to_end_value(ctx, name: str):
    """An end-to-end statistic of the traced run itself, reported beside
    the cell's end-to-end metrics where its own spread is too wide to
    carry a bound (e.g. `ttft_p50_ms` of an open-loop cell)."""
    from bench.harness import e2e

    return e2e.value(name, ctx["run"])


def front_overhead(ctx, scale: float = 1000.0):
    """Client TTFT (sent -> first token at the client) less engine TTFT
    (submitted to the engine -> end of its last prefill chunk, from the
    `serve.engine.*` spans of the same request id), median: what proxy,
    handle, replica and the stream back add."""
    spans = ctx["replica"]["engine_spans"]
    vals = []
    for o in ctx["run"]["outcomes"]:
        s = spans.get(o.request_id)
        if o.cause or o.first is None or not s \
                or "submitted" not in s or "prefill_end" not in s:
            continue
        vals.append((o.first - o.sent) - (s["prefill_end"] - s["submitted"]))
    return scale * median(vals) if vals else None


def engine_span_median(ctx, start: str, end: str, scale: float = 1000.0):
    vals = [s[end] - s[start] for s in ctx["replica"]["engine_spans"].values()
            if start in s and end in s]
    return scale * median(vals) if vals else None


def counter_ratio(ctx, counter: str, num: str, den: str):
    c = _counter(ctx, counter)
    return c[num] / c[den] if c and c.get(den) else None


def program_time_per(ctx, program: str, per: Dict[str, str],
                     scale: float = 1.0):
    """Device seconds of a jitted program over a count of work: its
    executions (`{}`), times an engine size (`{"engine": "max_burst"}`),
    or times what one launch carried by a trace counter
    (`{"counter": c, "field": "tokens", "launches": "chunks"}`: the host
    runs ahead of the device, so launches and executions inside one
    traced window differ; work per launch does not)."""
    p = _program(ctx, program)
    units = p["count"]
    if "engine" in per:
        units *= ctx["cell"].config["engine"][per["engine"]]
    if "counter" in per:
        c = _counter(ctx, per["counter"])
        if not c or not c.get(per["launches"]):
            return None
        units *= c[per["field"]] / c[per["launches"]]
    return scale * p["seconds"] / units if units else None


def _per_launch(c: Dict, cost) -> float:
    """Mean of `cost(arguments)` over a counter's events: what one launch
    of the window carried (launches and executions inside one traced
    window differ; work per launch does not)."""
    return mean([cost(ev) for ev in c["each"]])


def decode_roofline(ctx, program: str, counter: str):
    """Bytes a decode step must move (the dense weights, the experts its
    lanes are routed to, the live KV of its lanes) at the chip's peak
    bandwidth, over the step's device time.  Bound by bandwidth: at <= 16
    tokens a step the FLOP side is a hundredth of it."""
    p, c = _program(ctx, program), _counter(ctx, counter)
    if not c or not p["count"]:
        return None
    cfg = ctx["cell"].config
    fam, burst = family(cfg), cfg["engine"]["max_burst"]
    least = _per_launch(c, lambda ev: fam.decode_step_bytes(
        cfg, ev["kv_tokens"] + ev["lanes"] * (burst - 1) / 2, ev["lanes"])
    ) / peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (p["seconds"] / (p["count"] * burst))


def moe_ffn_roofline(ctx, program: str, counter: str):
    """Expert weights a decode step needs (the experts its lanes are
    routed to: the family's `expert_bytes_per_step`), at peak bandwidth,
    over the device time of the expert FFN ops: the ops of `program`
    whose HLO text reads an operand shaped like a layer's expert weights
    (the family's `expert_operand`; None where there are no experts).  A
    program that reads all E experts for every token stays far under
    100% however fast it streams them."""
    cfg = ctx["cell"].config
    fam = family(cfg)
    shaped = fam.expert_operand(cfg)
    if shaped is None:
        return None
    p, c = _program(ctx, program), _counter(ctx, counter)
    seconds = sum(o["seconds"] for o in ctx["trace"]["ops"].values()
                  if o["program"] == program and shaped.search(o["text"]))
    if not seconds or not p["count"] or not c:
        return None
    steps = p["count"] * cfg["engine"]["max_burst"]
    least = _per_launch(c, lambda ev: fam.expert_bytes_per_step(
        cfg, ev["lanes"])) / peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)


def prefill_roofline(ctx, program: str, counter: str):
    """FLOPs the prompt tokens of the traced window need (block matrices
    with the routed experts only, causal attention over each token's
    context; the output head, once a prompt, is left out: under 0.01%)
    at the chip's peak, over the prefill programs' device time.  Bound
    by compute: a 128-token chunk does 128 multiply-adds per weight."""
    p, c = _program(ctx, program), _counter(ctx, counter)
    if not c or not p["seconds"] or not c.get("chunks"):
        return None
    cfg = ctx["cell"].config
    per_launch = family(cfg).prefill_flops(
        cfg, c["tokens"], c["context"]) / c["chunks"]
    return 100.0 * per_launch * p["count"] \
        / peaks(ctx["device"]["kind"])["bf16_flops"] / p["seconds"]


def device_idle_share(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def host_time_per_step(ctx, field: str, scale: float = 1000.0):
    vals = ctx["replica"].get(field)
    return scale * mean(vals) if vals else None


def collective_exposed_share(ctx, program: str):
    """Collective op time during which no compute op runs on that device,
    as a share of the step program's device time."""
    p = _program(ctx, program)
    c = ctx["trace"]["collectives"]
    return 100.0 * c["exposed_seconds"] / p["seconds"] if p["seconds"] else None


def train_mfu(ctx):
    """An end-to-end utilisation, named as such: tokens per second of the
    traced run's own window x FLOPs a token requires (forward + backward,
    no recomputation) over chips x peak."""
    run, cfg = ctx["run"], ctx["cell"].config
    if "tokens" not in run:
        return None
    per_tok = family(cfg).train_flops_per_token(
        cfg, ctx["cell"].traffic["seq_len"])
    peak = ctx["device"]["count"] * peaks(ctx["device"]["kind"])["bf16_flops"]
    return 100.0 * run["tokens"] / run["window_s"] * per_tok / peak
