"""`ssd_scan_roofline`: the least time one prefill chunk's state-space
scan can take (every Mamba layer: the family's `scan_flops_per_chunk` at
the chip's peak bf16 FLOP/s or `scan_bytes_per_chunk` at its peak HBM
bandwidth, whichever is larger, for the tokens a launch of the window
carried) over the device time, per execution of `program`, of its ops
whose HLO text shows a slot's state or the chunk's decay matrix (the
family's `scan_operand`).  A `while` carries the state through its tuple
and touches nothing of it itself, so its own time is left out.  A family
that lacks the three functions, a program without such ops and a trace
without the counter give None.  `chunk_roofline` is the shape of it, and
`moe_chunk_roofline` takes it with the experts' three functions."""
import re

from bench.harness.peaks import peaks
from bench.harness.spec import family
from bench.harness.stats import mean

_LOOP = re.compile(r"\bwhile\(")


def chunk_roofline(ctx, program: str, counter: str, operand: str,
                   flops: str, nbytes: str, skip_loops: bool):
    """100 x the least time of a launch's work (the larger of the
    family's `flops` at peak FLOP/s and `nbytes` at peak bandwidth, each
    a function of (configuration, tokens a chunk), mean over the
    counter's launches) over the device time, per execution of
    `program`, of its ops whose text the family's `operand` pattern
    finds."""
    cfg = ctx["cell"].config
    fam = family(cfg)
    fns = [getattr(fam, n, None) for n in (operand, flops, nbytes)]
    if None in fns:
        return None
    shaped, flops_of, bytes_of = fns
    pattern = shaped(cfg)
    trace = ctx["trace"]
    p = trace["programs"].get(program)
    c = trace["counters"].get(counter)
    if pattern is None or not p or not p.get("count") or not c \
            or not c.get("each"):
        return None
    seconds = sum(o["seconds"] for o in trace["ops"].values()
                  if o["program"] == program and pattern.search(o["text"])
                  and not (skip_loops and _LOOP.search(o["text"])))
    if not seconds:
        return None
    peak = peaks(ctx["device"]["kind"])

    def least(ev):
        tokens = ev["tokens"] / max(ev.get("chunks", 1), 1)
        return max(flops_of(cfg, tokens) / peak["bf16_flops"],
                   bytes_of(cfg, tokens) / peak["hbm_bytes_per_s"])

    return 100.0 * mean([least(ev) for ev in c["each"]]) \
        / (seconds / p["count"])


def read(ctx, program: str, counter: str):
    return chunk_roofline(ctx, program, counter, "scan_operand",
                          "scan_flops_per_chunk", "scan_bytes_per_chunk",
                          skip_loops=True)
