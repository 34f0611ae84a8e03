"""`dsa_index_roofline.decode`: the least time of one decode step's index
scan (the family's `index_flops_per_step` at the chip's peak bf16 FLOP/s or
`index_bytes_per_step` at its peak HBM bandwidth, whichever is larger, for
the lanes and the live positions a burst of the window carried) over the
device time, a step, of the ops of `program` whose HLO text shows an array
of stored index keys (the family's `index_operand`).  A `while` carries the
pool through its tuple and reads nothing of it itself, so its own time is
left out (told by its name as well: the burst's loops carry so long a tuple
that their text is cut before its `while(`).  `step_roofline` is the shape of it: `launch_roofline` of
bench/metrics/dsa_index_roofline.py for a burst's steps, counted as
`mla_attn_roofline` counts them; `dsa_attn_roofline.decode` takes it with
its own three functions.  A family that lacks them, a program without such
ops and a trace without the counter give None."""
import re

from bench.harness.peaks import peaks
from bench.harness.spec import family
from bench.harness.stats import mean

_LOOP = re.compile(r"^%?while[.\s]|\bwhile\(")


def step_roofline(ctx, program: str, counter: str, operand: str,
                  flops: str, nbytes: str):
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
                  and not _LOOP.search(o["text"]))
    if not seconds:
        return None
    peak = peaks(ctx["device"]["kind"])
    burst = cfg["engine"]["max_burst"]

    def least(ev):
        # a lane grows by a position a step: the burst's mean step
        seen = ev["kv_tokens"] + ev["lanes"] * (burst - 1) / 2
        return max(flops_of(cfg, seen, ev["lanes"]) / peak["bf16_flops"],
                   bytes_of(cfg, seen, ev["lanes"]) / peak["hbm_bytes_per_s"])

    return 100.0 * mean([least(ev) for ev in c["each"]]) \
        / (seconds / (p["count"] * burst))


def read(ctx, program: str, counter: str):
    return step_roofline(ctx, program, counter, "index_operand",
                         "index_flops_per_step", "index_bytes_per_step")
