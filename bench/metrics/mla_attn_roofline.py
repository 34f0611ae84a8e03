"""`mla_attn_roofline`: `window_attn_roofline`'s shape (bench/metrics/
window_attn_roofline.py) with the family's `latent_bytes_per_step`,
`latent_flops_per_step` and `latent_operand` in place of the rings'.  The
least time of one decode step's attention over the latent pool (the
larger of the latent rows its lanes must read at peak bandwidth and of
the absorbed form's products at peak FLOP/s), over the device time, a
step, of the ops of `program` whose HLO text shows an array of stored
latent rows as an operand or a result.  A `while` carries the pool
through its tuple and reads nothing of it itself, so its own time is
left out.  A family that gives none of the three functions, a program
without such ops (one that keeps no latent pool) and a trace without the
counter give None."""
import re

from bench.harness.peaks import peaks
from bench.harness.spec import family
from bench.harness.stats import mean

_LOOP = re.compile(r"\bwhile\(")


def read(ctx, program: str, counter: str):
    cfg = ctx["cell"].config
    fam = family(cfg)
    shaped = getattr(fam, "latent_operand", None)
    n_bytes = getattr(fam, "latent_bytes_per_step", None)
    n_flops = getattr(fam, "latent_flops_per_step", None)
    if shaped is None or n_bytes is None or n_flops is None:
        return None
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
    steps = p["count"] * burst

    def least(ev):
        seen = ev["kv_tokens"] + ev["lanes"] * (burst - 1) / 2
        return max(n_bytes(cfg, seen, ev["lanes"]) / peak["hbm_bytes_per_s"],
                   n_flops(cfg, seen, ev["lanes"]) / peak["bf16_flops"])

    return 100.0 * mean([least(ev) for ev in c["each"]]) / (seconds / steps)
