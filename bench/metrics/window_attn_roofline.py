"""`window_attn_roofline`: `ssm_state_roofline`'s shape (bench/metrics/
ssm_state_roofline.py) with the family's `ring_bytes_per_step` and
`ring_operand` in place of the recurrent state's.  Ring rows one decode
step must read, at peak bandwidth, over the device time of the ops of
`program` whose HLO text shows an array of the rings' shape as an operand
or a result.  A `while` carries the rings through its tuple and reads
nothing of them itself, so its own time is left out.  A family that gives
neither function, a program without such ops (one that keeps no ring) and
a trace without the counter give None."""
import re

from bench.harness.peaks import peaks
from bench.harness.spec import family
from bench.harness.stats import mean

_LOOP = re.compile(r"\bwhile\(")


def read(ctx, program: str, counter: str):
    cfg = ctx["cell"].config
    fam = family(cfg)
    shaped = getattr(fam, "ring_operand", None)
    per_step = getattr(fam, "ring_bytes_per_step", None)
    if shaped is None or per_step is None:
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
    burst = cfg["engine"]["max_burst"]
    steps = p["count"] * burst
    least = mean([per_step(
        cfg, ev["kv_tokens"] + ev["lanes"] * (burst - 1) / 2, ev["lanes"])
        for ev in c["each"]]) / peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)
