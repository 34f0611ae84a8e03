"""`ssm_state_roofline`: `moe_ffn_roofline`'s shape (bench/harness/
readers.py) with the family's `state_bytes_per_step` and `state_operand`
in place of the experts'.  Recurrent state one decode step must move, at
peak bandwidth, over the device time of the ops of `program` whose HLO
text shows the lanes' state as an operand or a result.  A family that
gives neither function, a program without such ops and a trace without
the counter give None."""
from bench.harness.peaks import peaks
from bench.harness.spec import family
from bench.harness.stats import mean


def read(ctx, program: str, counter: str):
    cfg = ctx["cell"].config
    fam = family(cfg)
    shaped = getattr(fam, "state_operand", None)
    per_step = getattr(fam, "state_bytes_per_step", None)
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
                  if o["program"] == program and pattern.search(o["text"]))
    if not seconds:
        return None
    steps = p["count"] * cfg["engine"]["max_burst"]
    least = mean([per_step(cfg, ev["lanes"]) for ev in c["each"]]) \
        / peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)
