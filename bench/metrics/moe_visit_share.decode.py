"""`moe_visit_share.decode`: the share of `program`'s device time spent
in its ops whose HLO text reads an operand shaped like a layer's held
expert weights (the family's `expert_operand`), the ops that
`moe_ffn_roofline` (bench/harness/readers.py) holds to their bytes: 100 x
their seconds over the program's.  Each op's seconds are its own (a
`while` less its body: bench/harness/xplane.py), so nothing is counted
twice.  A family that gives no such function, a program without such ops
and a run without a trace of the program give None."""
from bench.harness.spec import family


def read(ctx, program: str):
    cfg = ctx["cell"].config
    shaped = getattr(family(cfg), "expert_operand", None)
    trace = ctx.get("trace")
    if shaped is None or not trace:
        return None
    pattern = shaped(cfg)
    p = trace["programs"].get(program)
    if pattern is None or not p or not p.get("seconds"):
        return None
    seconds = sum(o["seconds"] for o in trace["ops"].values()
                  if o["program"] == program and pattern.search(o["text"]))
    return 100.0 * seconds / p["seconds"] if seconds else None
