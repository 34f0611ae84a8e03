"""`gdn_step_share.decode`: the share of `program`'s device time spent in
its ops whose HLO text shows an operand of a linear layer's mixer (the
family's `mixer_operand`: the lanes' or the slots' state, the rows of the
convolution, the linear layers' stacked projections): 100 x their seconds
over the program's, `moe_visit_share.decode`'s shape with another
pattern, so that the two read side by side say whether the step of the
rule or the visit of the experts sets a decode step's pace.  Each op's
seconds are its own (a `while` less its body: bench/harness/xplane.py).
A family that gives no such function, a program without such ops and a
run without a trace of the program give None."""
from bench.harness.spec import family


def read(ctx, program: str):
    cfg = ctx["cell"].config
    shaped = getattr(family(cfg), "mixer_operand", None)
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
