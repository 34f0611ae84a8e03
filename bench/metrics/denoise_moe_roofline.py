"""`denoise_moe_roofline`: 100 x the least time of one pass's expert
visit (the family's `expert_bytes_per_step` for the experts the program
counted a layer and pass, `experts_read` of the window's ticks, at peak
HBM bandwidth) over the device time, a pass, of
`program`'s ops whose HLO text reads an operand shaped like a layer's
expert weights (the family's `expert_operand`; each op's seconds are its
own, a `while` less its body).  A family that fills no blocks, a program
without such ops and a trace without the counter give None."""
from bench.harness.spec import BENCH_DIR, family, load_file, metric_file


def read(ctx, program: str, counter: str):
    cfg = ctx["cell"].config
    fam = family(cfg)
    trace = ctx.get("trace") or {}
    shaped = getattr(fam, "expert_operand", None)
    p = trace.get("programs", {}).get(program)
    if shaped is None or shaped(cfg) is None or not p or not p.get("count"):
        return None
    roof, per_pass = (load_file(metric_file(BENCH_DIR, name, ".py"),
                                "bench_metric_")
                      for name in ("denoise_roofline", "denoise_pass_dev_ms"))
    passes = per_pass.passes_per_launch(cfg)
    pattern = shaped(cfg)
    seconds = sum(o["seconds"] for o in trace["ops"].values()
                  if o["program"] == program and pattern.search(o["text"]))
    if not seconds or not passes:
        return None
    return roof.launch_roofline(
        ctx, program, counter,
        lambda cfg, ev, read: fam.expert_bytes_per_step(
            cfg, ev["lanes"], read),
        seconds / (p["count"] * passes))
