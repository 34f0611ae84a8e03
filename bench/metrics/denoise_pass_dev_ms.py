"""`denoise_pass_dev_ms`: device seconds of `program` (the burst of a
model that generates by diffusion over blocks) over the forward passes its
executions made, x `scale`.  A call fills `max_burst // diffusion_block`
blocks a lane, each in the family's `passes_per_block` passes (the
denoising passes and the commit), all from the configuration.  A family
without `passes_per_block` (a next-token model) and a trace without the
program give None.  `pass_seconds` is what the other `denoise_*` metrics
divide by."""
from bench.harness.spec import family


def passes_per_launch(cfg: dict):
    """Forward passes one execution of the burst makes; None for a family
    that fills no blocks."""
    per_block = getattr(family(cfg), "passes_per_block", None)
    if per_block is None:
        return None
    blocks = cfg["engine"]["max_burst"] // cfg["assumed"]["diffusion_block"]
    return blocks * per_block(cfg)


def pass_seconds(ctx, program: str):
    """Device seconds a pass of `program` took, mean over the trace."""
    passes = passes_per_launch(ctx["cell"].config)
    p = (ctx.get("trace") or {}).get("programs", {}).get(program)
    if not passes or not p or not p.get("count") or not p.get("seconds"):
        return None
    return p["seconds"] / (p["count"] * passes)


def read(ctx, program: str, scale: float = 1.0):
    seconds = pass_seconds(ctx, program)
    return None if seconds is None else scale * seconds
