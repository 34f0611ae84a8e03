"""`loop_attn_share.decode`: the share of `program`'s device time spent in
its ops named for `kernel` (a Pallas kernel's op carries the kernel's name:
`paged_decode_attention.13` in a trace): 100 x their seconds over the
program's.  In a model whose stack runs more than once a step launches the
kernel once a full layer and pass, so this is what those launches cost of a
step.  `kernel_seconds` is shared with `loop_attn_roofline.decode`.  A
program without such ops (a parent commit's, a model that reads its pool by
the block loop) and a run without a trace of the program give None."""


def kernel_seconds(ctx, program: str, kernel: str):
    """(device seconds of `program`'s ops named `kernel`.<n>, their count:
    the kernel's launches in the trace; the program's record of the
    trace), or None."""
    trace = ctx.get("trace")
    if not trace:
        return None
    p = trace["programs"].get(program)
    if not p or not p.get("seconds"):
        return None
    ops = [o for key, o in trace["ops"].items()
           if o["program"] == program
           and key.partition("/")[2].lstrip("%").startswith(kernel + ".")]
    seconds = sum(o["seconds"] for o in ops)
    return (seconds, sum(o["count"] for o in ops), p) if seconds else None


def read(ctx, program: str, kernel: str):
    found = kernel_seconds(ctx, program, kernel)
    return None if found is None else 100.0 * found[0] / found[2]["seconds"]
