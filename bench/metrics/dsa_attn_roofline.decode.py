"""`dsa_attn_roofline.decode`: `step_roofline` of bench/metrics/
dsa_index_roofline.decode.py with the family's `attn_operand`,
`attn_flops_per_step` and `attn_bytes_per_step`."""
from bench.harness.spec import BENCH_DIR, load_file, metric_file


def read(ctx, program: str, counter: str):
    shape = load_file(metric_file(BENCH_DIR, "dsa_index_roofline.decode",
                                  ".py"), "bench_metric_")
    return shape.step_roofline(ctx, program, counter, "attn_operand",
                               "attn_flops_per_step", "attn_bytes_per_step")
