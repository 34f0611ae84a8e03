"""`latent_swa_roofline`: `launch_roofline` of bench/metrics/
dsa_index_roofline.py with the family's `ring_operand`, `ring_flops` and
`ring_bytes`."""
from bench.harness.spec import BENCH_DIR, load_file, metric_file


def read(ctx, program: str, counter: str):
    shape = load_file(metric_file(BENCH_DIR, "dsa_index_roofline", ".py"),
                      "bench_metric_")
    return shape.launch_roofline(ctx, program, counter, "ring_operand",
                                 "ring_flops", "ring_bytes")
