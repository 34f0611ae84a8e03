"""Records the small trace that bench/tests checks the reduction against:
two named jitted programs, a few executions each, host annotations and
counters around them.  Run on the chip; writes
chiprun_out/small_trace/{small.xplane.pb, dump.txt, reduced.json}.
"""
from __future__ import annotations

import glob
import io
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import redirect_stdout

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from bench.harness import xplane

    @jax.jit
    def small_matmul(x):
        return jnp.tanh(x @ x)

    @jax.jit
    def small_scan(x):
        def body(c, _):
            return jnp.sin(c) + 1.0, ()
        return jax.lax.scan(body, x, None, length=4)[0]

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    y = jnp.ones((256, 256), jnp.float32)
    jax.block_until_ready((small_matmul(x), small_scan(y)))
    out = os.path.join("chiprun_out", "small_trace")
    os.makedirs(out, exist_ok=True)
    tmp = tempfile.mkdtemp(prefix="small_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.engine.decode_tick"):
            small_matmul(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.count.decode", lanes=4 + i,
                                          kv_tokens=100 * (i + 1)):
            pass
        time.sleep(0.005)
        with jax.profiler.TraceAnnotation("bench.engine.prefill_tick"):
            small_scan(y).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    buf = io.StringIO()
    with redirect_stdout(buf):
        xplane.dump(path, per_line=12)
    with open(os.path.join(out, "dump.txt"), "w") as f:
        f.write(buf.getvalue())
    reduced = xplane.reduce_file(path, programs=["small_matmul",
                                                 "small_scan"])
    with open(os.path.join(out, "reduced.json"), "w") as f:
        json.dump(reduced, f, indent=1)
    print(buf.getvalue()[-6000:])
    print(json.dumps({k: reduced[k] for k in (
        "window_s", "busy_s", "programs", "counters", "annotations",
        "breakdown")}, indent=1))
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
