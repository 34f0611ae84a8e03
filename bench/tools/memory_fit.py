"""Does a configuration fit its chips?  Compiles its largest programs for
a *described* v5e (no chip needed: on-chip-measurement guide, section 2)
and prints `memory_analysis()` per device beside what the process keeps
resident (parameters, pool / optimizer state).

    JAX_PLATFORMS=cpu python bench/tools/memory_fit.py <config> [--layers N ...]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _report(name, compiled):
    m = compiled.memory_analysis()
    out = {"program": name,
           "argument_GB": m.argument_size_in_bytes / 1e9,
           "output_GB": m.output_size_in_bytes / 1e9,
           "alias_GB": m.alias_size_in_bytes / 1e9,
           "temp_GB": m.temp_size_in_bytes / 1e9,
           "live_GB": (m.argument_size_in_bytes + m.output_size_in_bytes
                       - m.alias_size_in_bytes
                       + m.temp_size_in_bytes) / 1e9}
    print(json.dumps(out), flush=True)
    return out


def serve_fit(config, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from bench.harness.spec import transformer_config
    from ray_tpu.models.decoding import (
        init_paged_cache, make_paged_engine_fns)
    from ray_tpu.models.transformer import init_params

    cfg = transformer_config(config)
    eng = config["engine"]
    dev = SingleDeviceSharding(topo.devices[0])
    n_blocks = eng["num_slots"] * eng["max_len"] // eng["block_size"] + 1
    b_max = -(-eng["max_len"] // eng["block_size"])

    def spec(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=dev), tree)

    params = spec(jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)))
    cache = spec(jax.eval_shape(
        lambda: init_paged_cache(cfg, n_blocks, eng["block_size"])))
    rng = spec(jax.eval_shape(lambda: jax.random.key(0)))
    chunk_fn, burst_fn, _ = make_paged_engine_fns(cfg)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    w, c = eng["num_slots"], eng["prefill_chunk"]
    nbytes = lambda t: sum(x.size * x.dtype.itemsize  # noqa: E731
                           for x in jax.tree.leaves(t))
    print(json.dumps({"layers": cfg.n_layers,
                      "params_GB": nbytes(params) / 1e9,
                      "pool_GB": nbytes(cache) / 1e9}), flush=True)
    _report(f"paged_decode_burst w={w}", burst_fn.lower(
        params, cache, arr((w,), jnp.int32), arr((w, b_max), jnp.int32),
        arr((w,), jnp.int32), arr((w,), jnp.bool_),
        arr((w,), jnp.float32), rng, n_steps=eng["max_burst"]).compile())
    _report(f"paged_prefill_chunk c={c}", chunk_fn.lower(
        params, cache, arr((c,), jnp.int32), arr((b_max,), jnp.int32),
        arr((), jnp.int32), arr((), jnp.int32)).compile())


def train_fit(config, traffic, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench.harness.spec import transformer_config
    from ray_tpu.models.training import make_train_step
    from ray_tpu.parallel import MeshConfig, build_mesh

    cfg = transformer_config(config)
    mesh = build_mesh(MeshConfig(**config["mesh"]),
                      devices=topo.devices[:4])
    init_fn, step_fn = make_train_step(cfg, mesh)
    # eval_shape of the jitted init keeps its declared out_shardings
    state = jax.eval_shape(init_fn, jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (traffic["global_batch"], traffic["seq_len"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"))))}
    print(json.dumps({"layers": cfg.n_layers,
                      "params": cfg.num_params,
                      "state_GB_per_device":
                          16 * cfg.num_params / 4 / 1e9}), flush=True)
    # The program asks jax.default_backend() whether to use its Pallas
    # attention kernel; here that is the CPU, so steer it (as
    # tests/test_tpu_compile.py does) or the O(T^2) reference is compiled.
    real, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        lowered = step_fn.lower(state, batch)
    finally:
        jax.default_backend = real
    try:
        compiled = lowered.compile()
        _report("train_step fsdp=4 (per device)", compiled)
        print(json.dumps({"tpu_custom_call_in_step":
                          "tpu_custom_call" in compiled.as_text()}))
    except jax.errors.JaxRuntimeError as e:
        print(json.dumps({"program": "train_step fsdp=4",
                          "refused": str(e).splitlines()[0][:300]}),
              flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--traffic", default="sft-4k")
    ap.add_argument("--layers", type=int, nargs="*")
    args = ap.parse_args()
    from jax.experimental import topologies

    from bench.harness.spec import BENCH_DIR

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    with open(os.path.join(BENCH_DIR, "configs", args.config + ".json")) as f:
        config = json.load(f)
    for layers in args.layers or [config["num_hidden_layers"]]:
        c = dict(config, num_hidden_layers=layers)
        if c["kind"] == "train":
            with open(os.path.join(BENCH_DIR, "traffic",
                                   args.traffic + ".json")) as f:
                train_fit(c, json.load(f), topo)
        else:
            serve_fit(c, topo)


if __name__ == "__main__":
    main()
