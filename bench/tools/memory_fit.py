"""Does a configuration fit its chips?  Compiles its largest programs for
a *described* v5e (no chip needed: on-chip-measurement guide, section 2)
and prints `memory_analysis()` per device beside what the process keeps
resident (parameters, pool / optimizer state).

    JAX_PLATFORMS=cpu python bench/tools/memory_fit.py <config> \
        [--vary num_hidden_layers 8 12 16]

What the programs are is the configuration's family's to say
(`serve_programs` of bench/families/<family>.py; the train step is
`train_cell.step_fns`): nothing here names a model.
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


def _nbytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def serve_fit(config, topo):
    import jax
    from jax.sharding import SingleDeviceSharding

    from bench.harness import spec

    dev = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=dev), tree)

    resident, programs = spec.family(config).serve_programs(config, place)
    print(json.dumps({f"{name}_GB": _nbytes(tree) / 1e9
                      for name, tree in resident.items()}), flush=True)
    for name, lowered in programs:
        _report(name, lowered.compile())


def train_fit(config, traffic, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench.harness.train_cell import step_fns
    from ray_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(**config["mesh"]),
                      devices=topo.devices[:4])
    init_fn, step_fn = step_fns(config, mesh)
    # eval_shape of the jitted init keeps its declared out_shardings
    state = jax.eval_shape(init_fn, jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (traffic["global_batch"], traffic["seq_len"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"))))}
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    print(json.dumps({"params": n_params,
                      "state_GB_per_device":
                          16 * n_params / 4 / 1e9}), flush=True)
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
    ap.add_argument("--vary", nargs="+", metavar=("KEY", "VALUE"),
                    help="a key of the configuration file and the values "
                         "(JSON) to fit it at, one after another")
    args = ap.parse_args()
    from jax.experimental import topologies

    from bench.harness.spec import BENCH_DIR

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    with open(os.path.join(BENCH_DIR, "configs", args.config + ".json")) as f:
        config = json.load(f)
    key, *values = args.vary or [None]
    for value in values or [None]:
        varied = {} if value is None else {key: json.loads(value)}
        c = dict(config, **varied)
        print(json.dumps({"config": args.config, **varied}), flush=True)
        if c["kind"] == "train":
            with open(os.path.join(BENCH_DIR, "traffic",
                                   args.traffic + ".json")) as f:
                train_fit(c, json.load(f), topo)
        else:
            serve_fit(c, topo)


if __name__ == "__main__":
    main()
