"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once, through the entry points a user calls, at
the full width of a model the repo supports, with seeded random weights:

  train   ray_tpu.init() -> JaxTrainer(ScalingConfig(use_tpu=True)) ->
          Dataset.iter_jax_batches -> make_train_step(bench-350m), 1 warm-up
          + 5 steps at batch 8 x seq 2048
  serve   serve.run(LLMDeployment("bench-1b4", engine="paged")) -> HTTP
          proxy -> handle -> replica -> PagedLLMEngine: a warm-up, 4
          streamed requests two at a time, one unary request whose greedy
          tokens must equal the streamed ones, then a redeploy that must
          find its programs in the compile cache

Both run in worker processes the runtime starts.  A chip belongs to one
process at a time, so this process never initialises a JAX backend
(asserted before the last line) and the serve replica starting on the
chip right after the train worker is the check that the worker let go.

    python chip_smoke.py            one chip (what the driver runs)
    python chip_smoke.py --chips 4  only: bench-350m on MeshConfig(fsdp=4)
                                    against the same steps on one device
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny
                                    CPU rehearsal of the same control flow
                                    (tiny model, small shapes); runs its
                                    phases, then fails the device check

One JSON object per phase, then as the last line of stdout exactly
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}} with
the values the workers reported.  Anything that fails — a phase, a check,
a worker that is not on a TPU, a host with no chip — exits non-zero with
no such line.
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import re
import tempfile
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

SEED = 0

SIZES = {
    "full": dict(train_model="bench-350m", batch=8, seq=2048,
                 serve_model="bench-1b4", num_slots=8, max_len=2048,
                 prompt_len=512, new_tokens=64),
    "tiny": dict(train_model="tiny", batch=8, seq=128,
                 serve_model="tiny", num_slots=8, max_len=256,
                 prompt_len=48, new_tokens=16),
}


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def fail(why: str):
    raise SystemExit(f"chip_smoke: FAILED: {why}")


def corpus(model: str, rows: int, cols: int, seed: int):
    """Seeded synthetic tokens (host side; numpy only)."""
    import numpy as np

    from ray_tpu.models import configs

    vocab = configs.get(model).vocab_size
    return np.random.default_rng(seed).integers(
        0, vocab, (rows, cols), dtype=np.int32)


def wait_for_exit(pid: int, timeout_s: float = 120.0) -> float:
    """Seconds until `pid` is gone.  The next phase needs the chip this
    process held: a killed holder takes seconds to leave it."""
    t0 = time.monotonic()
    while os.path.exists(f"/proc/{pid}"):
        if time.monotonic() - t0 > timeout_s:
            fail(f"worker {pid} still alive {timeout_s:.0f}s after its "
                 f"phase ended; the chip is not free")
        time.sleep(0.05)
    return time.monotonic() - t0


# ---------------------------------------------------------------------------
# train phase (the loops below run inside the train worker)
# ---------------------------------------------------------------------------
_HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
              "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
              "u64": 8}


def _largest_all_reduce_bytes(text: str) -> int:
    """Bytes of the largest all-reduce result in optimized HLO text.  A
    gradient that is reduced to shards never passes through one; a
    gradient that is not shows up here at the size of its weight."""
    largest = 0
    for result in re.findall(r"= (.*?) all-reduce(?:-start)?\(", text):
        for dtype, dims in re.findall(r"\b([a-z]+\d+|pred)\[([\d,]*)\]",
                                      result):
            size = _HLO_BYTES[dtype] * math.prod(
                int(d) for d in dims.split(",") if d)
            largest = max(largest, size)
    return largest


def _program_report(compiled) -> dict:
    """Is the Pallas kernel in the compiled step, which collectives, and
    what one device is handed."""
    text = compiled.as_text()
    return {"tpu_custom_call_in_step": "tpu_custom_call" in text,
            "collectives_in_step": dict(collections.Counter(re.findall(
                r"all-gather|reduce-scatter|all-reduce|collective-permute",
                text))),
            "largest_all_reduce_bytes": _largest_all_reduce_bytes(text),
            "argument_bytes_per_device":
                compiled.memory_analysis().argument_size_in_bytes}


def _kernel_vs_reference():
    """The repo's own reference on a small input: flash attention (the
    Pallas kernel on a TPU) against plain-XLA attention, values and
    gradients, bf16."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import flash_attention, mha_reference

    keys = jax.random.split(jax.random.key(SEED), 4)
    q, k, v, w = (jax.random.normal(kk, (2, 512, 4, 64), jnp.bfloat16)
                  for kk in keys)

    def run(attn):
        def loss(q_, k_, v_):
            out = attn(q_, k_, v_).astype(jnp.float32)
            return jnp.sum(out * w.astype(jnp.float32))

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)

    (lf, gf) = run(lambda *a: flash_attention(*a, True, None))
    (lr, gr) = run(lambda *a: mha_reference(*a, causal=True))
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
              for a, b in zip(gf, gr))
    return {"loss_flash": float(lf), "loss_reference": float(lr),
            "grad_max_abs_err": err}


def _timed_steps(compiled, state, batches):
    """Run the compiled step over `batches`; every timing ends in
    block_until_ready."""
    import jax

    losses, seconds = [], []
    for batch in batches:
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        jax.block_until_ready((state, metrics))
        seconds.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
    return state, losses, seconds


def train_loop(config):
    import jax

    from ray_tpu import train
    from ray_tpu.models import configs
    from ray_tpu.models.training import make_train_step
    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.util import compile_cache
    from ray_tpu.util.tpu import device_report

    compile_cache.counts()        # starts counting: before any compile
    report = {"pid": os.getpid()}
    cfg = configs.get(config["model"])
    mesh = build_mesh(MeshConfig(fsdp=-1))
    init_fn, step_fn = make_train_step(cfg, mesh)
    state = init_fn(jax.random.key(config["seed"]))
    batches = train.get_dataset_shard("train").iter_jax_batches(
        batch_size=config["batch"])
    first = next(batches)
    t0 = time.perf_counter()
    compiled = step_fn.lower(state, first).compile()
    report["compile_s"] = time.perf_counter() - t0
    report.update(_program_report(compiled))
    # 1 warm-up step, then the timed ones.
    state, warm_loss, warm_s = _timed_steps(compiled, state, [first])
    state, losses, seconds = _timed_steps(compiled, state, batches)
    report.update(warmup_step_s=warm_s[0], losses=warm_loss + losses,
                  step_s=seconds,
                  attention_vs_reference=_kernel_vs_reference(),
                  compile_cache=compile_cache.counts(), **device_report())
    train.report(report)


def sharded_loop(config):
    """--chips 4: the same steps from the same seed on MeshConfig(fsdp=4)
    and on a one-device mesh, in one worker that owns all four chips."""
    import gc

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu import train
    from ray_tpu.models import configs
    from ray_tpu.models.training import make_train_step
    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.util.tpu import device_report

    dev = device_report()
    report = {"pid": os.getpid(), "platform": dev["platform"],
              "device_kind": dev["device_kind"], "count": dev["count"]}
    cfg = configs.get(config["model"])
    runs = {"sharded": build_mesh(MeshConfig(fsdp=4)),
            "single": build_mesh(MeshConfig(fsdp=1),
                                 devices=jax.devices()[:1])}
    for name, mesh in runs.items():
        init_fn, step_fn = make_train_step(cfg, mesh)
        state = init_fn(jax.random.key(config["seed"]))
        batches = list(train.get_dataset_shard(name).iter_jax_batches(
            batch_size=config["batch"],
            sharding=NamedSharding(mesh, P(("dp", "fsdp")))))
        compiled = step_fn.lower(state, batches[0]).compile()
        state, losses, seconds = _timed_steps(compiled, state, batches)
        wq = state.params["blocks"]["wq"]
        moments = jax.tree.leaves(state.opt_state)
        memory = [d.memory_stats() or {} for d in jax.devices()]
        report[name] = {
            "losses": losses, "step_s": seconds,
            **_program_report(compiled),
            "wq_shape": list(wq.shape),
            "wq_shard_shape": list(wq.sharding.shard_shape(wq.shape)),
            # After the last step: what the optimizer left on each device.
            "opt_state_bytes": sum(x.nbytes for x in moments),
            "opt_state_bytes_on_device": [
                sum(sh.data.nbytes for x in moments
                    for sh in x.addressable_shards if sh.device == d)
                for d in mesh.devices.flat],
            "bytes_in_use": [m.get("bytes_in_use") for m in memory],
            "peak_bytes_in_use": [m.get("peak_bytes_in_use")
                                  for m in memory]}
        del state, compiled, batches
        gc.collect()
    train.report(report)


def run_trainer(loop, loop_config, datasets, resources=None):
    """JaxTrainer.fit() -> the worker's single report."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    with tempfile.TemporaryDirectory(prefix="chip_smoke_train_") as storage:
        result = JaxTrainer(
            loop, train_loop_config=loop_config,
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         resources_per_worker=resources),
            run_config=RunConfig(name="chip_smoke", storage_path=storage),
            datasets=datasets).fit()
    if result.error is not None:
        fail(f"train worker failed: {result.error}")
    return result.metrics


def train_phase(size: dict, tiny: bool) -> dict:
    from ray_tpu import data as rdata

    n_steps = 1 + 5
    ds = rdata.from_numpy(
        corpus(size["train_model"], n_steps * size["batch"],
               size["seq"] + 1, SEED), column="tokens")
    r = run_trainer(train_loop, {"model": size["train_model"],
                                 "batch": size["batch"], "seed": SEED},
                    {"train": ds})
    r["chip_release_wait_s"] = wait_for_exit(r["pid"])
    emit("train", model=size["train_model"], batch=size["batch"],
         seq=size["seq"], **r)
    from ray_tpu.models import configs

    ln_vocab = math.log(configs.get(size["train_model"]).vocab_size)
    if len(r["losses"]) != n_steps or not all(
            math.isfinite(x) for x in r["losses"]):
        fail(f"train: want {n_steps} finite losses, got {r['losses']}")
    if abs(r["losses"][0] - ln_vocab) > 0.10 * ln_vocab:
        fail(f"train: first loss {r['losses'][0]:.3f} is not within 10% "
             f"of ln(vocab) = {ln_vocab:.3f}")
    if r["attention_vs_reference"]["grad_max_abs_err"] > 0.1:
        fail(f"train: flash attention disagrees with the reference: "
             f"{r['attention_vs_reference']}")
    if not tiny:
        if r["platform"] != "tpu":
            fail(f"train worker ran on {r['platform']!r}, not a TPU")
        if not r["tpu_custom_call_in_step"]:
            fail("train: no tpu_custom_call in the compiled step — the "
                 "Pallas kernel is not on the path")
    return r


def sharded_phase(size: dict) -> dict:
    from ray_tpu import data as rdata

    tokens = corpus(size["train_model"], 3 * size["batch"],
                    size["seq"] + 1, SEED)
    r = run_trainer(
        sharded_loop, {"model": size["train_model"],
                       "batch": size["batch"], "seed": SEED},
        {name: rdata.from_numpy(tokens, column="tokens")
         for name in ("sharded", "single")},
        resources={"TPU": 4})
    wait_for_exit(r["pid"])
    emit("train_sharded", model=size["train_model"], batch=size["batch"],
         seq=size["seq"], mesh="fsdp=4 vs one device", **r)
    sh, one = r["sharded"], r["single"]
    rel = [abs(a - b) / abs(b) for a, b in zip(sh["losses"], one["losses"])]
    if len(rel) != 3 or not all(x <= 1e-2 for x in rel):
        fail(f"sharded vs single-device losses differ: {sh['losses']} vs "
             f"{one['losses']} (relative {rel})")
    if sh["wq_shard_shape"][1] * 4 != sh["wq_shape"][1]:
        fail(f"parameters are not split four ways: wq {sh['wq_shape']} "
             f"has shards {sh['wq_shard_shape']}")
    # The optimizer ran on shards: after the last step each device holds
    # a quarter of its state (norm gains and counters are replicated).
    quarter = 1.01 * sh["opt_state_bytes"] / 4
    if (len(sh["opt_state_bytes_on_device"]) != 4
            or not all(b <= quarter for b in sh["opt_state_bytes_on_device"])):
        fail(f"optimizer state is not split four ways: "
             f"{sh['opt_state_bytes_on_device']} of {sh['opt_state_bytes']}")
    if r["platform"] == "tpu":    # the CPU backend reports no memory
        if not all(sh["bytes_in_use"]):
            fail(f"not every device holds memory: {sh['bytes_in_use']}")
        c = sh["collectives_in_step"]
        if not (sh["tpu_custom_call_in_step"] and c.get("all-gather")):
            fail(f"sharded step lacks the kernel or the parameter "
                 f"all-gather: {c}")
        # Gradients are reduced to shards.  XLA:TPU writes that
        # reduce-scatter as collective-permute rings fused into the
        # matmul (windowed einsum), and so does a windowed all-gather, so
        # neither name proves it.  What does: no all-reduce carries a
        # weight-sized gradient (only norm gains and scalars are
        # all-reduced), and a device is handed a quarter of the state.
        layer_shard = 4 * math.prod(sh["wq_shard_shape"][1:])
        if sh["largest_all_reduce_bytes"] >= layer_shard:
            fail(f"sharded step all-reduces {sh['largest_all_reduce_bytes']} "
                 f"bytes at once: a gradient is reduced whole, not to "
                 f"shards (one layer's wq shard is {layer_shard})")
        if sh["argument_bytes_per_device"] * 3.5 > one[
                "argument_bytes_per_device"]:
            fail(f"a device of the sharded step is handed "
                 f"{sh['argument_bytes_per_device']} bytes, the single "
                 f"device {one['argument_bytes_per_device']}: not a quarter")
    return r


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------
def http_stream(port: int, route: str, tokens, new_tokens: int) -> dict:
    """One streaming request over HTTP (chunked JSONL, one token a line)."""
    body = json.dumps({"tokens": tokens, "max_tokens": new_tokens}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}?stream=1&method=stream", data=body,
        headers={"Content-Type": "application/json"})
    out, t0, ttft = [], time.perf_counter(), None
    with urllib.request.urlopen(req, timeout=900) as resp:
        for line in resp:
            item = json.loads(line)
            if "token" not in item:
                fail(f"stream returned {item}")
            if ttft is None:
                ttft = time.perf_counter() - t0
            out.append(item["token"])
    return {"tokens": out, "ttft_s": ttft,
            "total_s": time.perf_counter() - t0}


def http_unary(port: int, route: str, tokens, new_tokens: int) -> dict:
    body = json.dumps({"tokens": tokens, "max_tokens": new_tokens}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}", data=body,
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=900) as resp:
        out = json.load(resp)
    return {"tokens": out["tokens"], "total_s": time.perf_counter() - t0}


def deploy(size: dict, route: str, warmup_prompt) -> dict:
    """serve.run (returns once a replica of this deploy reports ready),
    then one warm-up request: its seconds are this start's compile time."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.controller import get_or_create_controller
    from ray_tpu.serve.llm import LLMDeployment

    t0 = time.perf_counter()
    handle = serve.run(
        serve.deployment(LLMDeployment,
                         ray_actor_options={"num_tpus": 1}).bind(
            size["serve_model"], engine="paged",
            num_slots=size["num_slots"], max_len=size["max_len"],
            seed=SEED),
        name="llm", route_prefix=route, _http=True)
    ready_s = time.perf_counter() - t0
    port = serve.http_port()
    warm = http_stream(port, route, warmup_prompt, size["new_tokens"])
    if len(warm["tokens"]) != size["new_tokens"]:
        fail(f"warm-up returned {len(warm['tokens'])} tokens")
    (replica,) = ray_tpu.get(get_or_create_controller().get_routing.remote(
        "llm"), timeout=30)["replicas"]
    actor = ray_tpu.get_actor(replica)
    return {"handle": handle, "port": port, "replica": replica,
            "actor": actor,
            "pid": ray_tpu.get(actor.getpid.remote(), timeout=30),
            "ready_s": ready_s, "warmup_s": warm["total_s"]}


def serve_phase(size: dict, tiny: bool) -> dict:
    import ray_tpu

    route = "/llm"
    prompts = corpus(size["serve_model"], 5, size["prompt_len"],
                     SEED + 1).tolist()
    first = deploy(size, route, prompts[4])

    with ThreadPoolExecutor(max_workers=2) as pool:   # two in flight
        streams = list(pool.map(
            lambda p: http_stream(first["port"], route, p,
                                  size["new_tokens"]), prompts[:4]))
    unary = http_unary(first["port"], route, prompts[0], size["new_tokens"])
    stats = first["handle"].options(method_name="stats").remote(
        {}).result(timeout=60)
    runtime = first["handle"].options(method_name="runtime_report").remote(
        {}).result(timeout=60)
    rails = ray_tpu.get(first["actor"].stats.remote(),
                        timeout=30).get("rails", {})

    # Redeploy: the new replica takes the chip only after the old one has
    # left it, and must find its programs in the persistent compile cache.
    second = deploy(size, route, prompts[4])
    first["exit_wait_s"] = wait_for_exit(first["pid"])
    runtime2 = second["handle"].options(
        method_name="runtime_report").remote({}).result(timeout=60)

    r = {"model": size["serve_model"], "num_slots": size["num_slots"],
         "max_len": size["max_len"], "prompt_len": size["prompt_len"],
         "new_tokens": size["new_tokens"],
         "why_this_shape": "the issue's 8 slots x 2048: params + pool + "
                           "the widest program's temporaries fit 16 GB "
                           "(tests/test_tpu_compile.py), so nothing shrunk",
         **runtime["device"],
         "ready_s": first["ready_s"], "warmup_s": first["warmup_s"],
         "stream_ttft_s": [s["ttft_s"] for s in streams],
         "stream_total_s": [s["total_s"] for s in streams],
         "stream_tokens": [len(s["tokens"]) for s in streams],
         "unary_total_s": unary["total_s"],
         "streamed_equals_unary": streams[0]["tokens"] == unary["tokens"],
         "rails_used": rails.get("attached_total", 0) >= 1 + len(streams),
         "rails": rails,
         "prefix_hits": stats.get("prefix_hits"),
         "compile_cache_first_start": runtime["compile_cache"],
         "redeploy": {"ready_s": second["ready_s"],
                      "warmup_s": second["warmup_s"],
                      "new_replica": second["replica"] != first["replica"],
                      "old_replica_exit_wait_s": first["exit_wait_s"],
                      "compile_cache": runtime2["compile_cache"],
                      **runtime2["device"]}}
    emit("serve", **r)
    if r["stream_tokens"] != [size["new_tokens"]] * 4:
        fail(f"serve: streams returned {r['stream_tokens']} tokens, want "
             f"{size['new_tokens']} each")
    if not r["streamed_equals_unary"]:
        fail(f"serve: unary greedy tokens {unary['tokens']} != streamed "
             f"{streams[0]['tokens']}")
    if not r["redeploy"]["new_replica"]:
        fail("serve: the redeploy did not start a new replica")
    if (r["compile_cache_first_start"]["written"]
            and not r["redeploy"]["compile_cache"]["hits"]):
        fail(f"serve: the first start wrote "
             f"{r['compile_cache_first_start']} to the compile cache and "
             f"the redeployed replica hit none of it")
    if not tiny and (r["platform"] != "tpu"
                     or r["redeploy"]["platform"] != "tpu"):
        fail(f"serve replica ran on {r['platform']!r}, not a TPU")
    return r


# ---------------------------------------------------------------------------
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: tiny model, small shapes; always "
                         "ends non-zero at the device check")
    args = ap.parse_args()
    size = SIZES["tiny" if args.tiny else "full"]

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.util import compile_cache

    compile_cache.configure()     # exported: every worker inherits it
    # Detection must find the chips itself; the rehearsal has none to
    # find, so it declares what the workers will pretend to own.
    ray_tpu.init(num_tpus=args.chips if args.tiny else None)
    try:
        advertised = ray_tpu.cluster_resources().get("TPU", 0)
        if advertised != args.chips:
            fail(f"the node advertises TPU: {advertised:g}, want "
                 f"{args.chips}: no chip was detected on this host")
        if args.chips == 4:
            reports = [sharded_phase(size)]
        else:
            train = train_phase(size, args.tiny)
            serve_r = serve_phase(size, args.tiny)
            reports = [train, serve_r, serve_r["redeploy"]]
    finally:
        serve.shutdown()
        ray_tpu.shutdown()

    devices = {(r["platform"], r["device_kind"], r["count"]) for r in reports}
    if len(devices) != 1:
        fail(f"workers disagree on the device: {sorted(devices)}")
    (platform, kind, count), = devices
    if platform != "tpu" or count != args.chips:
        fail(f"device check: workers ran on {count} x {platform!r} "
             f"({kind!r}), want {args.chips} x 'tpu'")
    import jax._src.xla_bridge as xla_bridge

    if xla_bridge.backends_are_initialized():
        fail("this process initialised a JAX backend; it would have held "
             "the chip its workers need")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)


if __name__ == "__main__":
    main()
