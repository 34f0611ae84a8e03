"""A train cell: JaxTrainer -> one worker that owns the cell's chips ->
make_train_step on the configuration's mesh, fed by
get_dataset_shard("train").iter_jax_batches(...).  The loop below runs in
that worker; it times itself (only it can block on the device) and
reports once."""
from __future__ import annotations

import math
import tempfile
import time
from typing import Any, Dict

from bench.harness import report, runtime, schedule, spec

TRACE_AT, TRACE_STEPS = 0.4, 3


def step_fns(c: Dict[str, Any], mesh):
    """(init_fn, step_fn) of the configuration `c` on `mesh`: the train
    path's entry point, on whatever the family's `program_config`
    returns."""
    from ray_tpu.models.training import make_train_step

    return make_train_step(spec.family(c).program_config(c), mesh)


def train_loop(config: Dict[str, Any]) -> None:
    import os

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench.harness import device
    from ray_tpu import train
    from ray_tpu.parallel import MeshConfig, build_mesh

    t = {"loop_start": time.time()}
    counter = device.CompileCounter()
    c, traffic = config["config"], config["traffic"]
    seed, seconds = config["seed"], config["seconds"]
    fam = spec.family(c)
    mesh = build_mesh(MeshConfig(**c["mesh"]))
    init_fn, step_fn = step_fns(c, mesh)
    state = init_fn(device.seeded_key(seed))
    jax.block_until_ready(state)
    t["state_ready"] = time.time()
    shard = train.get_dataset_shard("train")
    batch_sharding = NamedSharding(mesh, P(("dp", "fsdp")))

    def batches():
        while True:                          # epochs over the seeded rows
            yield from shard.iter_jax_batches(
                batch_size=traffic["global_batch"], sharding=batch_sharding)

    feed = batches()
    first = next(feed)
    # `correct`: the sharded step's loss on one seeded batch against the
    # plain float32 reference on the same parameters, before any update.
    with jax.default_matmul_precision("highest"):
        rows = jax.device_get(first["tokens"])
        ref_loss = sum(float(fam.row_loss(
            state.params, jnp.asarray(r), c, jit=jax.jit))
            for r in rows) / len(rows)
    t["reference"] = time.time()
    losses, waits, step_s = [], [], []

    def one_step(batch, annotate=False):
        nonlocal state
        t0 = time.perf_counter()
        if annotate:
            with jax.profiler.TraceAnnotation("bench.train.step"):
                state, metrics = step_fn(state, batch)
                loss = float(metrics["loss"])     # blocks until ready
        else:
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
        step_s.append(time.perf_counter() - t0)
        losses.append(loss)

    one_step(first)
    for _ in range(traffic["warmup_steps"] - 1):
        one_step(next(feed))
    warm_losses = list(losses)
    del losses[:], step_s[:]
    t["warmed"] = time.time()
    opened = counter.snapshot()
    profiler = device.ProfilerWindow() if config["trace"] else None
    tracing, traced_steps = False, 0
    t_open_wall, t_open = time.time(), time.perf_counter()
    t_end = t_open
    # The window is [t_open, the end of the last step that started inside
    # `seconds`]: all the work and all the time, cut at a step boundary.
    while time.perf_counter() - t_open < seconds:
        if profiler and not tracing and not traced_steps \
                and time.perf_counter() - t_open >= TRACE_AT * seconds:
            profiler.start()
            tracing = True
        w0 = time.perf_counter()
        if tracing:
            with jax.profiler.TraceAnnotation("bench.train.next_batch"):
                batch = next(feed)
        else:
            batch = next(feed)
        waits.append(time.perf_counter() - w0)
        one_step(batch, annotate=tracing)
        t_end = time.perf_counter()
        if tracing:
            traced_steps += 1
            if traced_steps >= TRACE_STEPS:
                profiler.stop()
                tracing = False
    if tracing:
        profiler.stop()
    closed = counter.snapshot()
    out = {"pid": os.getpid(), "device": device.device_facts(),
           "times": t, "window_open_wall": t_open_wall,
           "window_s": t_end - t_open, "steps": len(losses),
           "losses": losses, "warm_losses": warm_losses,
           "reference_loss": ref_loss, "input_wait_s": waits,
           "step_s": step_s,
           "compile_marks": {"open": opened, "close": closed}}
    if profiler:
        out["trace"] = profiler.reduce(programs=config["programs"])
    train.report(out)


def run(cell: spec.Cell, *, seed: int, seconds: float, traced: bool,
        rehearse: bool, t_start: float) -> int:
    from bench.harness import reference
    from ray_tpu import data as rdata
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    traffic, c = cell.traffic, cell.config
    rows = schedule.train_rows(traffic, seed, c["vocab_size"])
    if rows.shape[0] % traffic["global_batch"]:
        raise spec.SpecError("rows is not a multiple of global_batch")
    runtime.start(cell.chips, rehearse)
    pids = []
    try:
        with tempfile.TemporaryDirectory(prefix="bench_train_") as storage:
            result = JaxTrainer(
                train_loop,
                train_loop_config={"config": c, "traffic": traffic,
                                   "seed": seed, "seconds": seconds,
                                   "trace": traced, "programs": cell.programs()},
                scaling_config=ScalingConfig(
                    num_workers=1, use_tpu=True,
                    resources_per_worker={"TPU": cell.chips}),
                run_config=RunConfig(name="bench", storage_path=storage),
                datasets={"train": rdata.from_numpy(rows, column="tokens")}
            ).fit()
        if result.error is not None:
            raise RuntimeError(f"train worker failed: {result.error}")
        r = result.metrics
        pids.append(r["pid"])
    finally:
        runtime.stop(*pids)

    per_step = traffic["global_batch"] * traffic["seq_len"]
    run_ = {"setup_s": r["window_open_wall"] - t_start,
            "tokens": r["steps"] * per_step, "window_s": r["window_s"]}
    bad = [i for i, x in enumerate(r["losses"]) if not math.isfinite(x)]
    for i in bad:
        report.note("failed_step", step=i, loss=r["losses"][i])
    opened, closed = r["compile_marks"]["open"], r["compile_marks"]["close"]
    window_compiles = {k: closed[k] - opened[k] for k in opened}
    ln_vocab = math.log(c["vocab_size"])
    loss_rel = reference.tolerances(spec.family(c))["LOSS_REL"]
    first = r["warm_losses"][0]
    check = {
        "first_loss": first, "ln_vocab": ln_vocab,
        "reference_loss": r["reference_loss"],
        "loss_rel": abs(first - r["reference_loss"]) / r["reference_loss"],
        "bound": loss_rel,
        "all_finite": not bad and all(math.isfinite(x)
                                      for x in r["warm_losses"])}
    check["ok"] = (check["all_finite"]
                   and abs(first - ln_vocab) <= 0.10 * ln_vocab
                   and check["loss_rel"] <= loss_rel)
    times = r["times"]
    report.note(
        "phases", attempted=r["steps"], failed=len(bad),
        window_s=r["window_s"], window_compiles=window_compiles, check=check,
        setup={"to_loop_start": times["loop_start"] - t_start,
               "state": times["state_ready"] - times["loop_start"],
               "reference": times["reference"] - times["state_ready"],
               "compile_and_warmup": times["warmed"] - times["reference"]},
        step_s_median=sorted(r["step_s"])[len(r["step_s"]) // 2]
        if r["step_s"] else None,
        last_loss=r["losses"][-1] if r["losses"] else None)
    ctx = {"cell": cell, "run": run_, "device": dict(r["device"]),
           "replica": {"input_wait_s": r["input_wait_s"],
                       "step_s": r["step_s"]},
           "trace": r.get("trace")}
    return report.finish(
        cell, traced, ctx, attempted=r["steps"], failed=len(bad),
        correct=check["ok"] and not any(window_compiles.values()))
