"""Ahead-of-time compiles for a described (not attached) TPU v5e.

The chip's compiler is installed wherever libtpu is, so the kernels and
step programs of the train and serve main paths are compiled here at
their real widths without a chip: what Mosaic or XLA:TPU would refuse on
the device (a kernel that cannot be partitioned, a tile that does not
align, a program that does not fit 16 GB) fails in this file first.
Nothing runs, so nothing here is a result or a time.  `chip_smoke.py` is
the run on the chip.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from ray_tpu.models import configs  # noqa: E402
from ray_tpu.ops import attention  # noqa: E402

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 no libtpu on this host
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next run warns and
    recompiles), so the cache is off around every compile here."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


# (B, T, H, Hkv, D) of the attention call inside bench-350m at batch 8 and
# bench-1b4 at batch 4, both at seq 2048 (MHA), and a device's share of the
# `mistral7b-sft-fsdp4` cell's: four query heads a KV head at seq 4096.
FLASH_SHAPES = [(8, 2048, 16, 16, 64), (4, 2048, 16, 16, 128),
                (2, 4096, 32, 8, 128)]
FLASH_IDS = ["350m", "1b4", "mistral-gqa"]


def _flash_args(shape, sharding, batch=None):
    b, t, h, h_kv, d = shape
    b = batch or b
    return (jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=sharding),
            jax.ShapeDtypeStruct((b, t, h_kv, d), jnp.bfloat16,
                                 sharding=sharding))


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=FLASH_IDS)
def test_flash_forward_kernel_compiles(topo, shape):
    q, kv = _flash_args(shape, SingleDeviceSharding(topo.devices[0]))
    fn = jax.jit(lambda q, k, v: attention._flash_pallas(
        q, k, v, causal=True, sm_scale=shape[-1] ** -0.5))
    compiled = fn.lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=FLASH_IDS)
def test_flash_backward_kernels_compile(topo, shape):
    b, t, h, h_kv, _ = shape
    one = SingleDeviceSharding(topo.devices[0])
    q, kv = _flash_args(shape, one)
    lse = jax.ShapeDtypeStruct((b * h_kv, h // h_kv, t), jnp.float32,
                               sharding=one)
    fn = jax.jit(lambda q, k, v, o, lse, g: attention._flash_bwd_pallas(
        q, k, v, o, lse, g, causal=True, sm_scale=shape[-1] ** -0.5))
    text = fn.lower(q, kv, kv, q, lse, q).compile().as_text()
    # dq and dk/dv are separate kernels.
    assert text.count("tpu_custom_call") >= 2


def _fsdp_mesh(topo):
    from ray_tpu.parallel import MeshConfig, build_mesh

    return build_mesh(MeshConfig(fsdp=4), devices=topo.devices)


@pytest.mark.parametrize("shape", [FLASH_SHAPES[0], FLASH_SHAPES[2]],
                         ids=[FLASH_IDS[0], FLASH_IDS[2]])
def test_flash_attention_under_fsdp_mesh_compiles(topo, monkeypatch, shape):
    """The public flash_attention with the batch split over four chips:
    a bare pallas_call under a sharded jit is refused ("Mosaic kernels
    cannot be automatically partitioned"), so the model wraps the call in
    shard_map (`make_sharded_attention`, as `models.transformer.forward`
    does when it is given a mesh).  The kernel must survive into the
    compiled program and no collective may be added for it.  With grouped
    heads K/V reach the kernels at their own head count: nothing in the
    program has a K or V of the query heads' count (the repeat is gone)."""
    from ray_tpu.ops.ring_attention import make_sharded_attention

    # The eligibility check asks jax.default_backend(), which is the CPU
    # here; steer it in the test, not through an option of the program.
    monkeypatch.setattr(attention, "_pallas_eligible", lambda q, k: True)
    mesh = _fsdp_mesh(topo)
    _, t, h, h_kv, d = shape
    q, kv = _flash_args(
        shape, NamedSharding(mesh, P(("dp", "fsdp"), None, None, None)),
        batch=8)
    attn = make_sharded_attention(
        lambda q, k, v: attention.flash_attention(q, k, v, True, None), mesh)

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") >= 3   # fwd, dq, dk/dv
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective
    if h != h_kv:
        # A device's K/V, folded for the kernels, is (2 * h_kv, t, d); a
        # repeat ahead of them would make it (2 * h, t, d).
        assert f"bf16[{2 * h_kv},{t},{d}]" in text
        assert f"bf16[{2 * h},{t},{d}]" not in text


def test_sharded_train_step_compiles_for_four_chips(topo, monkeypatch):
    """`make_train_step` on `MeshConfig(fsdp=4)` — the README's "FSDP by
    changing the mesh" — at bench-350m widths (depth cut: the scan makes
    compile time independent of it).  Kernel in place, parameters and
    Adam moments split, and the collectives XLA derives from the layout:
    parameters all-gathered, gradients reduced back to shards.  XLA:TPU
    writes that reduce-scatter as rings of collective-permutes fused
    into the matmul that produces the gradient (windowed einsum), so the
    literal op name is absent from the optimized text — and a windowed
    all-gather emits the same permutes.  What shows the reduction to
    shards is what `chip_smoke.py --chips 4` checks on the chip: the only
    all-reduces left carry norm gains and scalars, never a weight-sized
    gradient, and the moments leave the step split like they entered."""
    import chip_smoke
    from ray_tpu.models.training import make_train_step

    monkeypatch.setattr(attention, "_pallas_eligible", lambda q, k: True)
    cfg = dataclasses.replace(configs.get("bench-350m"), n_layers=2)
    mesh = _fsdp_mesh(topo)
    init_fn, step_fn = make_train_step(cfg, mesh)
    key = jax.ShapeDtypeStruct(
        (), jax.random.key(0).dtype,
        sharding=NamedSharding(mesh, P()))
    init_c = init_fn.lower(key).compile()
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(init_fn, key), init_c.output_shardings)
    for wq in (state.params["blocks"]["wq"],
               state.opt_state[1][0].mu["blocks"]["wq"]):
        assert wq.sharding.shard_shape(wq.shape)[1] == cfg.d_model // 4
    batch = {"tokens": jax.ShapeDtypeStruct(
        (8, 2049), jnp.int32,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"), None)))}
    compiled = step_fn.lower(state, batch).compile()
    report = chip_smoke._program_report(compiled)
    assert report["tpu_custom_call_in_step"]
    assert report["collectives_in_step"].get("all-gather")
    # Replicated norm gains (d_model f32) are the largest thing
    # all-reduced; one layer's wq shard is 256x that.
    assert 0 < report["largest_all_reduce_bytes"] <= 4 * cfg.d_model
    mu_out = compiled.output_shardings[0].opt_state[1][0].mu["blocks"]["wq"]
    assert mu_out.shard_shape(wq.shape)[1] == cfg.d_model // 4
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(state))
    assert report["argument_bytes_per_device"] < 1.02 * state_bytes / 4
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def _serve_shapes(topo, cfg=None, num_slots=8, max_len=2048, block_size=16):
    from ray_tpu.models import init_params
    from ray_tpu.models.decoding import init_paged_cache

    cfg = cfg or configs.get("bench-1b4")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)))
    num_blocks = num_slots * max_len // block_size + 1
    cache = on_chip(jax.eval_shape(
        lambda: init_paged_cache(cfg, num_blocks, block_size)))
    b_max = max_len // block_size

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    return cfg, params, cache, b_max, arr


def _lower_served(program, cfg, eng, params, cache, arr, width):
    """`paged_decode_burst` at `width` lanes or `paged_prefill_chunk` at
    the configuration's chunk, lowered for the shapes `arr` places."""
    from ray_tpu.models.decoding import make_paged_engine_fns

    b_max = eng["max_len"] // eng["block_size"]
    chunk, burst, _ = make_paged_engine_fns(cfg)
    if program == "paged_decode_burst":
        return burst.lower(
            params, cache, arr((width,), jnp.int32),
            arr((width, b_max), jnp.int32), arr((width,), jnp.int32),
            arr((width,), jnp.bool_), arr((width,), jnp.float32),
            arr((), jax.random.key(0).dtype), n_steps=eng["max_burst"])
    return chunk.lower(
        params, cache, arr((eng["prefill_chunk"],), jnp.int32),
        arr((b_max,), jnp.int32), arr((), jnp.int32), arr((), jnp.int32))


def test_paged_decode_burst_fits_one_chip(topo):
    """bench-1b4 at the smoke's serving shape (8 slots x 2048, block 16,
    8-step burst): compiles for one v5e chip and fits its 16 GB."""
    from ray_tpu.models.decoding import make_paged_engine_fns

    cfg, params, cache, b_max, arr = _serve_shapes(topo)
    _, burst, _ = make_paged_engine_fns(cfg)
    w = 8
    rng = arr((), jax.random.key(0).dtype)
    compiled = burst.lower(
        params, cache, arr((w,), jnp.int32), arr((w, b_max), jnp.int32),
        arr((w,), jnp.int32), arr((w,), jnp.bool_),
        arr((w,), jnp.float32), rng, n_steps=8).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_paged_prefill_chunk_fits_one_chip(topo):
    """The widest prefill tier (128 tokens) at the same serving shape."""
    from ray_tpu.models.decoding import make_paged_engine_fns

    cfg, params, cache, b_max, arr = _serve_shapes(topo)
    chunk, _, _ = make_paged_engine_fns(cfg)
    compiled = chunk.lower(
        params, cache, arr((128,), jnp.int32), arr((b_max,), jnp.int32),
        arr((), jnp.int32), arr((), jnp.int32)).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def _assert_pool_read_by_the_kernel(text, pool_shape, calls: int):
    """`calls` sites of `text` read a K / V pool of `pool_shape` by the
    decode kernel (`ops.attention._paged_decode_kernel`), each handed K
    and V once as they are stored: rows of (position, KV head) x D, a
    bitcast of the pool and no copy of it.  A pool kept as those rows
    (`ops.attention.pages_as_rows`: four dimensions) is handed over as it
    is, and nothing makes an array of its shape but the scatter that
    writes it in place (a `fusion` of that shape roots one)."""
    import re

    n_layers, n_blocks, *page, d = pool_shape
    stored = f"bf16[{n_layers},{n_blocks},{math.prod(page)},{d}]"
    reads = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "paged_decode_attention" in line]
    assert len(reads) == calls, (len(reads), calls)
    for call in reads:
        operands = call.split("operand_layout_constraints=")[1].split(
            "metadata=")[0]
        assert operands.count(stored) == 2, call
    made = r"= " + re.escape(stored) + r"\S* "
    makers = set(re.findall(made + r"([a-z-]+)\(", text))
    if len(page) == 2:
        assert makers <= {"bitcast"}, makers
        return
    assert makers <= {"parameter", "get-tuple-element", "scatter", "fusion",
                      "bitcast"}, makers
    for fused in re.findall(made + r"fusion\(.*?calls=%([\w.]+)", text):
        body = text.split(f"\n%{fused} (")[1].split("\n}")[0]
        assert re.search(r"ROOT %\S+ " + made + r"scatter\(", body), fused


@pytest.mark.parametrize("program", ["paged_decode_burst",
                                     "paged_prefill_chunk"])
def test_served_step_does_not_copy_the_pool(topo, program):
    """Mistral-7B at the benchmark's serving shape (16 slots x 4096, block
    16: a 2.15 GB pool; widest burst, 128-token chunk): the pool is updated
    in place and only live blocks are read, so the program's temporaries
    are its weight relayouts and one group of blocks.  They were 5.84 GB
    (burst) and 2.69 GB (chunk) when the pool's slices went through the
    layer scan and the whole table width was gathered in float32.  In the
    optimised HLO nothing but the in-place scatter makes an array of the
    pool's shape, and no float32 array is as large as one lane's KV
    window (the old body held (S, T, Hkv, rep, D) of them)."""
    import json
    import re

    from bench.harness.spec import BENCH_DIR, transformer_config

    with open(os.path.join(BENCH_DIR, "configs",
                           "mistral-7b-serve-1chip.json")) as f:
        config = json.load(f)
    eng = config["engine"]
    cfg, params, cache, _, arr = _serve_shapes(
        topo, transformer_config(config), eng["num_slots"], eng["max_len"],
        eng["block_size"])
    compiled = _lower_served(program, cfg, eng, params, cache, arr,
                             eng["num_slots"]).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(s.size * s.dtype.itemsize
                     for s in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 10**9, mem.temp_size_in_bytes

    hlo = compiled.as_text()
    pool = "bf16[" + ",".join(map(str, cache.k.shape)) + "]"
    makers = set(re.findall(
        r"= " + re.escape(pool) + r"\S* ([a-z-]+)\(", hlo))
    # `fusion` here is the scatter's own (its root is the `scatter`).
    assert "scatter" in makers
    assert makers <= {"parameter", "get-tuple-element", "scatter", "fusion",
                      "bitcast"}, makers
    # A decode step reads the pool by the kernel (one site: the scan's
    # body), a chunk by the block loop.
    _assert_pool_read_by_the_kernel(
        hlo, cache.k.shape, 1 if program == "paged_decode_burst" else 0)
    window = eng["max_len"] * cfg.n_kv_heads * cfg.head_dim
    largest = max(
        (math.prod(map(int, dims.split(","))), dims)
        for dims in re.findall(r"f32\[([0-9,]+)\]", hlo))
    assert largest[0] < window, largest


@pytest.mark.parametrize("program", ["paged_decode_burst",
                                     "paged_prefill_chunk"])
def test_tensor_parallel_served_step_compiles_for_four_chips(topo, program):
    """What `PagedLLMEngine(mesh=...)` hands the two served programs on
    `MeshConfig(tp=4)`, at Mistral-7B's widths and the benchmark's serving
    shape: parameters laid out by `TP_RULES`, the pool's KV heads over
    `tp`, everything else replicated.  The single-device programs compile
    unchanged: a device holds a quarter of the weights and of the pool,
    the pool leaves the step split as it entered (and is still updated in
    place), and the all-reduces XLA derives carry activations, never a
    weight-sized array."""
    import json

    import chip_smoke
    from bench.harness.spec import BENCH_DIR, transformer_config
    from ray_tpu.models import init_params
    from ray_tpu.models.decoding import (
        init_paged_cache,
        paged_cache_shardings,
    )
    from ray_tpu.models.transformer import param_logical_axes
    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import TP_RULES, param_shardings

    with open(os.path.join(BENCH_DIR, "configs",
                           "mistral-7b-serve-1chip.json")) as f:
        config = json.load(f)
    eng = config["engine"]
    cfg = transformer_config(config)
    mesh = build_mesh(MeshConfig(tp=4, fsdp=1), devices=topo.devices)
    rep = NamedSharding(mesh, P())

    def placed(tree, shardings):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), tree, shardings)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    params = placed(
        jax.eval_shape(lambda: init_params(jax.random.key(0), cfg)),
        param_shardings(param_logical_axes(cfg), mesh, TP_RULES))
    num_blocks = eng["num_slots"] * eng["max_len"] // eng["block_size"] + 1
    cache = placed(
        jax.eval_shape(lambda: init_paged_cache(cfg, num_blocks,
                                                eng["block_size"])),
        paged_cache_shardings(mesh))
    w = eng["num_slots"]
    compiled = _lower_served(program, cfg, eng, params, cache, arr,
                             w).compile()
    pool_out = compiled.output_shardings[0]
    for a, sh in ((cache.k, pool_out.k), (cache.v, pool_out.v)):
        assert sh.shard_shape(a.shape)[3] == cfg.n_kv_heads // 4
    mem = compiled.memory_analysis()
    pool_bytes = sum(s.size * s.dtype.itemsize
                     for s in jax.tree.leaves(cache))
    state_bytes = pool_bytes + sum(s.size * s.dtype.itemsize
                                   for s in jax.tree.leaves(params))
    assert mem.alias_size_in_bytes >= pool_bytes // 4
    assert mem.argument_size_in_bytes < 1.02 * state_bytes / 4
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    report = chip_smoke._program_report(compiled)
    assert report["collectives_in_step"].get("all-reduce")
    # The widest thing reduced is a lane's (or a chunk's) activations or
    # logits; one layer's wq shard is 4096 x 1024 bf16.
    rows = w if program == "paged_decode_burst" else eng["prefill_chunk"]
    assert 0 < report["largest_all_reduce_bytes"] <= (
        4 * rows * max(cfg.d_model, cfg.vocab_size))


def _expert_readers(text, operand):
    """The ops of a compiled program that read a layer's experts where
    they lie (`operand`: the family's `expert_operand`, what the
    benchmark's rooflines look for in an op's text): the fused products
    whose parameter is the stack, sliced inside (a launch that visits, in
    the loop of trips), the calls of the tile kernel
    (`ops.moe._fused_ffn_kernel`, a launch that groups its rows by expert)
    and those of the visit's kernel (`ops.moe._visit_kernel`, a narrow
    launch of experts that fit in VMEM twice), each with the stacks it is
    handed whole.  Returns (products, stacks a tile-kernel call, stacks a
    visit-kernel call)."""
    products = [
        body for body in text.split("\n\n")
        if body.lstrip().startswith("%fused_computation")
        and " convolution(" in body
        and operand.search(body.lstrip().split("\n", 1)[0])]

    def calls(kernel):
        return [
            len(operand.findall(line.split("operand_layout_constraints=")[1]
                                .split("metadata=")[0]))
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and f"%{kernel}." in line.split("=")[0]]

    return products, calls("grouped_expert_ffn"), calls("expert_visit_ffn")


def _assert_experts_read_in_place(text, operand, program, visit_sites=0):
    """A chunk of 64 rows or more groups its rows by expert (a call of the
    tile kernel a call site, the three stacks whole).  A burst visits:
    with `visit_sites` (experts that fit in VMEM twice: `ops.moe.
    _visit_kernel_takes`) as that many calls of the visit's kernel, one a
    call site of a layer, each handed the three stacks whole, and no fused
    product over a stack beside them; else as gate, up and down of a trip,
    fused products over the stack, and no kernel."""
    products, tiles, visits = _expert_readers(text, operand)
    if program.startswith("paged_prefill_chunk"):
        assert tiles and set(tiles) == {3} and not products and not visits, (
            tiles, len(products), visits)
    elif visit_sites:
        assert visits == [3] * visit_sites and not products and not tiles, (
            visits, len(products), tiles)
    else:
        assert len(products) >= 3 and not tiles and not visits, (
            len(products), tiles, visits)


@pytest.mark.parametrize("program", [
    "paged_decode_burst", "paged_prefill_chunk",
    "paged_prefill_chunk granite-4.0-h-small"])
def test_expert_ffn_reads_experts_in_place(topo, program):
    """Mixtral-8x7B at its published widths, depth 2, at the benchmark's
    serving shape: the width-4 burst (the tier `mixtral-chat` decodes at)
    and the chunk fit one v5e chip, and their temporaries stay
    under ONE expert matrix (4096 x 14336 bf16 = 117 MB): no expert's
    weights are copied out before their product and nothing of the
    weights is laid out again.  (A layer's (8, ..) slice taken by the
    layer scan was copied whole, 2.9 GB of temporaries, before
    `moe_mlp_dropless` was handed the stacks of all layers.)  The products
    read the stack itself, sliced inside the fusion: at least one fused
    product has an operand shaped like a layer's experts, one leading
    dimension allowed, which is what the benchmark's `moe_ffn_roofline`
    looks for in an op's text.

    The chunk is lowered at the widest tier the engine launches, 512
    rows (at 128 rows eight experts keep the visit: `ops.moe.
    grouped_tile_rows`), and groups its rows by expert (since PR 46): its
    products are one call of the tile kernel a call site, handed the
    three stacks of all layers whole (the same operand shape, which is
    what `moe_chunk_roofline` looks for), tiles of 256 rows where the
    visit took all 512 through every expert; no fused product reads an
    expert any more, and the temporaries are still under one expert
    matrix.  The third case holds the same at a small expert (granite-
    4.0-h-small's file at depth 2: 36 held experts of three 4096 x 768
    matrices, a 256-row chunk in tiles of 64): nothing a quarter as large
    as one layer's stack of an expert matrix (226 MB) is made."""
    import json
    import re

    from bench.harness import spec
    from bench.harness.spec import BENCH_DIR, transformer_config

    program, _, small = program.partition(" ")
    if small:
        return _small_expert_chunk_groups_its_rows(topo, small)
    with open(os.path.join(BENCH_DIR, "configs",
                           "mixtral-8x7b-serve-1chip.json")) as f:
        config = json.load(f)
    config["num_hidden_layers"] = 2
    eng = config["engine"]
    if program == "paged_prefill_chunk":
        eng = dict(eng, prefill_chunk=512)
    cfg, params, cache, _, arr = _serve_shapes(
        topo, transformer_config(config), eng["num_slots"], eng["max_len"],
        eng["block_size"])
    assert (cfg.n_experts, cfg.d_model, cfg.d_ff) == (8, 4096, 14336)
    compiled = _lower_served(program, cfg, eng, params, cache, arr,
                             4).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    one_expert_matrix = 4096 * 14336 * 2
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < one_expert_matrix, mem.temp_size_in_bytes

    expert_operand = re.compile(
        r"\[(?:\d+,)?8,(?:4096,14336|14336,4096)\]")
    if program == "paged_prefill_chunk":
        from ray_tpu.ops.moe import grouped_tile_rows

        assert grouped_tile_rows(512, cfg.moe) == 256
        assert not grouped_tile_rows(128, cfg.moe)
        products, kernels, _ = _expert_readers(compiled.as_text(),
                                               expert_operand)
        assert spec.family(config).expert_operand(config).pattern \
            == expert_operand.pattern
        # gate, up and down of a tile, in one call; nothing else reads one
        assert kernels == [3] and not products, (kernels, len(products))
        return
    products = [
        body for body in compiled.as_text().split("\n\n")
        if body.lstrip().startswith("%fused_computation")
        and " convolution(" in body
        and expert_operand.search(body.lstrip().split("\n", 1)[0])]
    # gate, up and down of a visit
    assert len(products) >= 3, len(products)


def _small_expert_chunk_groups_its_rows(topo, name):
    import json

    from bench.harness import spec
    from ray_tpu.ops.moe import grouped_tile_rows

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           f"{name}-serve-1chip.json")) as f:
        config = json.load(f)
    config["num_hidden_layers"] = 2
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    fam = spec.family(config)
    _, programs = fam.serve_programs(config, place)
    (lowered,) = [low for name, low in programs
                  if name.startswith("paged_prefill_chunk")]
    cfg = fam.program_config(config)
    assert cfg.moe.held == (0, 36) and grouped_tile_rows(256, cfg.moe) == 64
    compiled = lowered.compile()
    text = compiled.as_text()
    products, kernels, visits = _expert_readers(
        text, fam.expert_operand(config))
    assert kernels and set(kernels) == {3} and not products and not visits, (
        kernels, visits)
    expert_matrix_stack = 36 * 4096 * 768 * 2
    assert compiled.memory_analysis().temp_size_in_bytes \
        < expert_matrix_stack / 4


@pytest.mark.parametrize("program", ["paged_decode_burst",
                                     "paged_prefill_chunk"])
def test_hybrid_served_programs_fit_one_chip(topo, program):
    """Phi-4-mini-flash-reasoning whole (32 layers, 200,064 tokens) at the
    benchmark's serving shape (32 slots x 8192, block 16: layer 17's pool
    1.34 GB, eight rings of 640 rows a slot 0.87 GB, recurrent state 0.11
    GB beside 7.71 GB of weights): the widest burst and the 128-token
    chunk compile for one v5e chip and their live bytes fit its 15.75 GB
    usable.  Pool, rings and state are updated in place (their bytes are
    aliased), and neither program holds a copy of the pool or of a ring
    among its temporaries: with a trailing dimension of 64 the compiler
    laid them out otherwise and copied all of them, 5.7 GB of temporaries
    a burst and 8.5 GB a chunk, which did not fit."""
    import json

    from bench.harness import spec

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "phi4-mini-flash-serve-1chip.json")) as f:
        config = json.load(f)
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    resident, programs = spec.family(config).serve_programs(config, place)
    (lowered,) = [low for name, low in programs if name.startswith(program)]
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(resident["sequence_state"]))
    resident_bytes = state_bytes + sum(
        s.size * s.dtype.itemsize
        for s in jax.tree.leaves(resident["params"]))
    assert abs(resident_bytes - 10.0e9) < 1.0e9, resident_bytes
    assert mem.alias_size_in_bytes >= state_bytes
    assert _device_bytes(compiled) < 15.75e9
    assert mem.temp_size_in_bytes < 1.2e9, mem.temp_size_in_bytes


@pytest.mark.parametrize("program", ["paged_decode_burst",
                                     "paged_prefill_chunk"])
def test_window_moe_served_programs_fit_one_chip(topo, program):
    """Mellum2-12B-A2.5B at the benchmark's cut (8 of 28 layers: six
    window layers, two full) and serving shape (32 slots x 8192, block 16:
    two layers' pool 1.07 GB, six rings of 1152 rows a slot 0.47 GB beside
    7.59 GB of weights): the width-32 burst and the 128-token chunk
    compile for one v5e chip and fit its 15.75 GB usable.  Pool and rings
    are updated in place (their bytes are aliased), and the temporaries
    together stay under one ring array (234 MB), which is less than the
    pool (537 MB a side) and than one matrix of a layer's expert stack
    (264 MB): nothing of the three is copied whole.  (The layers of a
    period index the weight stacks themselves: with a period's slice taken
    by the scan the burst held 301 MB.)  The expert products read the
    stacks in place, as Mixtral's: the chunk's tiles, and the burst's
    trips as the grid of the visit's kernel (since PR 63: an expert's
    12.4 MB fit in VMEM twice), one call a layer of the period, handed
    the three stacks whole."""
    import json
    import re

    from bench.harness import spec

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "mellum2-12b-serve-1chip.json")) as f:
        config = json.load(f)
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    fam = spec.family(config)
    resident, programs = fam.serve_programs(config, place)
    (lowered,) = [low for name, low in programs if name.startswith(program)]
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    state = resident["sequence_state"]
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(state))
    resident_bytes = state_bytes + sum(
        s.size * s.dtype.itemsize
        for s in jax.tree.leaves(resident["params"]))
    assert state.k.shape == (2, 16385, 16, 4, 128)
    assert state.wk.shape == (6, 33, 1152, 4, 128)
    assert abs(resident_bytes - 9.13e9) < 0.01e9, resident_bytes
    assert mem.alias_size_in_bytes >= state_bytes
    assert _device_bytes(compiled) < 15.75e9
    ring_array = state.wk.size * 2
    assert mem.temp_size_in_bytes < ring_array, mem.temp_size_in_bytes
    # (the burst: one call of the visit's kernel a layer of the period)
    _assert_experts_read_in_place(compiled.as_text(),
                                  fam.expert_operand(config), program,
                                  visit_sites=4)
    # the burst's full layer reads the pool by the kernel (one site in
    # the scan's body: 32 query heads over 4), the chunk by the block loop
    _assert_pool_read_by_the_kernel(
        compiled.as_text(), state.k.shape,
        1 if program == "paged_decode_burst" else 0)
    ring_ops = re.findall(r"= bf16\[[0-9,]*1152,4,128\]", compiled.as_text())
    assert ring_ops and fam.ring_operand(config).search(ring_ops[0])


@pytest.mark.parametrize("program", ["paged_decode_burst",
                                     "paged_prefill_chunk"])
def test_mamba2_moe_served_programs_fit_one_chip(topo, program):
    """granite-4.0-h-small at the benchmark's cut (one period of ten
    layers: nine Mamba-2, one attention; 36 of 72 experts, half the
    vocabulary) and serving shape (16 slots x 8192, block 16: one layer's
    pool 0.54 GB, nine layers' state 0.65 GB beside 9.51 GB of weights):
    the width-16 burst and the 256-token chunk compile for one v5e chip
    and fit its 15.75 GB usable.  Pool and state are updated in place
    (their bytes are aliased) and the temporaries together stay under the
    state array (0.64 GB) and under one layer's expert matrix stack (226
    MB): nothing of the two is copied whole.  (Stored heads first, every
    slot's state was copied into the compiler's layout and back a chunk,
    0.65 GB of temporaries; stored as one (H * P, N) matrix the burst's
    gather split all of it in four a layer, 0.71 GB.)  The expert products
    read the held stacks in place (the burst's in the loop of trips: 18.9
    MB an expert is over `ops.moe._VISIT_EXPERT_BYTES`).  **The chunk is not a scan over
    positions**: none of its loops lies in the `ssd` scope (they are the
    runs of layers, the expert visits and the paged attention's groups),
    and the chunk's decay matrix [H, Q, Q] is there."""
    import json
    import re

    from bench.harness import spec

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "granite-4.0-h-small-serve-1chip.json")) as f:
        config = json.load(f)
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    fam = spec.family(config)
    resident, programs = fam.serve_programs(config, place)
    (lowered,) = [low for name, low in programs if name.startswith(program)]
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    state = resident["sequence_state"]
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(state))
    resident_bytes = state_bytes + sum(
        s.size * s.dtype.itemsize
        for s in jax.tree.leaves(resident["params"]))
    assert state.k.shape == (1, 8193, 16, 8, 128)
    assert state.h.shape == (9, 17, 64, 128, 128)
    assert state.conv.shape == (9, 17, 3, 8448)
    assert abs(resident_bytes - 10.70e9) < 0.01e9, resident_bytes
    assert mem.alias_size_in_bytes >= state_bytes
    assert _device_bytes(compiled) < 15.75e9
    expert_stack = 36 * 4096 * 768 * 2
    assert mem.temp_size_in_bytes < expert_stack, mem.temp_size_in_bytes
    text = compiled.as_text()
    _assert_experts_read_in_place(text, fam.expert_operand(config), program)
    # the burst's one attention layer reads the pool by the kernel (its
    # own scale handed over), the chunk by the block loop
    _assert_pool_read_by_the_kernel(
        text, state.k.shape, 1 if program == "paged_decode_burst" else 0)
    loops = re.findall(r' while\(.*?op_name="([^"]*)"', text)
    assert loops and not [name for name in loops if "/ssd" in name], loops
    if program == "paged_prefill_chunk":
        assert re.search(r"\[128,256,256\]", text)
        assert fam.scan_operand(config).search(text)
    else:
        assert fam.state_operand(config).search(text)


def test_mamba2_moe_launch_of_two_chunks_copies_no_state(topo):
    """The same configuration's widest prefill launch, 512 rows = two
    chunks of 256 (`models/mamba2_moe.py:_mamba2`): it compiles for one
    v5e chip, its decay matrix is a chunk's ([H, 256, 256], not the
    launch's), nothing in the `ssd` scope is a loop, and the slots'
    state is still updated in place: the temporaries stay under a tenth
    of the state array (0.64 GB), where the sub-chunks as a lowered loop
    held 0.69 GB, a copy of all of it in the carry's layout."""
    import json
    import re

    from bench.harness import spec
    from ray_tpu.models.decoding import make_paged_engine_fns

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "granite-4.0-h-small-serve-1chip.json")) as f:
        config = json.load(f)
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    def arr(shape, dtype):
        return place(jax.ShapeDtypeStruct(shape, dtype))

    fam, eng = spec.family(config), config["engine"]
    resident, _ = fam.serve_programs(config, place)
    params, state = resident["params"], resident["sequence_state"]
    assert state.chunk == eng["prefill_chunk"] == config["mamba_chunk_size"]
    chunk_fn, _, _ = make_paged_engine_fns(fam.program_config(config))
    compiled = chunk_fn.lower(
        params, state, arr((2 * state.chunk,), jnp.int32),
        arr((eng["max_len"] // eng["block_size"],), jnp.int32),
        arr((), jnp.int32), arr((), jnp.int32),
        slot=arr((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes
    assert _device_bytes(compiled) < 15.75e9
    assert mem.temp_size_in_bytes < 0.064e9, mem.temp_size_in_bytes
    text = compiled.as_text()
    loops = re.findall(r' while\(.*?op_name="([^"]*)"', text)
    assert loops and not [name for name in loops if "/ssd" in name], loops
    assert re.search(r"\[128,256,256\]", text)
    assert not re.search(r"\[128,512,512\]", text)
    assert fam.scan_operand(config).search(text)


@pytest.mark.parametrize("program", ["paged_decode_burst",
                                     "paged_prefill_chunk c=512"])
def test_mla_moe_served_programs_fit_one_chip(topo, program):
    """GLM-4.7-Flash at the benchmark's cut (12 of 47 layers: the dense
    first layer and 11 expert layers; 32 of 64 experts, half the
    vocabulary) and serving shape (8 slots x 16,384, block 16: a latent
    pool of 8,193 blocks x 16 rows of 640 = 2.01 GB beside 8.14 GB of
    weights): the width-8 burst and the widest chunk tier (512 rows)
    compile for one v5e chip and fit its 15.75 GB usable.  **No program
    copies the pool whole into another layout**: the pool's bytes are
    aliased in and out, the temporaries stay under a quarter of one
    layer's pool (33 MB in the burst, 1.4 MB in the chunk), and in the optimised HLO nothing but the in-place scatter makes
    an array of the pool's shape.  (With flat rows of 576 the same
    programs hold a 2.02 GB temporary: the pool re-laid in whole tiles
    around every step; `models/mla_moe.py` has the numbers.)  The expert
    products read the held stacks in place, and the ops that read the
    pool show the stored row as the family's `latent_operand` says (the
    burst's in the loop of trips: with the visit's kernel, which 18.9 MB
    an expert would fit but `ops.moe._VISIT_EXPERT_BYTES` keeps from it,
    the burst's temporaries were 127 MB, the 94 MB of every layer's `wq_b`
    laid out once a burst no longer kept in VMEM beside the kernel's 42
    MiB: PR 63).  The
    burst's latent read is this repo's Pallas kernel (a `tpu_custom_call`
    for layer 0 and one in the scan's body, each handed the pool once, as
    it is stored), which Mosaic compiles here: the program takes the
    kernel or the loop by the platform it is lowered for, so this host's
    CPU has no say."""
    import json
    import re

    from bench.harness import spec

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "glm-4.7-flash-serve-1chip.json")) as f:
        config = json.load(f)
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    fam = spec.family(config)
    resident, programs = fam.serve_programs(config, place)
    (lowered,) = [low for name, low in programs if name.startswith(program)]
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    state = resident["sequence_state"]
    assert state.kv.shape == (12, 8193, 16, 640)
    # the burst reads the pool by the kernel (layer 0's and the scan's),
    # the chunk by the block loop
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "grouped_expert_ffn" not in line]
    # (the chunk's one other kernel is the experts' tiles: PR 46)
    assert len(calls) == text.count("tpu_custom_call") - (
        program != "paged_decode_burst") == (
        2 if program == "paged_decode_burst" else 0)
    state_bytes = state.kv.size * 2
    resident_bytes = state_bytes + sum(
        s.size * s.dtype.itemsize
        for s in jax.tree.leaves(resident["params"]))
    assert abs(resident_bytes - 10.15e9) < 0.01e9, resident_bytes
    assert mem.alias_size_in_bytes >= state_bytes
    assert _device_bytes(compiled) < 15.75e9
    assert mem.temp_size_in_bytes < state_bytes / 12 / 4, \
        mem.temp_size_in_bytes
    pool = "bf16[" + ",".join(map(str, state.kv.shape)) + "]"
    makers = set(re.findall(r"= " + re.escape(pool) + r"\S* ([a-z-]+)\(",
                            text))
    assert "scatter" in makers or "fusion" in makers, makers
    assert makers <= {"parameter", "get-tuple-element", "scatter", "fusion",
                      "bitcast", "while", "dynamic-update-slice"}, makers
    assert fam.latent_operand(config).search(text)
    # the kernel is handed the pool as it is stored, once (the borrowed
    # kernel took it twice, as keys and as values, every layer's pages in
    # one row), and nothing holds the pool's bytes in another shape
    for call in calls:
        operands = call.split("operand_layout_constraints=")[1].split(
            "metadata=")[0]
        assert re.findall(rf"bf16\[(?:\d+,){{3}}{state.kv.shape[-1]}\]",
                          operands) == [pool], call
    every_page = state.kv.shape[0] * state.kv.shape[1]
    assert not re.search(rf"bf16\[(?:\d+,)*{every_page}[,\]]", text)
    _assert_experts_read_in_place(text, fam.expert_operand(config), program)


@pytest.mark.parametrize("program", ["paged_decode_burst",
                                     "paged_prefill_chunk c=512"])
def test_mhc_mla_served_programs_fit_one_chip(topo, program):
    """Xing4.0-29B-A4B at the benchmark's cut (7 of 40 layers: one dense
    layer and six expert layers, all 64 experts, the whole vocabulary) and
    serving shape (8 slots x 8,192, block 16: a latent pool of 4,097
    blocks x 16 rows of 640 = 0.59 GB beside 11.08 GB of weights): the
    width-8 burst and the widest chunk tier (512 rows) compile for one
    v5e chip and fit its 15.75 GB usable.  **The four streams' mixing is
    this repo's three Pallas kernels** (`hc_pre`, `hc_sinkhorn`,
    `hc_post`: a `tpu_custom_call` of each for the leading layer's two
    mixes and for the two of the scan's body), which Mosaic compiles
    here at the published width (a row's 4 x 3,584 values laid flat), and
    every op that touches a row's streams shows them as the family's
    `hc_operand` says; no mix leaves a loop of rounds in the program."""
    import json

    from bench.harness import spec

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "xing4.0-29b-a4b-serve-1chip.json")) as f:
        config = json.load(f)
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    fam = spec.family(config)
    resident, programs = fam.serve_programs(config, place)
    (lowered,) = [low for name, low in programs if name.startswith(program)]
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    state = resident["sequence_state"]
    assert state.kv.shape == (7, 4097, 16, 640)
    state_bytes = state.kv.size * 2
    resident_bytes = state_bytes + sum(
        s.size * s.dtype.itemsize
        for s in jax.tree.leaves(resident["params"]))
    assert abs(resident_bytes - 11.67e9) < 0.01e9, resident_bytes
    assert mem.alias_size_in_bytes >= state_bytes
    assert _device_bytes(compiled) < 15.75e9
    assert mem.temp_size_in_bytes < 0.25e9, mem.temp_size_in_bytes
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in ("hc_pre", "hc_sinkhorn", "hc_post"):
        mine = [c for c in calls if f"%{kernel}." in c.split("=")[0]]
        assert len(mine) == 4, (kernel, len(mine))
    streams = fam.hc_operand(config)
    assert all(streams.search(c) for c in calls
               if "%hc_pre." in c.split("=")[0]
               or "%hc_post." in c.split("=")[0])
    # no loop runs the 20 Sinkhorn rounds: they are the kernel's
    assert '"known_trip_count":{"n":"20"}' not in text
    _assert_experts_read_in_place(text, fam.expert_operand(config), program)


@pytest.mark.parametrize("program", ["paged_decode_burst",
                                     "paged_prefill_chunk"])
def test_gated_moe_served_programs_fit_one_chip(topo, program):
    """Laguna-XS.2 at the benchmark's cut (layer 0 and two periods: three
    full layers of 48 query heads, six window layers of 64, over 8 KV
    heads; 128 of 256 experts; half the vocabulary) and serving shape (8
    slots x 16384, block 16: three layers' pool 1.61 GB, six rings of
    512 + `prefill_chunk` rows a slot beside 7.64 GB of weights): the
    width-8 burst and the chunk compile for one v5e chip, groups of 6 and
    of 8 query heads a KV head in one program, and fit its 15.75 GB
    usable.  Pool and rings are updated in place (their bytes are
    aliased) and the temporaries stay under one side of the pool (805
    MB): nothing of pool, rings or a layer's expert stack is copied
    whole.  The expert products read the held stacks in place, as
    Mellum's and GLM's (the burst's four calls of the visit's kernel
    among them, since PR 63: 6.3 MB an expert)."""
    import json
    import re

    from bench.harness import spec

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "laguna-xs.2-serve-1chip.json")) as f:
        config = json.load(f)
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    fam = spec.family(config)
    resident, programs = fam.serve_programs(config, place)
    (lowered,) = [low for name, low in programs if name.startswith(program)]
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    state, params = resident["sequence_state"], resident["params"]
    rows = 512 + config["engine"]["prefill_chunk"]
    assert state.k.shape == (3, 8193, 16, 8, 128)
    assert state.wk.shape == (6, 9, rows, 8, 128)
    assert params["kinds"]["full"]["wq"].shape == (2, 2048, 48 * 128)
    assert params["kinds"]["window"]["wq"].shape == (6, 2048, 64 * 128)
    assert params["lead"][0]["w_gate"].shape == (2048, 8192)
    assert params["blocks"]["w_gate"].shape == (8, 128, 2048, 512)
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(state))
    resident_bytes = state_bytes + sum(
        s.size * s.dtype.itemsize for s in jax.tree.leaves(params))
    assert abs(resident_bytes - 9.40e9) < 0.1e9, resident_bytes
    assert resident_bytes > 0.25 * V5E_HBM_BYTES
    assert mem.alias_size_in_bytes >= state_bytes
    assert _device_bytes(compiled) < 15.75e9
    assert mem.temp_size_in_bytes < state.k.size * 2, mem.temp_size_in_bytes
    # the burst reads the pool by the kernel (layer 0's site and the
    # scan's, 48 query heads over 8), the chunk by the block loop
    _assert_pool_read_by_the_kernel(
        text, state.k.shape, 2 if program == "paged_decode_burst" else 0)
    # (the burst: one call of the visit's kernel a layer of the period)
    _assert_experts_read_in_place(text, fam.expert_operand(config), program,
                                  visit_sites=4)
    ring_ops = re.findall(rf"= bf16\[[0-9,]*{rows},8,128\]", text)
    assert ring_ops and fam.ring_operand(config).search(ring_ops[0])


@pytest.mark.parametrize("program", ["paged_decode_burst",
                                     "paged_prefill_chunk"])
def test_dsa_moe_served_programs_fit_one_chip(topo, program):
    """dots3-note-prev at the benchmark's cut (layer 0 and one period:
    two full layers of 128 heads with the indexer, three window layers of
    64; 32 of 256 experts; an eighth of the vocabulary) and serving shape
    (8 slots x 32,768, block 16: the full layers' latent rows 0.67 GB and
    their index keys 0.13 GB pooled by one table of 2,048 entries a lane,
    three rings of 1,040 rows of 1,152 a slot, beside 8.17 GB of
    weights): the width-8 burst and the 512-row chunk compile for one
    v5e chip and fit its 15.75 GB usable.  The three leaves are updated in
    place (their bytes are aliased), the temporaries stay under 1 GB (the
    chunk's fetch, kept for what the kernel does not reach, takes its
    selected rows 128 query rows at a time: 0.34 GB a buffer, where all
    512 at once would be 1.3 GB a layer; 0.986 GB in all with the index
    scores kept for the kernel's mask, 0.91 before it; the search for a
    set's least score, since PR 51, compares the scores where they lie and
    keeps no second array of them: 0.980 -> 0.986; the burst 0.41), and
    the ops that read
    index keys, selected rows and rings show the shapes the family's
    `index_operand`, `attn_operand`, `ring_operand` and `select_operand`
    say, so that the traced run's readers find them.  **The chunk's
    selected read is this repo's Pallas kernel** (since PR 50: a
    `tpu_custom_call` for layer 0 and one in the scan's body, each handed
    the pool laid flat, which is what `attn_operand` and `select_operand`
    look for, within the 600 characters of an op's text that a profile
    keeps), under a branch whose other side is the fetch **and, since
    PR 51, the only sorts of index scores the chunk has**; **the burst's
    is, since PR 60, its own form of that kernel** (`masked_decode_
    attention`: one query row a lane, at the same two call sites, handed
    the pool laid flat the same way), its sorts all on the other side of
    its branch too."""
    import json
    import re

    from bench.harness import spec

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "dots3-note-prev-serve-1chip.json")) as f:
        config = json.load(f)
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    fam = spec.family(config)
    resident, programs = fam.serve_programs(config, place)
    (lowered,) = [low for name, low in programs if name.startswith(program)]
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    state, params = resident["sequence_state"], resident["params"]
    assert state.kv.shape == (2, 16385, 16, 640)
    assert state.idx.shape == (2, 16385, 16, 128)
    assert state.ring.shape == (3, 9, 1040, 1152)
    assert params["attn"]["wq_b"].shape == (2, 1024, 128 * 192)
    assert params["attn"]["wq_idx"].shape == (2, 1024, 64 * 128)
    assert params["attn_window"]["wq_b"].shape == (3, 1024, 64 * 256)
    assert params["attn_window"]["w_uk"].shape == (3, 64, 192, 1024)
    assert params["ffn"]["w_gate"].shape == (4, 32, 5120, 1536)
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(state))
    resident_bytes = state_bytes + sum(
        s.size * s.dtype.itemsize for s in jax.tree.leaves(params))
    assert abs(resident_bytes - 9.04e9) < 0.05e9, resident_bytes
    assert resident_bytes > 0.25 * V5E_HBM_BYTES
    assert mem.alias_size_in_bytes >= state_bytes
    assert _device_bytes(compiled) < 15.75e9
    assert mem.temp_size_in_bytes < 1.0e9, mem.temp_size_in_bytes
    _assert_experts_read_in_place(text, fam.expert_operand(config), program)
    for operand in (fam.index_operand, fam.attn_operand, fam.ring_operand,
                    fam.select_operand):
        assert operand(config).search(text), operand.__name__
    kernel = "masked_latent_attention" \
        if program == "paged_prefill_chunk" else "masked_decode_attention"
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "masked_" in line]
    assert len(calls) == 2 and all(kernel in call for call in calls)
    for call in calls:
        seen_by_a_profile = call.strip()[:600]
        assert "bf16[2,262160,640]" in seen_by_a_profile, call
        assert fam.attn_operand(config).search(seen_by_a_profile)
        assert fam.select_operand(config).search(seen_by_a_profile)
    # the fetch stands beside the kernel (a longer lane, a tie the mask
    # cannot settle): the gathered buffer is still a shape of the chunk
    if program == "paged_prefill_chunk":
        assert "bf16[128,2048,640]" in text
        assert mem.temp_size_in_bytes > 0.3e9, mem.temp_size_in_bytes
    else:
        assert "bf16[8,2048,640]" in text          # the fetch beside it
    # and every sort of index scores stands on that side of the branch
    # (the platform's branch, then `_attend_masked`'s): a launch or a
    # burst that reads the mask executes none
    sorts = [line for line in text.splitlines()
             if re.search(r"\bsort\(", line) and "dsa_select" in line]
    assert sorts and all(
        re.search(r"dsa_attend/(cond/branch_\d_fun/){2}jit\(_fetch_best\)/"
                  r"dsa_select", line)
        for line in sorts), sorts[:2]


@pytest.mark.parametrize("lanes", [4, 8])
@pytest.mark.parametrize("layers,entries", [(5, 1024), (2, 2048)],
                         ids=["dsv32", "dots3"])
def test_the_masked_decode_kernel_compiles(topo, lanes, layers, entries):
    """`_masked_decode_kernel` alone at DeepSeek-V3.2-Exp's and
    dots3-note-prev's widths (128 heads over rows of 640 bfloat16, 512 of
    them the value; the benchmark's pools: 5 layers under tables of 1,024
    entries, 2 under 2,048) for a burst of 4 and of 8 lanes: Mosaic takes
    the lanes' grid, the flat pool's page copies and the blocked scores,
    and the call shows the pool laid flat within what a profile keeps of
    an op's text."""
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    blocks = 8 * entries + 1
    compiled = jax.jit(functools.partial(
        attention._masked_decode_kernel, d_v=512, scale=0.1)).lower(
        arr((lanes, 1, 128, 640), jnp.bfloat16),
        arr((layers, blocks, 16, 640), jnp.bfloat16), arr((), jnp.int32),
        arr((lanes, entries), jnp.int32), arr((lanes,), jnp.int32),
        arr((lanes, 1, entries * 16), jnp.float32),
        arr((lanes, 1), jnp.float32), arr((lanes, 1), jnp.int32)).compile()
    (call,) = [line for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert "masked_decode_attention" in call
    assert f"bf16[{layers},{blocks * 16},640]" in call.strip()[:600]
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("program", ["paged_denoise_burst",
                                     "paged_prefill_chunk"])
def test_block_diffusion_served_programs_fit_one_chip(topo, program):
    """SDAR-30B-A3B-Chat at the benchmark's cut (6 of 48 layers, all 128
    experts top-8, the whole 151,936-row vocabulary) and serving shape (8
    slots x 4,096, block 16: a pool of 0.40 GB beside 8.72 GB of weights):
    the width-8 burst of two blocks (a scan of two denoising passes and a
    commit, a scan of those over the blocks) and the 512-row chunk under
    the block mask compile for one v5e chip and fit its 15.75 GB usable.
    The pool is updated in place through both scans (its bytes are
    aliased), the temporaries stay under the pool's own size, **a pass of
    32 rows groups its rows by expert as a chunk does**
    (`MoEConfig.grouped_from_rows` = 16: a call of the tile kernel at each
    of the burst's two call sites, the denoising scan's and the commit's,
    the three stacks whole; no visit), a pass's attention is the block
    loop (four rows a lane: no decode kernel), and the ops of the
    selection show the vocabulary as the family's `select_operand` says."""
    import json

    from bench.harness import spec

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "sdar-30b-a3b-serve-1chip.json")) as f:
        config = json.load(f)
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    fam = spec.family(config)
    resident, programs = fam.serve_programs(config, place)
    (lowered,) = [low for name, low in programs if name.startswith(program)]
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    state, params = resident["sequence_state"], resident["params"]
    assert state.k.shape == (6, 2049, 16, 4, 128)
    assert params["blocks"]["w_gate"].shape == (6, 128, 2048, 768)
    assert params["lm_head"].shape == (2048, 151936)
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(state))
    resident_bytes = state_bytes + sum(
        s.size * s.dtype.itemsize for s in jax.tree.leaves(params))
    assert abs(resident_bytes - 9.125e9) < 0.01e9, resident_bytes
    assert resident_bytes > 0.25 * V5E_HBM_BYTES
    assert mem.alias_size_in_bytes >= state_bytes
    assert _device_bytes(compiled) < 15.75e9
    assert mem.temp_size_in_bytes < state_bytes, mem.temp_size_in_bytes
    # both programs read their experts as a chunk does
    _assert_experts_read_in_place(text, fam.expert_operand(config),
                                  "paged_prefill_chunk")
    _assert_pool_read_by_the_kernel(text, state.k.shape, 0)
    if program == "paged_denoise_burst":
        assert len(_expert_readers(text, fam.expert_operand(config))[1]) == 2
        assert fam.select_operand(config).search(text)
        assert text.count("denoise_select") and text.count("block_commit")


@pytest.mark.parametrize("program", ["paged_decode_burst",
                                     "paged_prefill_chunk"])
def test_gated_delta_served_programs_fit_one_chip(topo, program):
    """Qwen3-Next-80B-A3B at the benchmark's cut (two periods of three
    linear layers to one full layer; 128 of 512 experts; a quarter of the
    vocabulary) and serving shape (8 slots x 16384, block 16: two layers'
    pool 0.54 GB, six linear layers' float32 state and conv rows for 9
    slots 0.12 GB, beside 7.33 GB of weights): the width-8 burst and the
    chunk of 512 rows (eight chunks of the delta rule, the state handed on
    in an unrolled scan) compile for one v5e chip and fit its 15.75 GB
    usable.  Pool, state and conv rows are updated in place (their bytes
    are aliased).  **The full layers' pool has 2 KV heads of 256 and is
    kept as the decode kernel reads it**, rows of (position, KV head),
    bf16[2,8193,32,256] in whole (16, 128) tiles
    (`ops.attention.pages_as_rows`; kept by position, (16, 2, 256), the
    compiler stores it in tiles of (2, 128) and the kernel's view was a
    copy of a layer's whole pool a step, 0.27 GB for K and again for V:
    1.49 s of a 3 s trace on the chip, PR 64): the burst reads it by the
    kernel, one call site in the scan over the periods, K and V handed
    over as stored; nothing of the pool's shape is made but by the
    in-place scatter, in the burst and in the chunk, which reads groups
    of pages by the loop (no kernel call), and the temporaries stay under
    one pool.  The expert products read the held stacks in place, as
    Laguna's (same expert, 6.3 MB)."""
    import json
    import re

    from bench.harness import spec

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "qwen3-next-80b-a3b-serve-1chip.json")) as f:
        config = json.load(f)
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    fam = spec.family(config)
    resident, programs = fam.serve_programs(config, place)
    (lowered,) = [low for name, low in programs if name.startswith(program)]
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    state, params = resident["sequence_state"], resident["params"]
    assert state.k.shape == (2, 8193, 32, 256) and state.wk is None
    assert state.lstate.shape == (6, 9, 32, 128, 128)
    assert state.lstate.dtype == jnp.float32
    assert state.lconv.shape == (6, 9, 3, 8192)
    assert params["kinds"]["full"]["wq"].shape == (2, 2048, 16 * 256)
    assert params["kinds"]["full"]["head_gate"].shape == (2, 2048, 16 * 256)
    assert params["kinds"]["linear"]["in_qkvz"].shape == (6, 2048, 12288)
    assert params["blocks"]["w_gate"].shape == (8, 128, 2048, 512)
    assert params["lm_head"].shape == (2048, 37984)
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(state))
    resident_bytes = state_bytes + sum(
        s.size * s.dtype.itemsize for s in jax.tree.leaves(params))
    assert abs(resident_bytes - 7.99e9) < 0.1e9, resident_bytes
    assert resident_bytes > 0.25 * V5E_HBM_BYTES
    assert mem.alias_size_in_bytes >= state_bytes
    assert _device_bytes(compiled) < 15.75e9
    assert mem.temp_size_in_bytes < state.k.size * 2, mem.temp_size_in_bytes
    _assert_pool_read_by_the_kernel(
        text, state.k.shape, 1 if program == "paged_decode_burst" else 0)
    _assert_experts_read_in_place(text, fam.expert_operand(config), program,
                                  visit_sites=4)
    # the slots' state as rows (the burst) or the slot's (the chunk) is there
    # in float32, and the rule's triangular system in the chunk alone
    assert fam.state_operand(config).search(text) \
        if program == "paged_decode_burst" \
        else re.search(r"f32\[1,8,32,64,64\]", text)
    if program == "paged_decode_burst":
        _assert_state_stepped_in_place(text, state.lstate.shape,
                                       fam.state_operand(config), calls=3)


def _assert_state_stepped_in_place(text, lstate_shape, state_operand,
                                   calls: int):
    """`calls` sites of `text` (one a linear layer of the period's body) run
    the delta rule's step as the kernel of `ops.gated_delta._step_kernel`
    on the slots' states viewed as rows, `f32[layers x slots, Hv, dk, dv]`,
    the call's first result: what the benchmark's `ssm_state_roofline`
    looks for (`state_operand`) in the 600 characters a trace keeps of an
    op's text.  Nothing makes an array of the slots' states, in either
    shape, but a bitcast between the two: no gather, no scatter, no copy,
    inside or around the scans over steps and periods."""
    import re

    layers, slots, *head = lstate_shape
    shape = ",".join(map(str, head))
    steps = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "gated_delta_step_rows" in line]
    assert len(steps) == calls, (len(steps), calls)
    for call in steps:
        assert f"= (f32[{layers * slots},{shape}]" in call[:600], call[:300]
        assert state_operand.search(call[:600]), call[:300]
    makers = set(re.findall(
        r"= f32\[(?:\d+|\d+,\d+)," + re.escape(shape) + r"\]\S* ([a-z-]+)\(",
        text))
    assert makers <= {"parameter", "get-tuple-element", "bitcast"}, makers


@pytest.mark.parametrize("program", ["paged_decode_burst",
                                     "paged_prefill_chunk"])
def test_short_conv_served_programs_fit_one_chip(topo, program):
    """LFM2-8B-A1B at the benchmark's cut (layers 0-13: two leading conv
    layers with a dense FFN, three periods of one full layer to three conv
    layers; every expert, the whole vocabulary, the head tied) and serving
    shape (32 slots x 4096, block 16: three layers' pool 0.81 GB, eleven
    conv layers' two rows of 2048 for 33 slots 3 MB, beside 9.33 GB of
    weights): the width-32 burst and the chunk of 512 rows compile for one
    v5e chip and fit its 15.75 GB usable.  Pool and conv rows are updated
    in place (their bytes are aliased).  **The full layers' heads are 64
    wide and their pool is kept as rows of whole lanes**, two of a
    position's eight KV heads side by side, bf16[3,8193,64,128]
    (`ops.attention.pages_as_rows`; kept by position, (16, 8, 64), the
    compiler stores it with the blocks' axis innermost and both programs
    copied it whole into half-empty tiles and back, K and V, 1.63 GB of
    temporaries: PR 67): nothing of the pool's shape is made but by the
    in-place scatter; the burst reads it by the decode kernel, one call
    site in the scan over the periods, told of 4 KV heads of 128 and
    handed K and V as stored (`_paged_decode_side_by_side`), the chunk
    reads groups of pages by the loop (no kernel call), and the
    temporaries stay under a tenth of the pool.  The experts are 22 MB
    each, over what the visit's kernel holds in VMEM twice: a burst visits
    by the loop's fused products over the stacks in place, a chunk groups
    its rows for the tile kernel."""
    import json
    import re

    from bench.harness import spec

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "lfm2-8b-a1b-serve-1chip.json")) as f:
        config = json.load(f)
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    fam = spec.family(config)
    resident, programs = fam.serve_programs(config, place)
    (lowered,) = [low for name, low in programs if name.startswith(program)]
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    state, params = resident["sequence_state"], resident["params"]
    assert state.k.shape == (3, 8193, 64, 128) and state.wk is None
    assert state.lconv.shape == (11, 33, 2, 2048) and state.lstate is None
    assert state.lconv.dtype == jnp.bfloat16
    assert params["kinds"]["full"]["wq"].shape == (3, 2048, 32 * 64)
    assert params["kinds"]["full"]["wk"].shape == (3, 2048, 8 * 64)
    assert params["kinds"]["conv"]["in_proj"].shape == (9, 2048, 6144)
    assert params["lead"][1]["in_proj"].shape == (2048, 6144)
    assert params["lead"][0]["w_gate"].shape == (2048, 7168)
    assert params["blocks"]["w_gate"].shape == (12, 32, 2048, 1792)
    assert params["blocks"]["router_bias"].shape == (12, 32)
    assert params["embed"].shape == (65536, 2048) and "lm_head" not in params
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(state))
    resident_bytes = state_bytes + sum(
        s.size * s.dtype.itemsize for s in jax.tree.leaves(params))
    assert abs(resident_bytes - 10.14e9) < 0.1e9, resident_bytes
    assert resident_bytes > 0.25 * V5E_HBM_BYTES
    assert mem.alias_size_in_bytes >= state_bytes
    assert _device_bytes(compiled) < 15.75e9
    assert mem.temp_size_in_bytes < 0.1 * state.k.size * 2 * 2, \
        mem.temp_size_in_bytes
    memory = config["memory"]
    assert abs(memory["parameters_GB"] * 1e9 - (
        resident_bytes - state_bytes)) < 0.01e9
    assert abs(memory["resident_GB"] * 1e9 - resident_bytes) < 0.01e9
    _assert_pool_read_by_the_kernel(
        text, state.k.shape, 1 if program == "paged_decode_burst" else 0)
    _assert_experts_read_in_place(text, fam.expert_operand(config), program)
    # the lanes' three chunks and the slots' kept rows are there
    assert re.search(r"bf16\[(?:32,1|1,512|512),6144\]", text)
    assert re.search(r"bf16\[11,33,2,2048\]", text)
    assert fam.mixer_operand(config).search(text)


@pytest.mark.parametrize("program", ["paged_decode_burst",
                                     "paged_prefill_chunk"])
def test_looped_served_programs_fit_one_chip(topo, program):
    """Ouro-2.6B whole (48 layers applied 4 times over one set of weights,
    16 / 16 heads of 128, the whole vocabulary: 5.34 GB) at the benchmark's
    serving shape (8 slots x 512, block 16: a pool of 192 planes a position,
    bf16[192,257,16,16,128] K and V, 6.47 GB): the width-8 burst and the
    chunk of 128 rows compile for one v5e chip and fit its 15.75 GB usable
    beside 11.8 GB resident.  The pool is updated in place (its bytes are
    aliased) and nothing of its shape is made but by the in-place scatter;
    the burst reads it by the decode kernel, **one call site in the scan
    over the layers inside the scan over the passes** (192 launches a step,
    the plane a traced scalar the kernel prefetches), the chunk by the
    loop.  The passes and what follows each stand under their scopes
    (`loop_pass`, `loop_exit`).  The temporaries are the q, k and v
    weights of all layers in the layout the one-row products read (3 x
    0.40 GB, copied once a launch before the loops), under a fifth of the
    pool; the configuration's `memory` block holds these figures."""
    import json

    from bench.harness import spec

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "ouro-2.6b-serve-1chip.json")) as f:
        config = json.load(f)
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    fam = spec.family(config)
    resident, programs = fam.serve_programs(config, place)
    (lowered,) = [low for name, low in programs if name.startswith(program)]
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    pool, params = resident["pool"], resident["params"]
    assert pool.k.shape == pool.v.shape == (192, 257, 16, 16, 128)
    assert pool.wk is None and pool.lconv is None and pool.lstate is None
    assert params["blocks"]["wq"].shape == (48, 2048, 2048) \
        == params["blocks"]["wk"].shape
    assert params["blocks"]["w_gate"].shape == (48, 2048, 5632)
    assert params["blocks"]["attn_post_norm"].shape == (48, 2048) \
        == params["blocks"]["mlp_post_norm"].shape
    assert params["exit_gate"]["w"].shape == (2048,)
    assert params["lm_head"].shape == (2048, 49152)
    pool_bytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(pool))
    param_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(params))
    assert pool_bytes == 257 * 16 * 1572864             # 1.5 MiB a position
    assert (pool_bytes + param_bytes) > 0.25 * V5E_HBM_BYTES
    assert mem.alias_size_in_bytes >= pool_bytes
    assert _device_bytes(compiled) < 15.75e9 - 2.0e9    # the check's room
    assert mem.temp_size_in_bytes < 0.2 * pool_bytes, mem.temp_size_in_bytes
    memory = config["memory"]
    assert abs(memory["parameters_GB"] * 1e9 - param_bytes) < 0.01e9
    assert abs(memory["kv_pool_GB"] * 1e9 - pool_bytes) < 0.01e9
    assert abs(memory["resident_GB"] * 1e9 - pool_bytes - param_bytes) \
        < 0.01e9
    assert abs(memory["largest_program_temporaries_GB"] * 1e9
               - mem.temp_size_in_bytes) < 0.01e9
    _assert_pool_read_by_the_kernel(
        text, pool.k.shape, 1 if program == "paged_decode_burst" else 0)
    assert "/loop_pass/" in text and "/loop_exit/" in text
