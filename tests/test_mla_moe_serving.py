"""Latent attention with scored experts (`ray_tpu.models.mla_moe`) on the
served path, held to the glm4moelite family's plain float32 reference
(`bench/families/glm4moelite.py`, which imports nothing of the program
and computes the attention in its plain, expanded form): a chunk reads the
lane's earlier blocks of the latent pool in absorbed form; a dense first
layer; one rank's share of experts chosen by biased sigmoid scores beside
a shared expert.  Its sequences are pool blocks alone, so copy-on-write,
prefix reuse, preemption, speculation and shipped frames run on the
latent pool as on a `k` / `v` pool.  Seeded weights with a non-zero router
bias.  The served contract's cases are `tests/served_contract.py`'s."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_contract as contract
from bench.harness import reference
from ray_tpu.models import configs, mla_moe
from ray_tpu.ops import moe
from ray_tpu.serve.llm import LLMDeployment, PagedLLMEngine
from served_contract import Family, Teeth, seqs


def _a_burst_counts_its_share(e, t):
    # lanes x 8 steps x 3 layers x top-3
    assert 0 < t["routed_here"] < t["lanes"] * 8 * 3 * 3


FAM = Family(
    tiny="glmfamily/configs/tinyglm-serve.json", registry="tiny-mla-moe",
    as_registry=dict(compute_dtype=contract.FLOAT32),
    published=("glm-4.7-flash", 1e7, 2994),             # "30B" published
    # the norms' gains and the routers' biases on top
    leaves=("glm-4.7-flash", 1e-4),
    handed=lambda taken: {"routing": np.asarray(taken)},
    deployment=dict(contract.SMALL, engine="paged"),
    preempt=dict(engine=dict(num_blocks=12, max_burst=4,
                             prefix_sharing=False),
                 prompts=((30, 21), (30, 22)), max_tokens=24, stagger=0.05),
    burst_tick=_a_burst_counts_its_share,
    teeth=Teeth(fault_reads=None))
EXACT = FAM.exact
engines, served = contract.fixtures(FAM)


def test_the_tiny_configuration_is_the_registry_s():
    _, cfg = contract.tiny_configuration_is_the_registry_s(FAM)
    assert cfg.moe.held == (0, 4) and cfg.moe.num_experts == 8
    assert cfg.moe.scoring == "sigmoid" and cfg.moe.route_scale == 1.8
    assert cfg.n_expert_layers == 3 and cfg.row_width == 128
    assert not cfg.state_by_slot and not cfg.recurrent


def test_published_sizes_give_the_published_parameter_count():
    cfg, _ = contract.published_parameter_count(FAM)
    assert cfg.row_width == 640 and cfg.attention_scale == 1 / 16


def test_a_scoring_must_be_one_the_layer_has():
    with pytest.raises(ValueError, match="scoring"):
        moe.MoEConfig(num_experts=8, top_k=2, scoring="tanh")
    with pytest.raises(ValueError, match="held"):
        dataclasses.replace(configs.get("tiny-mla-moe"), experts_held=(6, 4))
    with pytest.raises(ValueError, match="n_dense_layers"):
        dataclasses.replace(configs.get("tiny-mla-moe"), n_dense_layers=4)


# -- (a) chunks, then decode steps, against the full forward ---------------
def test_chunks_through_the_pool_then_decode(served, engines, monkeypatch):
    """100 prompt tokens as one launch of the 128-row tier (a pool-only
    model's tiers go on above `prefill_chunk`, PR 37) and, on a narrow
    engine, as three whole chunks of 32 and a tail of 4 padded, each
    reading the lane's earlier blocks in absorbed form; then 10 decode
    steps; against the plain form's full forward."""
    from ray_tpu.serve import llm

    e, c = served
    assert e._chunk_tiers == [32, 64, 128, 256]
    contract.prefill_then_decode_equals_the_reference(FAM, e, c, 3, 100, 10)
    monkeypatch.setattr(llm, "_CHUNK_TOP_ROWS", 0)     # read as it is built
    with engines.private() as (narrow, _):
        assert narrow._chunk_tiers == [32]
        errs = FAM.errors(narrow, c, seqs(3, 100 + 10), 100)
        assert errs.max() < EXACT, errs


def test_chunk_sizes_one_three_and_whole_give_the_same_rows(served):
    """48 positions a position at a time, three at a time and as chunks
    of 32 leave the same latent rows and the same last logits; what
    stands in a chunk's padded tail changes neither, to the bit; a row is
    (latent | roped key | zeros to the tile), and the blocks nobody had
    stay zero."""
    e, c = served
    cfg, tokens = e.cfg, seqs(1, 48, seed=7)[0]
    whole, last = contract.prefill_alone(cfg, e.params, tokens, 32)
    for size in (1, 3):
        other, last_o = contract.prefill_alone(cfg, e.params, tokens,
                                               size)
        np.testing.assert_allclose(np.asarray(other.kv[:, 1:7]),
                                   np.asarray(whole.kv[:, 1:7]), atol=2e-5)
        np.testing.assert_allclose(np.asarray(last_o), np.asarray(last),
                                   atol=2e-5)
    junk, last_j = contract.prefill_alone(cfg, e.params, tokens, 32,
                                          pad_with=77)
    assert np.array_equal(np.asarray(junk.kv[:, 1:7]),
                          np.asarray(whole.kv[:, 1:7]))
    assert np.array_equal(np.asarray(last_j), np.asarray(last))
    rows = np.asarray(whole.kv)
    assert rows[:, 1:7, :, :32].any() and not rows[..., 32:].any()
    assert not rows[:, 9:].any()        # blocks no table names
    err = reference.position_errors(last[None], FAM.want(e, c, tokens)[-1:])
    assert float(err[0]) < EXACT


def test_the_absorbed_and_the_plain_form_agree():
    """One layer's attention by the program (the absorbed form over the
    pool: 4 query rows of the stored width against one row a position)
    and by the reference (keys and values of every head expanded from
    the latents), on the same input, at two chunkings."""
    c = FAM.config()
    fam = FAM.reference(c)
    cfg = FAM.program_config(c)
    params = FAM.params(cfg)
    li, t = 2, 40
    ap = {k: v[li] for k, v in params["attn"].items()}
    x = jax.random.normal(jax.random.key(1), (t, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = fam.attention(x, ap, c)
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]
    for size in (40, 8):
        pool = cfg.init_state(9, 8, 1, 32).kv
        outs = []
        for start in range(0, t, size):
            pos = jnp.arange(start, start + size, dtype=jnp.int32)[None]
            lanes = mla_moe._Lanes(table, pos, jnp.asarray([start + size]),
                                   table[0, pos // 8], pos % 8)
            out, (pool, _, _), *_ = mla_moe._attention(
                ap, x[None, start:start + size], (pool, None, None), "full",
                li, lanes, cfg)
            outs.append(out[0])
        np.testing.assert_allclose(np.asarray(jnp.concatenate(outs)),
                                   np.asarray(want), atol=1e-5)
        assert not np.asarray(pool[:li]).any()       # its own layer alone


# (heads, row width, d_v, block size, table entries, lengths, layer of 3),
# entries and lengths as (whole steps of the kernel, more): a step is
# `_LATENT_KERNEL_PAGES` table entries of `block size` positions.
_SMALL = (4, 128, 96, 8)
_KERNEL_CASES = {
    "a table shorter than one step": (
        *_SMALL, (0, 5), ((0, 37), (0, 0), (0, 9)), 2),
    "a table of one whole step": (
        *_SMALL, (1, 0), ((1, -3), (0, 0), (0, 9)), 2),
    "a table of whole steps and a part": (
        *_SMALL, (1, 13), ((1, 101), (0, 0), (1, 44)), 1),
    "20 heads at 640 / 512": (
        20, 640, 512, 16, (1, 8), ((1, 125), (0, 0), (0, 9)), 1),
    "a whole number of steps and one over": (
        *_SMALL, (2, 6), ((2, 0), (2, 1), (1, 0)), 2),
    "every lane idle": (*_SMALL, (1, 0), ((0, 0), (0, 0), (0, 0)), 1),
    "idle lanes before the first live one": (
        *_SMALL, (1, 8), ((0, 0), (0, 0), (1, 34)), 1),
    "layer 0": (*_SMALL, (1, 2), ((1, 13), (0, 0), (0, 9)), 0),
    "the last layer": (*_SMALL, (1, 2), ((0, 9), (1, 13), (0, 1)), 2),
}


@pytest.mark.parametrize("case", _KERNEL_CASES.values(), ids=_KERNEL_CASES)
def test_the_decode_kernel_agrees_with_the_block_loop(case):
    """What a TPU runs in a decode step (`_latent_decode_kernel`, this
    repo's Pallas kernel over the pool where it lies) against what every
    other platform runs (the block loop), on the CPU in Pallas's TPU
    interpret mode: scattered blocks, lanes of unlike lengths, rows in
    bfloat16 as they are served.  An idle lane reads 0 from the kernel,
    and the pool is what it was."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops import attention

    heads, w, d_v, bs, entries, lengths, layer = case
    pages = attention._LATENT_KERNEL_PAGES
    entries = entries[0] * pages + entries[1]
    lengths = [steps * pages * bs + more for steps, more in lengths]
    n_layers, n_blocks = 3, 3 * entries + 1
    pool = jax.random.normal(jax.random.key(0), (n_layers, n_blocks, bs, w),
                             jnp.bfloat16)
    before = np.asarray(pool.astype(jnp.float32))
    q = jax.random.normal(jax.random.key(1), (3, 1, heads, w), jnp.bfloat16)
    tables = jnp.asarray(
        np.random.default_rng(0).permutation(np.arange(1, n_blocks))
        .reshape(3, entries), jnp.int32)
    kv_len = jnp.asarray(lengths, jnp.int32)
    assert int(kv_len.max()) <= entries * bs
    args = (q, pool, layer, tables)
    with pltpu.force_tpu_interpret_mode():
        kernel = attention._latent_decode_kernel(
            *args, kv_len, d_v=d_v, scale=0.25)
    assert kernel.shape == (3, 1, heads, d_v) and kernel.dtype == jnp.float32
    live = np.asarray(kv_len) > 0
    assert not np.asarray(kernel[~live]).any()
    np.testing.assert_array_equal(np.asarray(pool.astype(jnp.float32)),
                                  before)
    if not live.any():
        return
    loop = attention.paged_latent_attention(
        *args, (kv_len - 1)[:, None], kv_len, d_v=d_v, scale=0.25)
    assert loop.shape == kernel.shape
    rms = float(jnp.sqrt(jnp.mean(loop[live] ** 2)))
    # the kernel rounds the probabilities to the rows' dtype (2**-9)
    assert float(jnp.abs(kernel - loop)[live].max()) < 1e-2 * rms
    # and the loop is the plain soft-max over the lane's own rows
    lane = int(np.argmax(live))
    rows = pool[layer][tables[lane]].reshape(-1, w)[
        :int(kv_len[lane])].astype(jnp.float32)
    p_ = jax.nn.softmax(jnp.einsum(
        "he,te->ht", q[lane, 0].astype(jnp.float32), rows) * 0.25, -1)
    np.testing.assert_allclose(np.asarray(loop[lane, 0]),
                               np.asarray(p_ @ rows[:, :d_v]), atol=1e-5)


def test_a_row_that_is_not_whole_tiles_warns_and_takes_the_loop():
    """A decode step over rows the kernel cannot take says so (as
    `flash_attention` does for its lengths) and runs the loop."""
    from ray_tpu.ops import attention

    pool = jax.random.normal(jax.random.key(0), (1, 5, 4, 72), jnp.float32)
    q = jax.random.normal(jax.random.key(1), (1, 1, 2, 72), jnp.float32)
    kv_len = jnp.asarray([7], jnp.int32)
    with pytest.warns(UserWarning, match="not whole tiles"):
        out = attention.paged_latent_attention(
            q, pool, 0, jnp.asarray([[3, 1]], jnp.int32),
            (kv_len - 1)[:, None], kv_len, d_v=64, scale=1.0)
    rows = pool[0][jnp.asarray([3, 1])].reshape(-1, 72)[:7]
    p_ = jax.nn.softmax(jnp.einsum("he,te->ht", q[0, 0], rows), -1)
    np.testing.assert_allclose(np.asarray(out[0, 0]),
                               np.asarray(p_ @ rows[:, :64]), atol=1e-5)


# -- (b) the router, and the shares ----------------------------------------
def _whole(c):
    c = dict(c, n_routed_experts=8, first_local_expert=0)
    whole = FAM.program_config(c)
    assert whole.experts_held is None
    return c, whole, FAM.params(whole)


def test_the_shares_add_up_to_the_uncut_layer():
    """With the same router and bias."""
    contract.shares_cut_in_the_program_add_up(
        FAM, mla_moe, mla_moe._expert_ffn, 1, "n_routed_experts")


def test_the_bias_moves_the_selection_and_not_the_gates():
    """The experts taken are the top-k of s + b; the gates are 1.8 x the
    unbiased s of those, renormalised.  With b zeroed the selection is
    another; with a constant added to b nothing changes at all (a bias
    that gated would scale every gate); and the output is the sum the
    published equations give for the program's own selection."""
    c, whole, params = _whole(FAM.config())
    li = 0
    x, fp, experts = contract.layer_inputs(mla_moe, whole, params, li)
    h = mla_moe.rms_norm(x, fp["norm"], eps=whole.norm_eps)
    p = {"router": fp["router"], "router_bias": fp["router_bias"], **experts}

    def run(bias):
        out, _, taken = moe.moe_mlp_dropless(
            h, dict(p, router_bias=bias), whole.moe, layer=li,
            return_routing=True)
        return np.asarray(out), np.asarray(taken)

    out, taken = run(p["router_bias"])
    assert float(jnp.abs(p["router_bias"]).min()) > 0
    _, unbiased = run(jnp.zeros_like(p["router_bias"]))
    differ = np.sort(taken, -1) != np.sort(unbiased, -1)
    assert differ.any() and not differ.all()
    out_shift, taken_shift = run(p["router_bias"] + 5.0)
    assert np.array_equal(taken_shift, taken)
    np.testing.assert_allclose(out_shift, out, atol=1e-6)
    s = jax.nn.sigmoid(jnp.einsum("btd,de->bte", h, p["router"]))
    g = jnp.take_along_axis(s, jnp.asarray(taken), -1)
    g = 1.8 * g / jnp.sum(g, -1, keepdims=True)
    want = jnp.zeros_like(h)
    for e_i in range(8):
        w = jnp.sum(jnp.where(jnp.asarray(taken) == e_i, g, 0.0), -1)
        hid = jax.nn.silu(h @ experts["w_gate"][li, e_i]) \
            * (h @ experts["w_up"][li, e_i])
        want = want + w[..., None] * (hid @ experts["w_down"][li, e_i])
    np.testing.assert_allclose(out, np.asarray(want), atol=1e-5)
    # soft-max scoring of the same layer is another function
    soft = dataclasses.replace(whole.moe, scoring="softmax", route_scale=1.0)
    assert np.abs(np.asarray(moe.moe_mlp_dropless(h, p, soft, layer=li)[0])
                  - out).max() > 1e-2


def test_the_seeded_bias_decides_and_leaves_the_load_even():
    """`ROUTER_BIAS_STD`'s two properties, at the published router's sizes
    (64 experts, top-4, width 2048; everything else tiny), through the
    program's own `init_params` and routing lines, over four seeds: with
    the bias zeroed most tokens take another set; eight tokens take within
    a tenth of the distinct experts uniform routing gives them (what
    `expert_bytes_per_step` counts); this rank's half takes about half.
    Twice the bias fails the second, a third of it the first."""
    cfg = mla_moe.MLAMoEConfig(
        vocab_size=16, n_layers=5, n_heads=1, q_rank=8, kv_rank=8,
        d_nope=4, d_rope=4, d_v=4, d_ff=8, d_expert=4, d_shared=4,
        experts_held=(0, 32), param_dtype=jnp.float32, max_seq_len=64)
    k, e = cfg.expert_top_k, cfg.n_experts
    uniform = e * (1 - (1 - k / e) ** 8)

    @jax.jit
    def route(h, lp, bias):
        return moe.moe_mlp_dropless(h, dict(lp, router_bias=bias), cfg.moe,
                                    return_routing=True)[2]

    def read(seed, times):
        p = cfg.init_params(jax.random.key(seed))["ffn"]
        x = jax.random.normal(jax.random.key(100 + seed),
                              (256, 8, cfg.d_model))
        moved, distinct, here = [], [], []
        for li in range(cfg.n_expert_layers):
            h = mla_moe.rms_norm(x, p["norm"][li], eps=cfg.norm_eps)
            lp = {"router": p["router"][li], **{
                w: p[w][li] for w in mla_moe._EXPERT_WEIGHTS}}

            def taken(bias):
                return np.sort(np.asarray(route(h, lp, bias)), -1)

            sel = taken(p["router_bias"][li] * times)
            moved.append((sel != taken(jnp.zeros(e))).any(-1).mean())
            distinct.append(np.mean([len(np.unique(g)) for g in sel]))
            here.append((sel < 32).mean())
        return np.mean(moved), np.mean(distinct), np.mean(here)

    for seed in range(4):
        moved, distinct, here = read(seed, 1.0)
        assert moved > 0.5, (seed, moved)
        assert distinct > 0.9 * uniform, (seed, distinct, uniform)
        assert 0.45 < here < 0.55, (seed, here)
    assert read(0, 2.0)[1] < 0.9 * uniform
    assert read(0, 0.3)[0] < 0.5


def test_an_idle_row_is_routed_nowhere():
    contract.an_idle_row_is_routed_nowhere(FAM, mla_moe, mla_moe._expert_ffn)


def test_a_burst_equals_its_steps_and_counts_what_it_routed(served):
    """The idle lane writes the null block alone, and the burst's count of
    choices on held experts is the steps' own routing's."""
    cfg = served[0].cfg
    b_state, state, visited, routed, here = contract.burst_equals_its_steps(
        served[0], held=(0, 4))
    np.testing.assert_allclose(np.asarray(b_state.kv[:, 1:]),
                               np.asarray(state.kv[:, 1:]), atol=1e-6)
    assert not np.asarray(b_state.kv[:, 5:9]).any()   # the idle lane's
    # experts 0..3 are held here: the burst counted the choices on them
    assert routed == here and 0 < here < 3 * 3 * 3 * cfg.n_expert_layers
    assert 0 < visited <= 4 * 3 * cfg.n_expert_layers


# -- (c) the engine's own scheduling, on the latent pool --------------------
def test_prefix_hit_copy_on_write_and_the_tick_log(engines):
    """The blocks are the sequence: the same prompt again is a whole-
    prompt hit whose shared partial tail block is copied before it is
    written (`copy_block` on the latent leaf), a fork off the shared
    prefix prefills only its own tail, and every stream is the
    reference's greedy one.  The tick log counts latent rows read and
    the choices that fell on held experts."""
    e, c = engines(prefix_sharing=True)
    assert e.allocator.prefix_sharing
    a = contract.prompt(45, 11)
    fork = a[:32] + contract.prompt(9, 12)
    since = contract.Since(e)
    outs = [e.generate(p, max_tokens=6) for p in (a, a, fork, a)]
    assert outs[0] == outs[1] == outs[3]
    assert FAM.is_greedy(e, c, a, outs[0])
    assert FAM.is_greedy(e, c, fork, outs[2])
    stats = since.stats()
    assert stats["reuse_hits"] > 0 and stats["cow_copies"] >= 1
    assert stats["prefix_hits"] >= 3
    state = stats["state"]
    assert state["kv_paged"] == e.cache.kv.size * 4 and \
        state["kv_window"] == state["recurrent"] == 0
    # what the store's arena is reserved by: layers x rows x width x f32
    assert e._state_bytes["kv_paged"] // e.num_blocks == 4 * 8 * 128 * 4
    ticks = since.ticks(stats)
    one = [t for t in ticks if t["lanes"] == 1][-1]
    assert one["kv_read_tokens"] > 0 and \
        one["kv_read_tokens"] % e.cfg.n_layers == 0
    assert e.cfg.kv_read_tokens([45, 7]) == 4 * 52
    assert 0 < one["experts_read"] <= 3
    # the first prompt's rows, by the program's own routing of them
    _, taken = e.score(np.asarray([a]), 45, routing=True)
    here = int(np.sum(np.asarray(taken[0])[:45] < 4))
    first = [t for t in ticks if t["prefill_tokens"]][0]
    assert first["prefill_tokens"] == 45 and first["routed_here"] == here
    assert 0.3 * 3 * 3 * 45 < here < 0.7 * 3 * 3 * 45
    # the fork prefilled its 9 own tokens (and the block's remainder)
    assert sum(t["prefill_tokens"] for t in ticks) < 45 + 41 + 8


def test_a_preempted_stream_equals_the_undisturbed_one(engines):
    """The younger's blocks are freed, and its re-prefill of prompt +
    emitted tokens writes its latent rows again."""
    contract.preempted_stream_equals_the_undisturbed_one(FAM, engines)


def test_a_shipped_frame_is_the_latent_rows_and_is_adopted(engines):
    """`export_streams` gathers a decoding stream's blocks as one frame
    (1 pooled leaf, layers, blocks, block size, stored width),
    `import_prefix` of another engine scatters it into fresh blocks, and
    the prompt then hits the adopted prefix there and streams what the
    reference does; a frame of another geometry is turned away."""
    from burst_ahead_cases import park, run_until_done, submit, tick

    src, c = engines()              # prefixes shared by default; unparked
    dst, _ = engines(prefix_sharing=True)       # when it is handed out again
    park(src)
    prompt = contract.prompt(37, 31)
    req = submit(src, prompt, 20, stream=True)
    req.trace = {"trace_id": "rid-latent"}
    for _ in range(50):
        tick(src)
        if len(req.out_tokens) >= 4:
            break
    (ticket,) = src.export_streams()
    n_kv = len(ticket["tokens"])
    assert ticket["tokens"] == (prompt + req.out_tokens)[:n_kv]
    kv = np.asarray(ticket["kv"])
    assert kv.shape == (1, 4, -(-n_kv // 8), 8, 128)
    assert kv[..., :32].any() and not kv[..., 32:].any()
    assert dst.import_prefix(ticket["tokens"], kv[:, :, :, :, :64], 8) == 0
    assert dst.import_prefix(ticket["tokens"], np.stack([kv[0]] * 2),
                             8) == 0
    adopted = dst.import_prefix(ticket["tokens"], kv, 8)
    assert adopted == -(-n_kv // 8)
    hits = dst.stats["prefix_hits"]
    out = dst.generate(ticket["tokens"], max_tokens=8)
    assert dst.stats["prefix_hits"] == hits + 1
    assert FAM.is_greedy(dst, c, ticket["tokens"], out)
    run_until_done(src, [req])
    assert FAM.is_greedy(src, c, prompt, req.out_tokens)


def test_speculation_verifies_through_the_model_s_own_step(engines):
    """`speculation_k` runs: `paged_verify_step` goes through
    `_served_forward`, so the candidates' latent rows are written and
    read as a chunk's are; the stream is the unspeculated twin's and
    some proposals are accepted."""
    plain, c = engines(max_burst=1)
    spec_e, _ = engines(max_burst=1, speculation_k=4)
    proposed = spec_e.stats["spec_proposed"]
    prompt = [100, 200] * 6
    want = plain.generate(prompt, max_tokens=24)
    assert spec_e.generate(prompt, max_tokens=24) == want
    assert FAM.is_greedy(plain, c, prompt, want)
    # a repeated continuation is proposed and taken
    again = prompt + want
    assert spec_e.generate(again, max_tokens=12) \
        == plain.generate(again, max_tokens=12)
    assert spec_e.stats["spec_proposed"] > proposed


def test_streams_equal_the_step_reference_while_lanes_join_and_leave(engines):
    """On a model that brings its own pool and whose burst hands out the
    routed count."""
    contract.streams_equal_the_step_reference_while_lanes_join_and_leave(
        FAM, engines)


# -- (d) what it cannot have is refused --------------------------------------
def test_a_mesh_is_refused():
    from jax.sharding import Mesh

    cfg = configs.get("tiny-mla-moe")
    params = cfg.init_params(jax.random.key(0))
    with pytest.raises(ValueError, match="no mesh"):
        PagedLLMEngine(cfg, params, num_slots=2, max_len=64, block_size=8,
                       prefill_chunk=16,
                       mesh=Mesh(np.array(jax.devices()[:1]), ("tp",)))
    with pytest.raises(ValueError, match="no mesh|sequence state"):
        LLMDeployment(cfg, num_slots=2, max_len=64, tensor_parallel=2)


def test_deployment_takes_the_configuration_by_name():
    with contract.deployed(FAM) as dep:
        state = dep.stats()["state"]
        assert state["kv_window"] == 0 and state["recurrent"] == 0
        assert state["kv_paged"] == dep.engine.cache.kv.size * 2   # bfloat16
        from ray_tpu.exceptions import KVMigrationError

        with pytest.raises(KVMigrationError):        # a k / v frame: not ours
            dep.adopt_kv(list(range(8)), np.zeros((2, 4, 1, 8, 2, 16)), 8)
        rows = np.zeros((1, 4, 1, 8, 128), np.float32)
        assert dep.adopt_kv(list(range(8)), rows, 8) == 1


# -- (e) the other models lower to the programs they lowered to --------------
# sha256 of the StableHLO text of the served programs and of the block
# copy, taken on PR 39's tree (commit 7cb3edd) with the shapes below: the
# scoring in `MoEConfig`, one pool given as both to the running soft-max,
# the block operations over `pooled_leaves` and the verify step through
# `_served_forward` leave them as they were, to the letter.  (`tiny-moe`,
# `tiny-window-moe` and `tiny-hybrid` are held by tests/
# test_mamba2_moe_serving.py and tests/test_window_moe_serving.py, and
# still are.)
_LOWERED_AT_PR_39 = {
    ("tiny", "chunk"): "011fc65882f1d997",
    ("tiny", "burst"): "de79f35fa15a540e",
    ("tiny", "copy_block"): "bd7b78018a15c81a",
    ("tiny", "verify"): "52829069696501de",
    ("tiny-mamba2-moe", "chunk"): "96eda432dc081025",
    ("tiny-mamba2-moe", "burst"): "9ec33ab53b970ef0",
}


@pytest.mark.parametrize("name,program", list(_LOWERED_AT_PR_39),
                         ids=lambda v: str(v))
def test_other_models_lower_as_before(name, program):
    assert contract.lowered_digest(name, program, **contract.SMALL_SHAPES) \
        == _LOWERED_AT_PR_39[(name, program)]


# -- the benchmark's comparison has teeth ------------------------------------
def _moe_config(monkeypatch, **change):
    prop = mla_moe.MLAMoEConfig.moe
    monkeypatch.setattr(mla_moe.MLAMoEConfig, "moe", property(
        lambda self: dataclasses.replace(prop.fget(self), **change)))


def _one_expert_fewer_a_token(monkeypatch, cfg):
    _moe_config(monkeypatch, top_k=2)


def _route_scale_dropped(monkeypatch, cfg):
    _moe_config(monkeypatch, route_scale=1.0)


def _softmax_for_the_sigmoid(monkeypatch, cfg):
    _moe_config(monkeypatch, scoring="softmax")


def _experts_with(monkeypatch, change):
    inner = mla_moe.moe_mlp_dropless
    monkeypatch.setattr(
        mla_moe, "moe_mlp_dropless",
        lambda h, params, *a, **kw: inner(h, change(params), *a, **kw))


def _bias_dropped_from_the_selection(monkeypatch, cfg):
    _experts_with(monkeypatch, lambda p: dict(
        p, router_bias=jnp.zeros_like(p["router_bias"])))


def _one_held_expert_dropped(monkeypatch, cfg):
    _experts_with(monkeypatch, lambda p: dict(
        p, w_down=p["w_down"].at[:, 1].set(0)))


def _roped_score_dropped(monkeypatch, cfg):
    inner = mla_moe._queries

    def no_rope_part(*a):
        q_n, q_r, cq = inner(*a)
        return q_n, jnp.zeros_like(q_r), cq

    monkeypatch.setattr(mla_moe, "_queries", no_rope_part)


def _latent_norm_dropped(monkeypatch, cfg):
    dropped = object()            # stands where the latent's gain stood
    inner, norm = mla_moe._latent_row, mla_moe.rms_norm
    monkeypatch.setattr(mla_moe, "_latent_row", lambda ap, *a: inner(
        dict(ap, kv_norm=dropped), *a))
    monkeypatch.setattr(mla_moe, "rms_norm", lambda x, gain, eps:
                        x if gain is dropped else norm(x, gain, eps=eps))


def _pool_in_8_bit_floats(monkeypatch, cfg):
    inner = mla_moe._latent_row
    monkeypatch.setattr(
        mla_moe, "_latent_row", lambda *a: inner(*a).astype(
            jnp.float8_e4m3fn).astype(jnp.bfloat16))


FAULTS = [_one_expert_fewer_a_token, _route_scale_dropped,
          _softmax_for_the_sigmoid, _bias_dropped_from_the_selection,
          _one_held_expert_dropped, _roped_score_dropped,
          _latent_norm_dropped, _pool_in_8_bit_floats]


@pytest.mark.parametrize("fault", [None] + FAULTS,
                         ids=lambda f: f.__name__.strip("_") if f
                         else "as_it_is")
def test_logits_check_has_teeth(engines, fault, monkeypatch):
    """Fails each fault of ISSUE 40's list."""
    contract.logits_check_has_teeth(FAM, engines, fault, monkeypatch)


def test_a_pool_in_8_bit_floats_shows_in_float32_arithmetic(engines,
                                                            monkeypatch):
    """With everything else in float32 a latent row rounded to 8-bit
    floats is far over the engine's own error of 1e-6, in the chunk and
    in the decode steps."""
    monkeypatch.setattr(
        mla_moe, "_latent_row", (lambda inner: lambda *a: inner(*a).astype(
            jnp.float8_e4m3fn).astype(jnp.float32))(mla_moe._latent_row))
    with engines.private() as (e, c):
        errs = FAM.errors(e, c, seqs(2, 100 + 6), 100)
    assert errs.min() > 5 * EXACT, errs
