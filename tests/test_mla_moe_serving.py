"""Latent attention with scored experts (`ray_tpu.models.mla_moe`) on the
served path, held to the glm4moelite family's plain float32 reference
(`bench/families/glm4moelite.py`, which imports nothing of the program
and computes the attention in its plain, expanded form): prefill chunks,
each reading the lane's earlier blocks of the latent pool in absorbed
form, then decode steps, through a real `PagedLLMEngine`; a dense first
layer; one rank's share of experts chosen by biased sigmoid scores beside
a shared expert.  Its sequences are pool blocks alone, so copy-on-write,
prefix reuse, preemption, speculation and shipped frames run on the
latent pool as on a `k` / `v` pool.  Tiny widths, seeded weights with a
non-zero router bias, float32 compute where the claim is that the engine
computes the same function (errors of 1e-6), bfloat16 where it is that
the benchmark's comparison tells a fault from rounding."""
import dataclasses
import hashlib
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench.harness import reference, spec  # noqa: E402
from ray_tpu.models import configs, decoding, init_params, mla_moe  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.serve.llm import LLMDeployment, PagedLLMEngine  # noqa: E402

TINY = os.path.join(ROOT, "bench", "tests", "data", "glmfamily",
                    "configs", "tinyglm-serve.json")
SEED = 5
EXACT = 2e-5          # float32 engine against float32 reference


def _config(**over):
    with open(TINY) as f:
        return dict(json.load(f), **over)


def _engine(c, **over):
    fam = spec.family(c)
    cfg, eng = fam.program_config(c), dict(c["engine"], **over)
    params = cfg.init_params(jax.random.key(SEED))
    return PagedLLMEngine(
        cfg, params, num_slots=eng["num_slots"], max_len=eng["max_len"],
        block_size=eng["block_size"], prefill_chunk=eng["prefill_chunk"],
        max_burst=eng["max_burst"], num_blocks=eng.get("num_blocks"),
        speculation_k=eng["speculation_k"],
        prefix_sharing=eng.get("prefix_sharing"))


def _want(e, c, seq, routing=None):
    logits, _ = spec.family(c).forward(
        e.params, jnp.asarray(seq, jnp.int32), c, jit=jax.jit,
        routing=routing)
    return logits


def _errors(e, c, seqs, n_prompt):
    """The engine's logits against the reference's, the reference given
    the experts the program took (and holding them to its own router)."""
    got, taken = e.score(seqs, n_prompt, routing=True)
    return np.concatenate([
        np.asarray(reference.position_errors(
            jnp.stack(got[lane]),
            _want(e, c, seqs[lane], np.asarray(taken[lane]))[n_prompt - 1:]))
        for lane in range(len(seqs))])


def _seqs(lanes, total, seed=0):
    return np.random.default_rng(seed).integers(1, 512, (lanes, total))


def _is_greedy(e, c, prompt, out):
    """`out` is the reference's greedy continuation of `prompt`: one
    full forward over both, whose argmax at every position from the
    prompt's last is the token that follows."""
    logits = _want(e, c, list(prompt) + list(out))
    return out == [int(t) for t in
                   jnp.argmax(logits[len(prompt) - 1:-1], axis=-1)]


@pytest.fixture(scope="module")
def served():
    c = _config()
    e = _engine(c)
    yield e, c
    e.shutdown()


def test_the_tiny_configuration_is_the_registry_s():
    c = _config()
    cfg = spec.family(c).program_config(c)
    assert cfg == dataclasses.replace(configs.get("tiny-mla-moe"),
                                      name=c["name"],
                                      compute_dtype=jnp.dtype("float32"))
    assert cfg.moe.held == (0, 4) and cfg.moe.num_experts == 8
    assert cfg.moe.scoring == "sigmoid" and cfg.moe.route_scale == 1.8
    assert cfg.n_expert_layers == 3 and cfg.row_width == 128
    assert not cfg.state_by_slot and not cfg.recurrent


def test_published_sizes_give_the_published_parameter_count():
    cfg = configs.get("glm-4.7-flash")
    assert round(cfg.num_params / 1e7) == 2994          # "30B" published
    assert cfg.row_width == 640 and cfg.attention_scale == 1 / 16
    shapes = jax.eval_shape(lambda: cfg.init_params(jax.random.key(0)))
    total = sum(x.size for x in jax.tree.leaves(shapes))
    # the norms' gains and the routers' biases on top
    assert 0 < total - cfg.num_params < 1e-4 * cfg.num_params


def test_a_scoring_must_be_one_the_layer_has():
    with pytest.raises(ValueError, match="scoring"):
        moe.MoEConfig(num_experts=8, top_k=2, scoring="tanh")
    with pytest.raises(ValueError, match="held"):
        dataclasses.replace(configs.get("tiny-mla-moe"), experts_held=(6, 4))
    with pytest.raises(ValueError, match="n_dense_layers"):
        dataclasses.replace(configs.get("tiny-mla-moe"), n_dense_layers=4)


# -- (a) chunks, then decode steps, against the full forward ---------------
def test_chunks_through_the_pool_then_decode(served, monkeypatch):
    """100 prompt tokens as one launch of the 128-row tier (a pool-only
    model's tiers go on above `prefill_chunk`, PR 37) and, on a narrow
    engine, as three whole chunks of 32 and a tail of 4 padded, each
    reading the lane's earlier blocks in absorbed form; then 10 decode
    steps; against the plain form's full forward."""
    from ray_tpu.serve import llm

    e, c = served
    assert e._chunk_tiers == [32, 64, 128, 256]
    seqs = _seqs(3, 100 + 10)
    errs = _errors(e, c, seqs, 100)
    assert errs.shape == (33,) and errs.max() < EXACT, errs
    monkeypatch.setattr(llm, "_CHUNK_TOP_ROWS", 0)
    narrow = _engine(c)
    try:
        assert narrow._chunk_tiers == [32]
        errs = _errors(narrow, c, seqs, 100)
        assert errs.max() < EXACT, errs
    finally:
        narrow.shutdown()


def _prefill(cfg, params, tokens, size, pad_with=0):
    """`tokens` through `paged_prefill_chunk` in chunks of `size` on a
    pool of its own; a last chunk is padded to `size` with `pad_with`.
    Returns (state, last logits)."""
    state = cfg.init_state(17, 8, 2, 32)
    chunk = jax.jit(decoding._bind_cfg(decoding.paged_prefill_chunk, cfg))
    table = jnp.arange(1, 9, dtype=jnp.int32)
    for start in range(0, len(tokens), size):
        toks = np.full((size,), pad_with, np.int32)
        nv = min(size, len(tokens) - start)
        toks[:nv] = tokens[start:start + nv]
        state, last, *_ = chunk(params, state, jnp.asarray(toks), table,
                                jnp.int32(start), jnp.int32(nv))
    return state, last


def test_chunk_sizes_one_three_and_whole_give_the_same_rows(served):
    """48 positions a position at a time, three at a time and as chunks
    of 32 leave the same latent rows and the same last logits; what
    stands in a chunk's padded tail changes neither, to the bit; a row is
    (latent | roped key | zeros to the tile), and the blocks nobody had
    stay zero."""
    e, c = served
    cfg, tokens = e.cfg, _seqs(1, 48, seed=7)[0]
    whole, last = _prefill(cfg, e.params, tokens, 32)
    for size in (1, 3):
        other, last_o = _prefill(cfg, e.params, tokens, size)
        np.testing.assert_allclose(np.asarray(other.kv[:, 1:7]),
                                   np.asarray(whole.kv[:, 1:7]), atol=2e-5)
        np.testing.assert_allclose(np.asarray(last_o), np.asarray(last),
                                   atol=2e-5)
    junk, last_j = _prefill(cfg, e.params, tokens, 32, pad_with=77)
    assert np.array_equal(np.asarray(junk.kv[:, 1:7]),
                          np.asarray(whole.kv[:, 1:7]))
    assert np.array_equal(np.asarray(last_j), np.asarray(last))
    rows = np.asarray(whole.kv)
    assert rows[:, 1:7, :, :32].any() and not rows[..., 32:].any()
    assert not rows[:, 9:].any()        # blocks no table names
    err = reference.position_errors(last[None], _want(e, c, tokens)[-1:])
    assert float(err[0]) < EXACT


def test_the_absorbed_and_the_plain_form_agree():
    """One layer's attention by the program (the absorbed form over the
    pool: 4 query rows of the stored width against one row a position)
    and by the reference (keys and values of every head expanded from
    the latents), on the same input, at two chunkings."""
    c = _config()
    fam = spec.family(c)
    cfg = fam.program_config(c)
    params = cfg.init_params(jax.random.key(SEED))
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
def _layer_inputs(cfg, params, li, rows=24):
    x = jax.random.normal(jax.random.key(3), (2, rows // 2, cfg.d_model),
                          jnp.float32)
    fp = {k: v[li] for k, v in params["ffn"].items()
          if k not in mla_moe._EXPERT_WEIGHTS}
    experts = {k: params["ffn"][k] for k in mla_moe._EXPERT_WEIGHTS}
    return x, fp, experts


def _whole(c):
    c = dict(c, n_routed_experts=8, first_local_expert=0)
    fam = spec.family(c)
    whole = fam.program_config(c)
    assert whole.experts_held is None
    return c, fam, whole, whole.init_params(jax.random.key(SEED))


def test_the_shares_add_up_to_the_uncut_layer():
    """A layer of 8 experts whole, and cut into the shares (0..3) and
    (4..7) with the same router and bias: the routed parts of the two
    shares, plus the shared expert counted once, equal the uncut layer;
    experts visited and choices routed add up too.  In the program and
    in the reference alike, and the two agree."""
    c, fam, whole, params = _whole(_config())
    li = 1
    x, fp, experts = _layer_inputs(whole, params, li)
    live = jnp.ones(x.shape[:2], bool)
    full, n_full, r_full, _ = mla_moe._expert_ffn(fp, experts, li, x, live,
                                                  whole, False)
    assert int(r_full) == x.shape[0] * x.shape[1] * whole.expert_top_k
    h = mla_moe.rms_norm(x, fp["norm"], eps=whole.norm_eps)
    routed_params = {"router": fp["router"],
                     "router_bias": fp["router_bias"], **experts}
    shared = full - moe.moe_mlp_dropless(h, routed_params, whole.moe,
                                         layer=li)[0]
    parts, ref_parts, visited, routed = [], [], 0, 0
    u = jnp.asarray(np.asarray(h).reshape(-1, whole.d_model))
    for first in (0, 4):
        cut = dataclasses.replace(whole, experts_held=(first, 4))
        held = {k: v[:, first:first + 4] for k, v in experts.items()}
        out, n, r, _ = mla_moe._expert_ffn(fp, held, li, x, live, cut, False)
        parts.append(out - shared)
        visited, routed = visited + int(n), routed + int(r)
        c_cut = dict(c, n_routed_experts=4, first_local_expert=first,
                     published={"n_routed_experts": 8})
        ref_fp = {**fp, **{k: v[li] for k, v in held.items()}}
        ref_parts.append(fam.experts(u, ref_fp, None, c_cut)[0])
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] + shared),
                               np.asarray(full), atol=1e-5)
    assert visited == int(n_full) and routed == int(r_full)
    assert 0 < routed - int(r) < routed            # neither share is empty
    ref_fp = {**fp, **{k: v[li] for k, v in experts.items()}}
    ref_full = fam.experts(u, ref_fp, None, c)[0] \
        + fam.shared_expert(u, ref_fp)
    np.testing.assert_allclose(
        np.asarray(ref_parts[0] + ref_parts[1]
                   + fam.shared_expert(u, ref_fp)),
        np.asarray(ref_full), atol=1e-5)
    np.testing.assert_allclose(np.asarray(full).reshape(u.shape),
                               np.asarray(ref_full), atol=1e-5)


def test_the_bias_moves_the_selection_and_not_the_gates():
    """The experts taken are the top-k of s + b; the gates are 1.8 x the
    unbiased s of those, renormalised.  With b zeroed the selection is
    another; with a constant added to b nothing changes at all (a bias
    that gated would scale every gate); and the output is the sum the
    published equations give for the program's own selection."""
    c, _, whole, params = _whole(_config())
    li = 0
    x, fp, experts = _layer_inputs(whole, params, li)
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
    """`live` by row: a chunk's padded tail and an idle lane take no
    expert, hit none, and are not counted (the shared expert, a dense
    layer, runs on them all the same and nobody reads it)."""
    cfg = configs.get("tiny-mla-moe")
    params = cfg.init_params(jax.random.key(0))
    x, fp, experts = _layer_inputs(cfg, params, 0)
    live = jnp.arange(x.shape[1])[None, :] < jnp.asarray([[5], [0]])
    out, n, r, _ = mla_moe._expert_ffn(fp, experts, 0, x, live, cfg, False)
    _, n_all, r_all, _ = mla_moe._expert_ffn(fp, experts, 0, x,
                                             jnp.ones_like(live), cfg, False)
    assert 0 < int(r) <= 5 * cfg.expert_top_k and int(r) < int(r_all)
    assert int(n) <= int(n_all)
    h = mla_moe.rms_norm(x, fp["norm"], eps=cfg.norm_eps)
    routed_part = moe.moe_mlp_dropless(
        h, {"router": fp["router"], "router_bias": fp["router_bias"],
            **experts}, cfg.moe, live=live, layer=0)[0]
    assert not np.asarray(routed_part[1], np.float32).any()
    assert not np.asarray(routed_part[0, 5:], np.float32).any()


def test_a_burst_equals_its_steps_and_counts_what_it_routed(served):
    """Lanes of unequal lengths with an idle lane between: the burst's
    tokens and pool are its steps', the idle lane writes the null block
    alone, and the burst's count of choices on held experts is the
    steps' own routing's."""
    e, _ = served
    cfg = e.cfg
    state = cfg.init_state(17, 8, 4, 32)
    tables = jnp.asarray(np.arange(1, 17, dtype=np.int32).reshape(4, 4))
    lengths = jnp.asarray([3, 0, 9, 1], jnp.int32)
    active = jnp.asarray([True, False, True, True])
    toks = jnp.asarray([5, 0, 7, 9], jnp.int32)
    burst = jax.jit(decoding._bind_cfg(decoding.paged_decode_burst, cfg),
                    static_argnames=("n_steps",))
    b_state, b_toks, _, visited, routed = burst(
        e.params, state, toks, tables, lengths, active,
        jnp.zeros((4,), jnp.float32), jax.random.key(0), n_steps=3)
    step = jax.jit(decoding._bind_cfg(decoding.paged_decode_step, cfg),
                   static_argnames=("routing",))
    s_toks, here = [], 0
    for _ in range(3):
        state, logits, taken = step(e.params, state, toks, tables, lengths,
                                    active, routing=True)
        taken = np.asarray(taken)[:, np.asarray(active)]   # (L, live, k)
        here += int(np.sum(taken < 4))
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        lengths = jnp.where(active, lengths + 1, lengths)
        s_toks.append(toks)
    live = np.asarray(active)
    assert np.array_equal(np.asarray(b_toks)[:, live],
                          np.stack(s_toks)[:, live])
    np.testing.assert_allclose(np.asarray(b_state.kv[:, 1:]),
                               np.asarray(state.kv[:, 1:]), atol=1e-6)
    assert not np.asarray(b_state.kv[:, 5:9]).any()   # the idle lane's
    # experts 0..3 are held here: the burst counted the choices on them
    assert int(routed) == here and 0 < here < 3 * 3 * 3 * cfg.n_expert_layers
    assert 0 < int(visited) <= 4 * 3 * cfg.n_expert_layers


# -- (c) the engine's own scheduling, on the latent pool --------------------
def test_prefix_hit_copy_on_write_and_the_tick_log():
    """The blocks are the sequence: the same prompt again is a whole-
    prompt hit whose shared partial tail block is copied before it is
    written (`copy_block` on the latent leaf), a fork off the shared
    prefix prefills only its own tail, and every stream is the
    reference's greedy one.  The tick log counts latent rows read and
    the choices that fell on held experts."""
    c = _config()
    e = _engine(c, prefix_sharing=True)
    try:
        assert e.allocator.prefix_sharing
        a = list(map(int, _seqs(1, 45, seed=11)[0]))
        fork = a[:32] + list(map(int, _seqs(1, 9, seed=12)[0]))
        n_logged = len(e.engine_stats()["tick_log"])
        outs = [e.generate(p, max_tokens=6) for p in (a, a, fork, a)]
        assert outs[0] == outs[1] == outs[3]
        assert _is_greedy(e, c, a, outs[0]) and _is_greedy(e, c, fork, outs[2])
        snap = e.allocator.snapshot()
        assert snap["reuse_hits"] > 0 and snap["cow_copies"] >= 1
        assert e.stats["prefix_hits"] >= 3
        with e._tick_lock:
            stats = e.engine_stats()
        state = stats["state"]
        assert state["kv_paged"] == e.cache.kv.size * 4 and \
            state["kv_window"] == state["recurrent"] == 0
        # what the store's arena is reserved by: layers x rows x width x f32
        assert e._state_bytes["kv_paged"] // e.num_blocks == 4 * 8 * 128 * 4
        fields = stats["tick_fields"]
        ticks = [dict(zip(fields, t)) for t in stats["tick_log"]][n_logged:]
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
    finally:
        e.shutdown()


def test_a_preempted_stream_equals_the_undisturbed_one():
    """A pool too small for two streams' growth: the younger is
    preempted mid-decode, its blocks freed, and its re-prefill of prompt
    + emitted tokens writes its latent rows again."""
    c = _config()
    e = _engine(c, num_blocks=12, max_burst=4, prefix_sharing=False)
    try:
        prompts = [list(map(int, _seqs(1, 30, seed=s)[0])) for s in (21, 22)]
        outs = [None, None]

        def run(i):
            outs[i] = e.generate(prompts[i], max_tokens=24)

        threads = [threading.Thread(target=run, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
            time.sleep(0.05)
        for t in threads:
            t.join(timeout=120)
        assert e.engine_stats()["preemptions"] >= 1
        assert all(len(o) == 24 for o in outs)
        assert all(_is_greedy(e, c, p, o) for p, o in zip(prompts, outs))
    finally:
        e.shutdown()


def test_a_shipped_frame_is_the_latent_rows_and_is_adopted():
    """`export_streams` gathers a decoding stream's blocks as one frame
    (1 pooled leaf, layers, blocks, block size, stored width),
    `import_prefix` of another engine scatters it into fresh blocks, and
    the prompt then hits the adopted prefix there and streams what the
    reference does; a frame of another geometry is turned away."""
    from burst_ahead_cases import park, run_until_done, submit, tick

    c = _config()
    src, dst = park(_engine(c, prefix_sharing=True)), \
        _engine(c, prefix_sharing=True)
    try:
        prompt = list(map(int, _seqs(1, 37, seed=31)[0]))
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
        assert _is_greedy(dst, c, ticket["tokens"], out)
        run_until_done(src, [req])
        assert _is_greedy(src, c, prompt, req.out_tokens)
    finally:
        src.shutdown()
        dst.shutdown()


def test_speculation_verifies_through_the_model_s_own_step():
    """`speculation_k` runs: `paged_verify_step` goes through
    `_served_forward`, so the candidates' latent rows are written and
    read as a chunk's are; the stream is the unspeculated twin's and
    some proposals are accepted."""
    c = _config()
    plain = _engine(c, max_burst=1)
    spec_e = _engine(c, max_burst=1, speculation_k=4)
    try:
        prompt = [100, 200] * 6
        want = plain.generate(prompt, max_tokens=24)
        assert spec_e.generate(prompt, max_tokens=24) == want
        assert _is_greedy(plain, c, prompt, want)
        # a repeated continuation is proposed and taken
        again = prompt + want
        assert spec_e.generate(again, max_tokens=12) \
            == plain.generate(again, max_tokens=12)
        assert spec_e.stats["spec_proposed"] > 0
    finally:
        plain.shutdown()
        spec_e.shutdown()


def test_streams_equal_the_step_reference_while_lanes_join_and_leave():
    """The run-ahead tick (tests/test_burst_ahead.py) on a model that
    brings its own pool and whose burst hands out the routed count."""
    from burst_ahead_cases import join_and_leave, park, ticks_of

    e = park(_engine(_config(), num_slots=8))
    try:
        join_and_leave(e)
        launched = [t for t in ticks_of(e) if t["lanes"]]
        for t in launched:      # lanes x 8 steps x 3 layers x top-3
            assert 0 < t["routed_here"] < t["lanes"] * 8 * 3 * 3
    finally:
        e.shutdown()


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
    dep = LLMDeployment("tiny-mla-moe", num_slots=2, max_len=64,
                        block_size=8, prefill_chunk=16, engine="paged")
    try:
        out = dep({"tokens": list(range(1, 20)), "max_tokens": 3})
        assert len(out["tokens"]) == 3
        state = dep.stats()["state"]
        assert state["kv_window"] == 0 and state["recurrent"] == 0
        assert state["kv_paged"] == dep.engine.cache.kv.size * 2   # bfloat16
        from ray_tpu.exceptions import KVMigrationError

        with pytest.raises(KVMigrationError):        # a k / v frame: not ours
            dep.adopt_kv(list(range(8)), np.zeros((2, 4, 1, 8, 2, 16)), 8)
        rows = np.zeros((1, 4, 1, 8, 128), np.float32)
        assert dep.adopt_kv(list(range(8)), rows, 8) == 1
    finally:
        dep.engine.shutdown()


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
    cfg = configs.get(name)
    own = getattr(cfg, "init_params", None)
    params = jax.eval_shape(
        lambda: own(jax.random.key(0)) if own
        else init_params(jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: decoding.init_sequence_state(
        cfg, 17, 8, num_slots=4, prefill_chunk=32))
    chunk, burst, _ = decoding.make_paged_engine_fns(cfg)
    by_slot = getattr(cfg, "state_by_slot", False)
    key = jax.eval_shape(lambda: jax.random.key(0))

    def arr(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    lanes = (arr(4, 8), arr(4), arr(4, dtype=jnp.bool_),
             arr(4, dtype=jnp.float32), key)
    if program == "chunk":
        lowered = chunk.lower(params, cache, arr(32), arr(8), arr(), arr(),
                              **({"slot": arr()} if by_slot else {}))
    elif program == "burst":
        lowered = burst.lower(params, cache, arr(4), *lanes, n_steps=4,
                              **({"slots": arr(4)} if by_slot else {}))
    elif program == "copy_block":
        lowered = jax.jit(decoding.copy_block).lower(cache, arr(), arr())
    else:
        lowered = decoding.make_paged_spec_fns(cfg).lower(
            params, cache, arr(4, 3), *lanes)
    digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
    assert digest == _LOWERED_AT_PR_39[(name, program)]


# -- the benchmark's comparison has teeth ------------------------------------
def _moe_config(monkeypatch, **change):
    prop = mla_moe.MLAMoEConfig.moe
    monkeypatch.setattr(mla_moe.MLAMoEConfig, "moe", property(
        lambda self: dataclasses.replace(prop.fget(self), **change)))


def _one_expert_fewer_a_token(monkeypatch):
    _moe_config(monkeypatch, top_k=2)


def _route_scale_dropped(monkeypatch):
    _moe_config(monkeypatch, route_scale=1.0)


def _softmax_for_the_sigmoid(monkeypatch):
    _moe_config(monkeypatch, scoring="softmax")


def _experts_with(monkeypatch, change):
    inner = mla_moe.moe_mlp_dropless
    monkeypatch.setattr(
        mla_moe, "moe_mlp_dropless",
        lambda h, params, *a, **kw: inner(h, change(params), *a, **kw))


def _bias_dropped_from_the_selection(monkeypatch):
    _experts_with(monkeypatch, lambda p: dict(
        p, router_bias=jnp.zeros_like(p["router_bias"])))


def _one_held_expert_dropped(monkeypatch):
    _experts_with(monkeypatch, lambda p: dict(
        p, w_down=p["w_down"].at[:, 1].set(0)))


def _roped_score_dropped(monkeypatch):
    inner = mla_moe._queries

    def no_rope_part(*a):
        q_n, q_r, cq = inner(*a)
        return q_n, jnp.zeros_like(q_r), cq

    monkeypatch.setattr(mla_moe, "_queries", no_rope_part)


def _latent_norm_dropped(monkeypatch):
    dropped = object()            # stands where the latent's gain stood
    inner, norm = mla_moe._latent_row, mla_moe.rms_norm
    monkeypatch.setattr(mla_moe, "_latent_row", lambda ap, *a: inner(
        dict(ap, kv_norm=dropped), *a))
    monkeypatch.setattr(mla_moe, "rms_norm", lambda x, gain, eps:
                        x if gain is dropped else norm(x, gain, eps=eps))


def _pool_in_8_bit_floats(monkeypatch):
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
def test_logits_check_has_teeth(fault, monkeypatch):
    """`deployment.logits_check` (3 lanes x (the last of 100 prompt
    positions + 8 decode steps), bfloat16 compute and pool as the
    benchmark's configuration has them, the program's routing handed
    over, held to the family's own tolerances) passes the program as it
    is and fails each fault of ISSUE 40's list."""
    from bench.harness.deployment import logits_check

    c = _config(param_dtype="bfloat16", compute_dtype="bfloat16",
                cache_dtype="bfloat16")
    if fault:
        fault(monkeypatch)
    fam = spec.family(c)
    e = _engine(c)
    try:
        v = logits_check(e, c, SEED)
    finally:
        e.shutdown()
    assert v["positions"] == 27
    assert v["bound"] == fam.TOLERANCES["LOGITS_REL_EXPERTS"]
    if fault is None:
        assert v["ok"] and v["decided"] == 27, v
    else:
        assert not v["ok"], v


def test_a_pool_in_8_bit_floats_shows_in_float32_arithmetic(monkeypatch):
    """With everything else in float32 a latent row rounded to 8-bit
    floats is far over the engine's own error of 1e-6, in the chunk and
    in the decode steps."""
    monkeypatch.setattr(
        mla_moe, "_latent_row", (lambda inner: lambda *a: inner(*a).astype(
            jnp.float8_e4m3fn).astype(jnp.float32))(mla_moe._latent_row))
    c = _config()
    e = _engine(c)
    try:
        errs = _errors(e, c, _seqs(2, 100 + 6), 100)
        assert errs.min() > 5 * EXACT, errs
    finally:
        e.shutdown()
