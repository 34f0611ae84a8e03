"""Generation by diffusion over blocks on the served path
(`TransformerConfig.diffusion_block`: a position sees to the end of its
block of 4, a row's logits predict its own position, a sequence grows a
block at a time in denoising passes and a commit), held to the sdar
family's plain float32 reference (`bench/families/sdar.py`, which imports
nothing of the program): chunked prefill of whole blocks and denoise
bursts through a real `PagedLLMEngine`.  Tiny widths, seeded weights,
float32 throughout, so that the engine's greedy tokens are the
reference's to the token and its logits to 1e-5."""
import dataclasses
import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench.harness import spec  # noqa: E402
from ray_tpu.models import configs, decoding, init_params  # noqa: E402
from ray_tpu.models.transformer import forward  # noqa: E402
from ray_tpu.serve.llm import LLMDeployment, PagedLLMEngine  # noqa: E402

TINY = os.path.join(ROOT, "bench", "tests", "data", "sdarfamily",
                    "configs", "tinysdar-serve.json")
CFG = configs.get("tiny-block-diffusion-moe")
SEED = 3
EXACT = 1e-5          # float32 engine against float32 reference
B, MASK = CFG.diffusion_block, CFG.mask_token_id


def _config(steps=2):
    with open(TINY) as f:
        c = json.load(f)
    c["assumed"]["denoise_steps"] = steps
    return c


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(SEED), CFG)


def _engine(params, steps=2, **over):
    kw = dict(num_slots=4, max_len=128, block_size=8, prefill_chunk=32,
              max_burst=8)
    kw.update(over)
    return PagedLLMEngine(dataclasses.replace(CFG, denoise_steps=steps),
                          params, **kw)


def _reference(params, prompt, max_tokens, steps=2, eos_id=None):
    fam = spec.family(_config())
    with jax.default_matmul_precision("highest"):
        return fam.generate_reference(
            params, prompt, max_tokens, _config(steps), eos_id=eos_id,
            jit=jax.jit, pad_to=128)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 499, n).tolist()


def test_the_preset_is_the_family_s_tiny_configuration():
    fam = spec.family(_config())
    assert fam.program_config(_config()) == dataclasses.replace(
        CFG, name="tinysdar-serve")
    big = configs.get("sdar-30b-a3b")
    assert round(big.num_params / 1e7) == 3053        # the published "30B"


# -- (a) the engine's tokens are the reference's ----------------------------------
@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("prompt_len", [3, 8, 9, 10, 11])
def test_greedy_tokens_are_the_reference_s(params, steps, prompt_len):
    """Every residue of the prompt mod 4 (and a prompt shorter than a
    block: nothing to prefill), T in {1, 2, 4}; 13 tokens end inside a
    block for all of them."""
    prompt = _prompt(prompt_len, seed=prompt_len)
    want, passes = _reference(params, prompt, 13, steps)
    eng = _engine(params, steps)
    try:
        got = eng.generate(prompt, max_tokens=13, timeout=180)
        stats = eng.engine_stats()
    finally:
        eng.shutdown()
    assert got == want and len(got) == 13
    blocks = -(-(prompt_len % B + 13) // B)
    assert passes == blocks * (steps + 1)
    # the engine's bursts are two blocks each, all of them counted
    assert stats["blocks"] == 2 * -(-blocks // 2)
    assert stats["passes"] == stats["blocks"] * (steps + 1)
    assert stats["block_tokens"] == 13 == stats["tokens_generated"]
    ticks = [dict(zip(stats["tick_fields"], t)) for t in stats["tick_log"]]
    assert stats["tick_fields"][-4:-1] == ("blocks", "passes",
                                           "block_tokens")
    assert sum(t["block_tokens"] for t in ticks) == 13
    assert {t["passes"] for t in ticks if t["lanes"]} == {2 * (steps + 1)}
    assert all(0 < t["experts_read"] <= 8 for t in ticks if t["lanes"])


def test_a_pass_s_rows_grouped_by_expert_give_the_same_tokens(
        params, monkeypatch):
    """At the published widths a pass of 16 rows or more groups its rows by
    expert (`MoEConfig.grouped_from_rows` = 16; the tiny preset's 8
    experts stay under the products' floor, which this test lowers): the
    same tokens as the visit's, to the token."""
    from ray_tpu.ops import moe

    assert CFG.moe.grouped_from_rows == 16
    assert configs.get("tiny-moe").moe.grouped_from_rows == 0
    assert moe.grouped_tile_rows(32, configs.get("sdar-30b-a3b").moe) == 16
    assert moe.grouped_tile_rows(16, configs.get("sdar-30b-a3b").moe) == 16
    assert moe.grouped_tile_rows(32, configs.get("mellum2-12b").moe) == 0
    assert moe.grouped_tile_rows(16, CFG.moe) == 0
    monkeypatch.setattr(moe, "_GROUPED_FROM_PRODUCTS", 0)
    assert moe.grouped_tile_rows(16, CFG.moe) == 16
    prompt = _prompt(10, seed=3)
    want, _ = _reference(params, prompt, 13)
    eng = _engine(params)
    try:
        assert eng.generate(prompt, max_tokens=13, timeout=180) == want
    finally:
        eng.shutdown()


@pytest.mark.parametrize("at", [1, 2, 6])
def test_an_end_token_inside_a_block_ends_the_stream_there(params, at):
    prompt = _prompt(10, seed=7)
    free, _ = _reference(params, prompt, 12)
    eos = free[at]
    want, _ = _reference(params, prompt, 12, eos_id=eos)
    assert want == free[:free.index(eos) + 1] and len(want) < 12
    eng = _engine(params, eos_id=eos)
    try:
        assert eng.max_burst == B          # an end token holds a burst to 4
        assert eng.generate(prompt, max_tokens=12, timeout=180) == want
        assert eng.allocator.snapshot()["blocks_active"] == 0
    finally:
        eng.shutdown()


def test_lanes_that_join_at_different_ticks(params):
    """A second request joins while the first is some bursts in: each
    stream is its own reference's, whatever the other lane holds."""
    prompts = [_prompt(11, seed=1), _prompt(21, seed=2)]
    want = [_reference(params, p, n)[0] for p, n in zip(prompts, (40, 9))]
    eng = _engine(params)
    got = {}

    def late():
        got[1] = eng.generate(prompts[1], max_tokens=9, timeout=180)

    try:
        stream = eng.generate_stream(prompts[0], max_tokens=40, timeout=180)
        first = [next(stream) for _ in range(9)]
        t = threading.Thread(target=late)
        t.start()
        got[0] = first + list(stream)
        t.join(timeout=180)
        seen = [r["lanes_seen"] for r in eng.engine_stats()["request_phases"]]
    finally:
        eng.shutdown()
    assert got[0] == want[0] and got[1] == want[1]
    assert max(seen) > 1.0                 # they did share bursts


def test_a_prompt_that_holds_the_mask_token(params):
    """Which rows are open is carried as booleans: the mask id in a whole
    block of the prompt and as a given row stays what it is."""
    prompt = _prompt(10, seed=4)
    prompt[2] = prompt[9] = MASK
    want, _ = _reference(params, prompt, 10)
    eng = _engine(params)
    try:
        assert eng.generate(prompt, max_tokens=10, timeout=180) == want
    finally:
        eng.shutdown()


def test_a_registered_prefix_is_whole_pages_and_hits(params):
    prompt = _prompt(29, seed=5)     # three pages of 8, a block and a tail
    want, _ = _reference(params, prompt, 8)
    eng = _engine(params)
    try:
        assert eng.generate(prompt, max_tokens=8, timeout=180) == want
        assert eng.allocator.snapshot()["prefixes_registered"] == 3
        assert eng.stats["prefix_hits"] == 0
        assert eng.generate(prompt, max_tokens=8, timeout=180) == want
        assert eng.stats["prefix_hits"] == 1
        # 24 of its positions were shared: one launch of the block behind
        assert eng.stats["prefill_chunks"] == 2
        assert eng.stats["prefill_launch_tokens"][32] == 28 + 4
        whole = _prompt(16, seed=6)          # a prompt of whole pages
        first = eng.generate(whole, max_tokens=5, timeout=180)
        chunks = eng.stats["prefill_chunks"]
        assert eng.generate(whole, max_tokens=5, timeout=180) == first
        assert eng.stats["prefill_chunks"] == chunks      # nothing prefilled
        assert first == _reference(params, whole, 5)[0]
    finally:
        eng.shutdown()


def test_logits_check_through_the_engine_s_scoring_entry(params):
    """The benchmark's own comparison at the tiny size: eight whole blocks
    a lane behind a prefill of 96, seeded open rows, routing handed over."""
    from bench.harness.deployment import logits_check

    c = _config()
    fam = spec.family(c)
    eng = _engine(params, max_len=256)
    try:
        v = logits_check(eng, c, SEED)
        handed = list(fam._HANDED.values())
    finally:
        eng.shutdown()
    assert v["positions"] == 3 * 32 == v["decided"]
    assert v["ok"] and v["worst"] < EXACT, v
    assert v["bound"] == fam.TOLERANCES["LOGITS_REL_EXPERTS"]
    for h in handed:       # every count of open rows from 1 to 4, twice
        assert sorted(h["open"].sum(axis=1)) == [1, 1, 2, 2, 3, 3, 4, 4]
        assert h["clean"].shape == (128, 3, 4)
        assert h["noised"].shape == (32, 3, 4)


@pytest.mark.parametrize("fault", ["causal_mask", "kept_noised_kv",
                                   "no_commit"])
def test_the_comparison_has_teeth(params, fault, monkeypatch):
    """A causal mask inside a block, a block's K / V left from the pass
    that saw mask tokens, and a commit left out each fail the check."""
    from bench.harness.deployment import logits_check

    c = _config()
    eng = _engine(params, max_len=256)
    if fault == "causal_mask":
        real = decoding.paged_attention
        monkeypatch.setattr(
            decoding, "paged_attention",
            lambda *a, sees=None, **kw: real(*a, **kw))
    else:
        real = decoding.paged_block_pass
        calls = []

        def faulty(params_, cache, tokens, *a, **kw):
            calls.append(1)
            if len(calls) % 2 == 0:          # the commit of a block
                if fault == "no_commit":
                    out = real(params_, cache, tokens, *a, **kw)
                    return (cache, *out[1:])
                tokens = jnp.where(jnp.arange(B) == 1, MASK, tokens)
            return real(params_, cache, tokens, *a, **kw)

        monkeypatch.setattr(decoding, "paged_block_pass", faulty)
        # traced anew for every call, so that `calls` counts them
        eng._score_step = lambda *a, **kw: faulty(*a, cfg=eng.cfg, **kw)
    try:
        v = logits_check(eng, c, SEED)
    finally:
        eng.shutdown()
    assert not v["ok"], v
    assert not v["finite"] or v["worst_decided"] > v["bound"], v


# -- (b) a pass's K / V never survives ---------------------------------------------
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_the_pool_after_a_commit_is_a_clean_prefill_s(params, steps):
    cfg = dataclasses.replace(CFG, denoise_steps=steps)
    chunk, burst, _ = decoding.make_paged_engine_fns(cfg, donate=False)
    prompt = _prompt(18, seed=8)
    table = jnp.arange(1, 9, dtype=jnp.int32)            # 8 pages of 8

    def prefilled(tokens):
        cache = decoding.init_sequence_state(cfg, 9, 8, num_slots=1,
                                             prefill_chunk=32)
        toks = np.zeros((32,), np.int32)
        toks[:len(tokens)] = tokens
        cache, _ = chunk(params, cache, jnp.asarray(toks), table,
                         jnp.int32(0), jnp.int32(len(tokens)))
        return cache

    with jax.default_matmul_precision("highest"):
        cache = prefilled(prompt[:16])
        given = prompt[16:]
        toks = np.full((1, B), MASK, np.int32)
        toks[0, :2] = given
        still = np.array([[False, False, True, True]])
        cache, out, _, visited = burst(
            params, cache, jnp.asarray(toks), jnp.asarray(still),
            table[None], jnp.array([16], jnp.int32), jnp.array([True]),
            jnp.zeros((1,), jnp.float32), jax.random.key(0), n_blocks=2)
        out = np.asarray(out)[:, 0].reshape(-1)
        assert out[:2].tolist() == given
        assert out[2:].tolist() == _reference(params, prompt, 6, steps)[0]
        clean = prefilled(prompt[:16] + out.tolist())
    assert 0 < int(visited) <= 2 * (steps + 1) * cfg.n_layers * 8
    for got, want in ((cache.k, clean.k), (cache.v, clean.v)):
        np.testing.assert_allclose(np.asarray(got[:, 1:4]),
                                   np.asarray(want[:, 1:4]), atol=EXACT)
    assert float(jnp.abs(clean.k[:, 3]).max()) > 0.1     # positions 16 .. 23


# -- (c) the selection -------------------------------------------------------------
def _logits_with(conf):
    """(1, 4, 16) logits whose row i has arg-max i + 1 at soft-max
    probability conf[i]."""
    out = np.zeros((1, B, 16), np.float32)
    for i, p in enumerate(conf):
        out[0, i, i + 1] = np.log(p / (1 - p) * 15)
    return jnp.asarray(out)


@pytest.mark.parametrize("conf,still,n_fill,want", [
    # the two most confident of four open rows
    ([0.3, 0.9, 0.5, 0.7], [1, 1, 1, 1], 2, [0, 1, 0, 1]),
    # ties go to the lower position
    ([0.5, 0.5, 0.5, 0.5], [1, 1, 1, 1], 2, [1, 1, 0, 0]),
    # a given row is never chosen, however confident
    ([0.99, 0.2, 0.3, 0.1], [0, 1, 1, 1], 2, [0, 1, 1, 0]),
    # fewer open rows than the pass fills: all of them, no more
    ([0.9, 0.8, 0.1, 0.7], [0, 0, 1, 0], 2, [0, 0, 1, 0]),
    # one a pass
    ([0.3, 0.9, 0.5, 0.7], [1, 0, 1, 1], 1, [0, 0, 0, 1]),
    # nothing open: nothing filled
    ([0.3, 0.9, 0.5, 0.7], [0, 0, 0, 0], 4, [0, 0, 0, 0]),
])
def test_denoise_select_fills_the_most_confident_open_rows(conf, still,
                                                           n_fill, want):
    x0, fill = decoding.denoise_select(
        _logits_with(conf), jnp.asarray([still], bool), jnp.int32(n_fill),
        jnp.zeros((1,), jnp.float32), jax.random.key(0))
    assert x0.tolist() == [[1, 2, 3, 4]]
    assert fill.astype(int).tolist() == [want]


def test_a_sampling_lane_fills_as_many_rows_as_a_greedy_one():
    logits = jnp.concatenate([_logits_with([0.3, 0.9, 0.5, 0.7])] * 2)
    x0, fill = decoding.denoise_select(
        logits, jnp.ones((2, B), bool), jnp.int32(2),
        jnp.asarray([0.0, 1.5]), jax.random.key(1))
    assert x0[0].tolist() == [1, 2, 3, 4]
    assert fill.sum(axis=1).tolist() == [2, 2]
    assert decoding._fills(dataclasses.replace(CFG, denoise_steps=3)) \
        == [2, 1, 1]


# -- (d) preemption ----------------------------------------------------------------
def test_pool_deadlock_preempts_and_resumes_to_the_same_tokens(params):
    prompts = [_prompt(9, seed=11), _prompt(10, seed=12)]
    kw = dict(num_slots=2, max_len=48, block_size=4, prefill_chunk=16,
              max_burst=4, prefix_sharing=False)
    want = [_reference(params, p, 16)[0] for p in prompts]
    eng = _engine(params, num_blocks=10, **kw)
    done = {}

    def run(key, prompt):
        done[key] = eng.generate(prompt, max_tokens=16, timeout=180)

    try:
        threads = [threading.Thread(target=run, args=(i, p))
                   for i, p in enumerate(prompts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert eng.stats["preemptions"] >= 1
        assert done[0] == want[0] and done[1] == want[1]
        assert eng.allocator.snapshot()["blocks_active"] == 0
    finally:
        eng.shutdown()


# -- (e) what is refused, and why --------------------------------------------------
@pytest.mark.parametrize("how,reason", [
    (dict(speculation_k=4), "verified against the logits of the next"),
    (dict(block_size=6), "whole blocks"),
    (dict(mesh="tp2"), "has not been shown to agree under a `tp` split"),
])
def test_the_engine_refuses_with_the_reason(params, how, reason):
    if how.get("mesh"):
        from jax.sharding import Mesh

        how = {"mesh": Mesh(np.array(jax.devices()[:1]), ("tp",))}
    with pytest.raises(ValueError, match=reason):
        _engine(params, **how)


@pytest.mark.parametrize("how", [dict(tensor_parallel=2), dict(disagg=True)])
def test_the_deployment_refuses_a_mesh_and_prefill_offload(how):
    with pytest.raises(ValueError, match="diffusion over blocks"):
        LLMDeployment("tiny-block-diffusion-moe", **how)


def test_frames_and_the_offline_path_are_refused(params):
    eng = _engine(params)
    try:
        with pytest.raises(ValueError, match="diffusion over blocks"):
            eng.export_streams()
        with pytest.raises(ValueError, match="diffusion over blocks"):
            eng.import_prefix([1, 2, 3], np.zeros((2, 3, 1, 8, 2, 16)), 8)
    finally:
        eng.shutdown()
    with pytest.raises(ValueError, match="diffusion_block"):
        forward(params, jnp.zeros((1, 8), jnp.int32), CFG)
    with pytest.raises(ValueError, match="denoise_steps"):
        dataclasses.replace(CFG, denoise_steps=5)
    with pytest.raises(ValueError, match="ring"):
        dataclasses.replace(CFG, layer_pattern=("window", "full"),
                            n_layers=4, window=8)


# -- (f) the stream's count and the first token's mark -----------------------------
@pytest.mark.parametrize("max_tokens", [1, 7, 8, 30])
def test_a_stream_is_max_tokens_long_and_starts_at_the_first_burst(
        params, max_tokens):
    prompt = _prompt(37, seed=13)
    eng = _engine(params)
    try:
        got = list(eng.generate_stream(prompt, max_tokens=max_tokens,
                                       timeout=180))
        stats = eng.engine_stats()
    finally:
        eng.shutdown()
    assert len(got) == max_tokens
    assert got == _reference(params, prompt, max_tokens)[0]
    (rec,) = stats["request_phases"]
    assert abs(rec["queue_wait_s"] + rec["prefill_wait_s"]
               + rec["prefill_span_s"] - rec["ttft_s"]) < 1e-9
    assert rec["n_out"] == max_tokens
    assert abs(rec["burst_read_s"] + rec["first_read_s"] + rec["host_s"]
               - rec["decode_s"]) < 1e-6
    ticks = [dict(zip(stats["tick_fields"], t)) for t in stats["tick_log"]]
    bursts = [t for t in ticks if t["lanes"]]
    # one launch of 36 rows prefilled the whole blocks, nothing was sampled
    # from it, and the first token is marked after the first burst's launch
    assert sum(t["prefill_tokens"] for t in ticks) == 36
    assert all(t["sample_s"] == 0.0 for t in ticks)
    assert rec["submitted"] + rec["ttft_s"] > bursts[0]["start"]
    assert stats["phase_seconds"]["first_read"] == 0.0
    assert len(bursts) == -(-(1 + max_tokens) // 8)


# -- (g) the models that share the stack lower to the programs they lowered to -----
# sha256 of the StableHLO text (`lowered.as_text()`: no locations) of the
# served programs, taken on PR 51's tree (commit e02fa6e, this PR's parent:
# `git archive` of it unpacked beside this one, the lowering below run
# there under `JAX_PLATFORMS=cpu`) with the shapes below: `sees` in
# `paged_attention` and `_paged_forward`, the three fields of
# `TransformerConfig`, `MoEConfig.grouped_from_rows` and the burst of the
# model's kind in `make_paged_engine_fns` leave the Mistral-, Mixtral-,
# Mellum- and Laguna-shaped presets' programs as they were, to the letter:
# their compiled programs come from the cache as before.  (A verify step
# takes no slots, so the two presets with rings have none.)
_LOWERED_AT_PR_51 = {
    ("tiny", "chunk"): "26d36df597e7b123",
    ("tiny", "burst"): "78168c857a908d81",
    ("tiny", "verify"): "538c3a832dadb406",
    ("tiny-moe", "chunk"): "aad04f48fab2b38e",
    ("tiny-moe", "burst"): "303a001c72da4788",
    ("tiny-moe", "verify"): "0c22336be418db07",
    ("tiny-window-moe", "chunk"): "712251b4d56b0178",
    ("tiny-window-moe", "burst"): "f1575631141e1dd7",
    ("tiny-gated-moe", "chunk"): "d6f91f877ff3360b",
    ("tiny-gated-moe", "burst"): "1e265666f0b25dc5",
}


@pytest.mark.parametrize("name,program", list(_LOWERED_AT_PR_51),
                         ids=lambda v: str(v))
def test_the_other_presets_lower_as_at_the_parent(name, program):
    import hashlib

    cfg = configs.get(name)
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: decoding.init_sequence_state(
        cfg, 33, 16, num_slots=8, prefill_chunk=64))
    chunk, burst, _ = decoding.make_paged_engine_fns(cfg)
    by_slot = cfg.state_by_slot
    key = jax.eval_shape(lambda: jax.random.key(0))

    def arr(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    lanes = (arr(8, 16), arr(8), arr(8, dtype=jnp.bool_))
    if program == "chunk":
        lowered = chunk.lower(shapes, cache, arr(64), arr(16), arr(), arr(),
                              **({"slot": arr()} if by_slot else {}))
    elif program == "burst":
        lowered = burst.lower(
            shapes, cache, arr(8), *lanes, arr(8, dtype=jnp.float32), key,
            n_steps=8, **({"slots": arr(8)} if by_slot else {}))
    else:
        lowered = decoding.make_paged_spec_fns(cfg).lower(
            shapes, cache, arr(8, 4), *lanes, arr(8, dtype=jnp.float32), key)
    digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
    assert digest == _LOWERED_AT_PR_51[(name, program)]
