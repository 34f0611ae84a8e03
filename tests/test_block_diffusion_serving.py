"""Generation by diffusion over blocks on the served path
(`TransformerConfig.diffusion_block`: a position sees to the end of its
block of 4, a row's logits predict its own position, a sequence grows a
block at a time in denoising passes and a commit), held to the sdar
family's plain float32 reference (`bench/families/sdar.py`, which imports
nothing of the program): chunked prefill of whole blocks and denoise
bursts.  Float32 throughout, so that the engine's greedy tokens are the
reference's to the token and its logits to 1e-5.  The helpers and the
file's engines are `tests/served_contract.py`'s."""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_contract as contract
from burst_ahead_cases import park, run_until_done, submit, tick
from ray_tpu.models import configs, decoding
from ray_tpu.models.transformer import forward
from ray_tpu.serve.llm import LLMDeployment
from served_contract import Family, Since

FAM = Family(
    tiny="sdarfamily/configs/tinysdar-serve.json",
    registry="tiny-block-diffusion-moe", as_registry={},
    published=("sdar-30b-a3b", 1e7, 3053),            # the published "30B"
    own_init=False, seed=3, exact=1e-5, engine=dict(max_len=128))
CFG = configs.get(FAM.registry)
SEED, EXACT = FAM.seed, FAM.exact
B, MASK = CFG.diffusion_block, CFG.mask_token_id
ASSUMED = FAM.config()["assumed"]
engines, _ = contract.fixtures(FAM)


def _steps(steps):
    """The configuration's override for `steps` denoising passes a block."""
    return {"assumed": dict(ASSUMED, denoise_steps=steps)}


@pytest.fixture(scope="module")
def params():
    return FAM.params(CFG)


def _reference(params, prompt, max_tokens, steps=2, eos_id=None):
    c = FAM.config(**_steps(steps))
    with jax.default_matmul_precision("highest"):
        return FAM.reference(c).generate_reference(
            params, prompt, max_tokens, c, eos_id=eos_id,
            jit=contract.jit, pad_to=128)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 499, n).tolist()


def test_the_preset_is_the_family_s_tiny_configuration():
    contract.tiny_configuration_is_the_registry_s(FAM)
    contract.published_parameter_count(FAM)


# -- (a) the engine's tokens are the reference's ----------------------------------
@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("prompt_len", [3, 8, 9, 10, 11])
def test_greedy_tokens_are_the_reference_s(engines, params, steps,
                                           prompt_len):
    """Every residue of the prompt mod 4 (and a prompt shorter than a
    block: nothing to prefill), T in {1, 2, 4}; 13 tokens end inside a
    block for all of them."""
    prompt = _prompt(prompt_len, seed=prompt_len)
    want, passes = _reference(params, prompt, 13, steps)
    eng, _ = engines(config=_steps(steps))
    since = Since(eng)
    got = eng.generate(prompt, max_tokens=13, timeout=180)
    stats = since.stats()
    assert got == want and len(got) == 13
    blocks = -(-(prompt_len % B + 13) // B)
    assert passes == blocks * (steps + 1)
    # the engine's bursts are two blocks each, all of them counted
    assert stats["blocks"] == 2 * -(-blocks // 2)
    assert stats["passes"] == stats["blocks"] * (steps + 1)
    assert stats["block_tokens"] == 13 == stats["tokens_generated"]
    ticks = since.ticks(stats)
    assert stats["tick_fields"][-4:-1] == ("blocks", "passes",
                                           "block_tokens")
    assert sum(t["block_tokens"] for t in ticks) == 13
    assert {t["passes"] for t in ticks if t["lanes"]} == {2 * (steps + 1)}
    assert all(0 < t["experts_read"] <= 8 for t in ticks if t["lanes"])


def test_a_pass_s_rows_grouped_by_expert_give_the_same_tokens(
        engines, params, monkeypatch):
    """At the published widths a pass of 16 rows or more groups its rows by
    expert (`MoEConfig.grouped_from_rows` = 16; the tiny preset's 8
    experts stay under the products' floor, which this test lowers, for
    an engine of its own): the same tokens as the visit's, to the token."""
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
    with engines.private() as (eng, _):
        assert eng.generate(prompt, max_tokens=13, timeout=180) == want


@pytest.mark.parametrize("at", [1, 2, 6])
def test_an_end_token_inside_a_block_ends_the_stream_there(engines, params,
                                                           at):
    prompt = _prompt(10, seed=7)
    free, _ = _reference(params, prompt, 12)
    eos = free[at]
    want, _ = _reference(params, prompt, 12, eos_id=eos)
    assert want == free[:free.index(eos) + 1] and len(want) < 12
    eng, _ = engines(eos_id=eos)
    assert eng.max_burst == B          # an end token holds a burst to 4
    assert eng.generate(prompt, max_tokens=12, timeout=180) == want
    assert eng.allocator.snapshot()["blocks_active"] == 0


def test_lanes_that_join_at_different_ticks(engines, params):
    """A second request joins while the first is some bursts in: each
    stream is its own reference's, whatever the other lane holds.  (The
    case ticks the parked engine itself: a thread that joined by the clock
    had to be admitted before the first stream's last 31 tokens were out,
    which a loaded machine does not promise: ROADMAP D12 (k).)"""
    prompts = [_prompt(11, seed=1), _prompt(21, seed=2)]
    want = [_reference(params, p, n)[0] for p, n in zip(prompts, (40, 9))]
    eng, _ = engines()
    since = Since(park(eng))
    first = submit(eng, prompts[0], 40)
    while len(first.out_tokens) < 9:
        tick(eng)
    assert not first.done.is_set()
    late = submit(eng, prompts[1], 9)
    run_until_done(eng, [first, late])
    seen = [r["lanes_seen"] for r in since.stats()["request_phases"]]
    assert [first.out_tokens, late.out_tokens] == want
    assert max(seen) > 1.0                 # they did share bursts


def test_a_prompt_that_holds_the_mask_token(engines, params):
    """Which rows are open is carried as booleans: the mask id in a whole
    block of the prompt and as a given row stays what it is."""
    prompt = _prompt(10, seed=4)
    prompt[2] = prompt[9] = MASK
    want, _ = _reference(params, prompt, 10)
    eng, _ = engines()
    assert eng.generate(prompt, max_tokens=10, timeout=180) == want


def test_a_registered_prefix_is_whole_pages_and_hits(engines, params):
    """(An engine of its own: the registry and the launches are counted
    from empty.)"""
    prompt = _prompt(29, seed=5)     # three pages of 8, a block and a tail
    want, _ = _reference(params, prompt, 8)
    with engines.private() as (eng, _):
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


def test_logits_check_through_the_engine_s_scoring_entry(engines):
    """The benchmark's own comparison at the tiny size: eight whole blocks
    a lane behind a prefill of 96, seeded open rows, routing handed over."""
    from bench.harness.deployment import logits_check

    eng, c = engines(max_len=256)
    fam = FAM.reference(c)
    v = logits_check(eng, c, SEED)
    handed = list(fam._HANDED.values())
    assert v["positions"] == 3 * 32 == v["decided"]
    assert v["ok"] and v["worst"] < EXACT, v
    assert v["bound"] == fam.TOLERANCES["LOGITS_REL_EXPERTS"]
    for h in handed:       # every count of open rows from 1 to 4, twice
        assert sorted(h["open"].sum(axis=1)) == [1, 1, 2, 2, 3, 3, 4, 4]
        assert h["clean"].shape == (128, 3, 4)
        assert h["noised"].shape == (32, 3, 4)


@pytest.mark.parametrize("fault", ["causal_mask", "kept_noised_kv",
                                   "no_commit"])
def test_the_comparison_has_teeth(engines, fault, monkeypatch):
    """A causal mask inside a block, a block's K / V left from the pass
    that saw mask tokens, and a commit left out each fail the check (an
    engine of its own: its scoring programs are traced with the fault)."""
    from bench.harness.deployment import logits_check

    with engines.private(max_len=256) as (eng, c):
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
        v = logits_check(eng, c, SEED)
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
def test_pool_deadlock_preempts_and_resumes_to_the_same_tokens(engines,
                                                               params):
    prompts = [_prompt(9, seed=11), _prompt(10, seed=12)]
    want = [_reference(params, p, 16)[0] for p in prompts]
    eng, _ = engines(num_blocks=10, num_slots=2, max_len=48, block_size=4,
                     prefill_chunk=16, max_burst=4, prefix_sharing=False)
    since, done = Since(eng), {}

    def run(key, prompt):
        done[key] = eng.generate(prompt, max_tokens=16, timeout=180)

    threads = [threading.Thread(target=run, args=(i, p))
               for i, p in enumerate(prompts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert since.stats()["preemptions"] >= 1
    assert done[0] == want[0] and done[1] == want[1]
    assert eng.allocator.snapshot()["blocks_active"] == 0


# -- (e) what is refused, and why --------------------------------------------------
@pytest.mark.parametrize("how,reason", [
    (dict(speculation_k=4), "verified against the logits of the next"),
    (dict(block_size=6), "whole blocks"),
    (dict(mesh="tp2"), "has not been shown to agree under a `tp` split"),
])
def test_the_engine_refuses_with_the_reason(how, reason):
    if how.get("mesh"):
        from jax.sharding import Mesh

        how = {"mesh": Mesh(np.array(jax.devices()[:1]), ("tp",))}
    with pytest.raises(ValueError, match=reason):
        FAM.build(FAM.config(), **how)


@pytest.mark.parametrize("how", [dict(tensor_parallel=2), dict(disagg=True)])
def test_the_deployment_refuses_a_mesh_and_prefill_offload(how):
    with pytest.raises(ValueError, match="diffusion over blocks"):
        LLMDeployment("tiny-block-diffusion-moe", **how)


def test_frames_and_the_offline_path_are_refused(engines, params):
    eng, _ = engines()
    with pytest.raises(ValueError, match="diffusion over blocks"):
        eng.export_streams()
    with pytest.raises(ValueError, match="diffusion over blocks"):
        eng.import_prefix([1, 2, 3], np.zeros((2, 3, 1, 8, 2, 16)), 8)
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
        engines, params, max_tokens):
    prompt = _prompt(37, seed=13)
    eng, _ = engines()
    since = Since(eng)
    got = list(eng.generate_stream(prompt, max_tokens=max_tokens,
                                   timeout=180))
    stats = since.stats()
    assert len(got) == max_tokens
    assert got == _reference(params, prompt, max_tokens)[0]
    (rec,) = stats["request_phases"]
    assert abs(rec["queue_wait_s"] + rec["prefill_wait_s"]
               + rec["prefill_span_s"] - rec["ttft_s"]) < 1e-9
    assert rec["n_out"] == max_tokens
    assert abs(rec["burst_read_s"] + rec["first_read_s"] + rec["host_s"]
               - rec["decode_s"]) < 1e-6
    ticks = since.ticks(stats)
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
    assert contract.lowered_digest(name, program, **contract.WIDE_SHAPES) \
        == _LOWERED_AT_PR_51[(name, program)]
