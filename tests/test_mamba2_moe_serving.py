"""The state-space / attention hybrid with routed experts
(`ray_tpu.models.mamba2_moe`) on the served path, held to the
granitehybrid family's plain float32 reference
(`bench/families/granitehybrid.py`, which imports nothing of the program
and carries the recurrence a position at a time): prefill chunks, each
one chunk of the state-space-duality form from the slot's state, then
decode steps, through a real `PagedLLMEngine`; one rank's share of the
experts beside a shared expert.  Tiny widths, seeded weights, float32
compute where the claim is that the engine computes the same function
(errors of 1e-6), bfloat16 where it is that the benchmark's comparison
tells a fault from rounding."""
import dataclasses
import hashlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench.harness import reference, spec  # noqa: E402
from ray_tpu.models import configs, decoding, init_params, mamba2_moe  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.serve import llm  # noqa: E402
from ray_tpu.serve.llm import LLMDeployment, PagedLLMEngine  # noqa: E402

TINY = os.path.join(ROOT, "bench", "tests", "data", "granitefamily",
                    "configs", "tinygranite-serve.json")
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
        max_burst=eng["max_burst"], num_blocks=eng.get("num_blocks"))


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


@pytest.fixture(scope="module")
def served():
    c = _config()
    e = _engine(c)
    yield e, c
    e.shutdown()


def test_the_tiny_configuration_is_the_registry_s():
    c = _config()
    cfg = spec.family(c).program_config(c)
    assert cfg == dataclasses.replace(
        configs.get("tiny-mamba2-moe"), name=c["name"],
        compute_dtype=jnp.dtype("float32"))
    assert cfg.n_of("mamba") == 6 and cfg.n_of("attention") == 2
    assert cfg.moe.held == (4, 4) and cfg.moe.num_experts == 8


def test_published_sizes_give_the_published_parameter_count():
    cfg = configs.get("granite-4.0-h-small")
    assert cfg.layer_pattern.count("mamba") == 9 and cfg.periods == 4
    assert round(cfg.num_params / 1e8) == 322          # "32B" published
    shapes = jax.eval_shape(lambda: cfg.init_params(jax.random.key(0)))
    total = sum(x.size for x in jax.tree.leaves(shapes))
    # norms, conv rows and biases, dt_bias, A_log and D on top
    assert 0 < total - cfg.num_params < 0.001 * cfg.num_params


def test_a_held_range_must_lie_inside_the_experts():
    with pytest.raises(ValueError, match="held"):
        moe.MoEConfig(num_experts=8, top_k=2, held=(6, 4))
    with pytest.raises(ValueError, match="held"):
        dataclasses.replace(configs.get("tiny-mamba2-moe"),
                            experts_held=(0, 9))


# -- (a) chunks, then decode steps, against the full forward ---------------
def test_chunks_of_carried_state_then_decode(served):
    """100 prompt tokens in one launch of 128 rows: four chunks of 32,
    the state carried from chunk to chunk inside the program, the last
    4 valid of 32; then 10 decode steps on the lanes' state."""
    e, c = served
    errs = _errors(e, c, _seqs(3, 100 + 10), 100)
    assert errs.shape == (33,) and errs.max() < EXACT, errs


@pytest.fixture(scope="module")
def served_chunk64():
    c = _config()
    e = _engine(c, prefill_chunk=64)
    yield e, c
    e.shutdown()


@pytest.mark.parametrize("n_prompt", [64, 81, 97, 128])
def test_every_chunk_tier_and_a_padded_tail(served_chunk64, n_prompt):
    """prefill_chunk 64 has the tier 64 and, above it, 128 and 256
    (launches of two and four chunks): 64 = one whole chunk, 81 and 97 =
    a launch of 128 rows whose valid rows end inside its second chunk,
    128 = two whole.  The padded tail must not advance the recurrence."""
    e, c = served_chunk64
    assert e._chunk_tiers == [64, 128, 256]
    errs = _errors(e, c, _seqs(2, n_prompt + 4, seed=n_prompt), n_prompt)
    assert errs.max() < EXACT, errs


def _prefill(cfg, params, tokens, size, pad_with=0, chunk=32, blocks=8):
    """`tokens` through `paged_prefill_chunk` in launches of `size` rows
    (of `chunk` positions a chunk) on a state of its own, a table of
    `blocks` pages; a last launch is padded to `size` with `pad_with`.
    Returns (state, last logits)."""
    state = cfg.init_state(max(17, blocks + 1), 8, 2, chunk)
    chunk = jax.jit(decoding._bind_cfg(decoding.paged_prefill_chunk, cfg))
    table = jnp.arange(1, blocks + 1, dtype=jnp.int32)
    for start in range(0, len(tokens), size):
        toks = np.full((size,), pad_with, np.int32)
        nv = min(size, len(tokens) - start)
        toks[:nv] = tokens[start:start + nv]
        state, last, _ = chunk(params, state, jnp.asarray(toks), table,
                               jnp.int32(start), jnp.int32(nv),
                               slot=jnp.int32(1))
    return state, last


# A launch of 2Q rows after one of Q, its valid rows ending inside its
# first chunk, on the boundary, inside its second, and at its end.
@pytest.mark.parametrize("n_valid", [40, 64, 100, 128])
def test_a_launch_of_two_chunks_equals_two_launches(served_chunk64, n_valid):
    """Q = 64: 64 + `n_valid` tokens as launches of 64 rows, and as one
    of 64 and one of 128 (two chunks, the state handed on inside the
    program), leave the same conv rows and state in the slot and give
    the same last logits; padded rows hold a real token id."""
    e, _ = served_chunk64
    tokens = list(map(int, _seqs(1, 64 + n_valid, seed=n_valid)[0]))
    state, last = _prefill(e.cfg, e.params, tokens, 64, pad_with=9,
                           chunk=64, blocks=24)
    head, _ = _prefill(e.cfg, e.params, tokens[:64], 64, chunk=64, blocks=24)
    toks = np.full((128,), 9, np.int32)
    toks[:n_valid] = tokens[64:]
    chunk = jax.jit(decoding._bind_cfg(decoding.paged_prefill_chunk, e.cfg))
    wide, got, _ = chunk(e.params, head, jnp.asarray(toks),
                         jnp.arange(1, 25, dtype=jnp.int32), jnp.int32(64),
                         jnp.int32(n_valid), slot=jnp.int32(1))
    np.testing.assert_allclose(got, last, atol=EXACT)
    np.testing.assert_array_equal(wide.conv[:, 1], state.conv[:, 1])
    np.testing.assert_allclose(wide.h[:, 1], state.h[:, 1], atol=EXACT)
    assert not np.asarray(wide.h[:, 0]).any()        # nobody's slot


def test_chunk_sizes_one_three_and_whole_give_the_same_state(served):
    """48 positions a position at a time (the one-position recurrence),
    three at a time and as whole chunks of 32 (the duality form) leave
    the same recurrent state and the same last logits; what stands in a
    chunk's padded tail changes neither, to the bit; the null slot and
    the slot nobody had stay zero."""
    e, _ = served
    cfg, tokens = e.cfg, _seqs(1, 48, seed=7)[0]
    whole, last = _prefill(cfg, e.params, tokens, 32)
    for size in (1, 3):
        other, last_o = _prefill(cfg, e.params, tokens, size)
        for name in ("h", "conv"):
            np.testing.assert_allclose(
                np.asarray(getattr(other, name)),
                np.asarray(getattr(whole, name)), atol=2e-5, err_msg=name)
        np.testing.assert_allclose(np.asarray(last_o), np.asarray(last),
                                   atol=2e-5)
    junk, last_j = _prefill(cfg, e.params, tokens, 32, pad_with=77)
    assert np.array_equal(np.asarray(junk.h), np.asarray(whole.h))
    assert np.array_equal(np.asarray(junk.conv), np.asarray(whole.conv))
    assert np.array_equal(np.asarray(last_j), np.asarray(last))
    assert np.asarray(whole.h[:, 1]).any()
    assert not np.asarray(whole.h[:, (0, 2)]).any()
    assert not np.asarray(whole.conv[:, (0, 2)]).any()


def test_unequal_lanes_with_an_idle_lane_between(served):
    """The step the burst scans, over lanes 0 and 2 of different lengths
    with lane 1 idle and pointed at its own slot all the same: the live
    lanes' logits are the reference's, and the idle lane's slot and the
    null slot keep conv rows and state to the bit."""
    e, c = served
    seqs = [_seqs(1, 70, seed=1)[0], _seqs(1, 30, seed=2)[0],
            _seqs(1, 41, seed=3)[0]]
    step = jax.jit(decoding._bind_cfg(decoding.paged_decode_step, e.cfg))
    bs = e.block_size
    with e._tick_lock:
        tables = np.zeros((4, e._b_max), np.int32)
        for lane, seq in enumerate(seqs):
            per = -(-(len(seq) + 1) // bs)
            tables[lane, :per] = 1 + lane * 16 + np.arange(per)
            e.cache = e._reset_state(e.cache, jnp.int32(lane))
            for start in range(0, len(seq) - 1, e.prefill_chunk):
                toks = np.zeros((e.prefill_chunk,), np.int32)
                nv = min(e.prefill_chunk, len(seq) - 1 - start)
                toks[:nv] = seq[start:start + nv]
                e.cache, _, _ = e._prefill_chunk_fn(
                    e.params, e.cache, jnp.asarray(toks),
                    jnp.asarray(tables[lane]), jnp.int32(start),
                    jnp.int32(nv), slot=jnp.int32(lane))
        before = jax.tree.map(np.asarray, e.cache)
        lengths = np.array([69, 29, 40, 0], np.int32)
        active = np.array([True, False, True, False])
        cache, logits = step(
            e.params, e.cache, jnp.asarray([s[-1] for s in seqs] + [0],
                                           jnp.int32),
            jnp.asarray(tables), jnp.asarray(lengths), jnp.asarray(active),
            slots=jnp.asarray([0, 1, 2, e.num_slots], jnp.int32))
        after = jax.tree.map(np.asarray, cache)
    for lane in (0, 2):
        err = reference.position_errors(
            logits[lane][None], _want(e, c, seqs[lane])[-1:])
        assert float(err[0]) < EXACT
    for name in ("conv", "h"):
        for slot in (1, e.num_slots):
            assert np.array_equal(getattr(after, name)[:, slot],
                                  getattr(before, name)[:, slot]), name
    assert not np.array_equal(after.h[:, 0], before.h[:, 0])


def test_a_burst_equals_its_steps_and_counts_what_it_routed(served):
    e, _ = served
    cfg = e.cfg
    state = cfg.init_state(17, 8, 4, 32)
    tables = jnp.asarray(np.arange(1, 17, dtype=np.int32).reshape(4, 4))
    lengths = jnp.asarray([3, 0, 9, 1], jnp.int32)
    active = jnp.asarray([True, False, True, True])
    slots = jnp.asarray([2, 4, 0, 3], jnp.int32)
    toks = jnp.asarray([5, 0, 7, 9], jnp.int32)
    key = jax.random.key(0)
    burst = jax.jit(decoding._bind_cfg(decoding.paged_decode_burst, cfg),
                    static_argnames=("n_steps",))
    b_state, b_toks, _, visited, routed = burst(
        e.params, state, toks, tables, lengths, active,
        jnp.zeros((4,), jnp.float32), key, n_steps=3, slots=slots)
    step = jax.jit(decoding._bind_cfg(decoding.paged_decode_step, cfg),
                   static_argnames=("routing",))
    s_toks, here = [], 0
    for _ in range(3):
        state, logits, taken = step(e.params, state, toks, tables, lengths,
                                    active, slots=slots, routing=True)
        taken = np.asarray(taken)[:, np.asarray(active)]   # (L, live, k)
        here += int(np.sum((taken >= 4) & (taken < 8)))
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        lengths = jnp.where(active, lengths + 1, lengths)
        s_toks.append(toks)
    live = np.asarray(active)
    assert np.array_equal(np.asarray(b_toks)[:, live],
                          np.stack(s_toks)[:, live])
    for a, b in zip(jax.tree.leaves(b_state), jax.tree.leaves(state)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)
    assert not np.asarray(b_state.h[:, 1]).any()      # a slot no lane had
    # experts 4..7 are held here: the burst counted the choices on them
    assert int(routed) == here and 0 < here < 3 * 3 * 3 * cfg.n_layers
    assert 0 < int(visited) <= 4 * 3 * cfg.n_layers


# -- (b) the shares add up --------------------------------------------------
def _layer_inputs(cfg, params, li, rows=24):
    x = jax.random.normal(jax.random.key(3), (2, rows // 2, cfg.d_model),
                          jnp.float32)
    fp = {k: v[li] for k, v in params["ffn"].items()
          if k not in mamba2_moe._EXPERT_WEIGHTS}
    experts = {k: params["ffn"][k] for k in mamba2_moe._EXPERT_WEIGHTS}
    return x, fp, experts


def test_the_shares_add_up_to_the_uncut_layer():
    """A layer of 8 experts whole, and cut into the shares (0..3) and
    (4..7) with the same router: the routed parts of the two shares, plus
    the shared expert counted once, equal the uncut layer; experts
    visited and choices routed add up too.  In the program and in the
    reference alike, and the two agree."""
    c = _config(num_local_experts=8, first_local_expert=0)
    fam = spec.family(c)
    whole = dataclasses.replace(fam.program_config(c),
                                compute_dtype=jnp.float32)
    assert whole.experts_held is None
    params = whole.init_params(jax.random.key(SEED))
    li = 3
    x, fp, experts = _layer_inputs(whole, params, li)
    live = jnp.ones(x.shape[:2], bool)
    full, n_full, r_full, _ = mamba2_moe._ffn(fp, experts, li, x, live,
                                              whole, False)
    assert int(r_full) == x.shape[0] * x.shape[1] * whole.expert_top_k
    h = mamba2_moe.rms_norm(x, fp["norm"], eps=whole.norm_eps)
    shared = full - moe.moe_mlp_dropless(
        h, {"router": fp["router"], **experts}, whole.moe, layer=li)[0]
    parts, visited, routed = [], 0, 0
    ref_parts = []
    u = np.asarray(h).reshape(-1, whole.d_model)
    for first in (0, 4):
        cut = dataclasses.replace(whole, experts_held=(first, 4))
        held = {k: v[:, first:first + 4] for k, v in experts.items()}
        out, n, r, _ = mamba2_moe._ffn(fp, held, li, x, live, cut, False)
        parts.append(out - shared)
        visited, routed = visited + int(n), routed + int(r)
        c_cut = dict(c, num_local_experts=4, first_local_expert=first,
                     published={"num_local_experts": 8})
        ref_fp = {**fp, **{k: v[li] for k, v in held.items()}}
        ref_parts.append(fam.experts(jnp.asarray(u), ref_fp, None, c_cut)[0])
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] + shared),
                               np.asarray(full), atol=1e-5)
    assert visited == int(n_full) and routed == int(r_full)
    assert 0 < routed - int(r) < routed            # neither share is empty
    ref_fp = {**fp, **{k: v[li] for k, v in experts.items()}}
    ref_full = fam.experts(jnp.asarray(u), ref_fp, None, c)[0] \
        + fam.shared_expert(jnp.asarray(u), ref_fp)
    np.testing.assert_allclose(
        np.asarray(ref_parts[0] + ref_parts[1]
                   + fam.shared_expert(jnp.asarray(u), ref_fp)),
        np.asarray(ref_full), atol=1e-5)
    np.testing.assert_allclose(np.asarray(full).reshape(u.shape),
                               np.asarray(ref_full), atol=1e-5)


def test_an_idle_row_is_routed_nowhere():
    """`live` by row: a chunk's padded tail takes no expert, hits none,
    and is not counted."""
    cfg = configs.get("tiny-mamba2-moe")
    params = cfg.init_params(jax.random.key(0))
    x, fp, experts = _layer_inputs(cfg, params, 0)
    x = x.astype(cfg.compute_dtype)
    live = jnp.arange(x.shape[1])[None, :] < jnp.asarray([[5], [0]])
    out, n, r, _ = mamba2_moe._ffn(fp, experts, 0, x, live, cfg, False)
    _, n_all, r_all, _ = mamba2_moe._ffn(fp, experts, 0, x,
                                         jnp.ones_like(live), cfg, False)
    assert 0 < int(r) <= 5 * cfg.expert_top_k and int(r) < int(r_all)
    assert int(n) <= int(n_all)
    h = mamba2_moe.rms_norm(x, fp["norm"], eps=cfg.norm_eps)
    routed_part = moe.moe_mlp_dropless(
        h, {"router": fp["router"], **experts}, cfg.moe, live=live,
        layer=0)[0]
    assert not np.asarray(routed_part[1], np.float32).any()
    assert not np.asarray(routed_part[0, 5:], np.float32).any()


# -- (c) the engine's own scheduling ----------------------------------------
def _is_greedy(e, c, prompt, out):
    """`out` is the reference's greedy continuation of `prompt`: one
    full forward over both, whose argmax at every position from the
    prompt's last is the token that follows."""
    logits = _want(e, c, list(prompt) + list(out))
    return out == [int(t) for t in
                   jnp.argmax(logits[len(prompt) - 1:-1], axis=-1)]


def test_a_slot_reused_by_a_second_request_and_the_tick_log(served):
    e, c = served
    first = list(map(int, _seqs(1, 60, seed=11)[0]))
    second = list(map(int, _seqs(1, 45, seed=12)[0]))
    resets = e.engine_stats()["state"]["state_resets"]
    n_logged = len(e.engine_stats()["tick_log"])
    out1 = e.generate(first, max_tokens=6)
    out2 = e.generate(second, max_tokens=6)      # slot 0 again
    assert len(out1) == len(out2) == 6
    assert _is_greedy(e, c, first, out1) and _is_greedy(e, c, second, out2)
    with e._tick_lock:
        stats = e.engine_stats()
    assert stats["state"]["state_resets"] == resets + 2
    assert stats["state"]["recurrent"] == 6 * 5 * (
        8 * 16 * 8 * 4 + 3 * (8 * 16 + 2 * 8) * 4)
    assert stats["prefix_hits"] == 0
    fields = stats["tick_fields"]
    ticks = [dict(zip(fields, t)) for t in stats["tick_log"]][n_logged:]
    assert any(t["reset_s"] > 0 for t in ticks)
    one = [t for t in ticks if t["lanes"] == 1][-1]
    # one lane of length n: the two attention layers read its KV
    assert one["kv_read_tokens"] == e.cfg.kv_read_tokens([45]) == 2 * 45
    assert 0 < one["experts_read"] <= 3
    # what the prompts' rows routed to experts 4..7, by the program's own
    # routing of the same tokens
    _, taken = e.score(np.asarray([first[:45], second]), 45, routing=True)
    _, taken60 = e.score(np.asarray([first]), 60, routing=True)
    here = sum(int(np.sum(t >= 4)) for t in (taken60[0], taken[1]))
    prefill = [t for t in ticks if t["prefill_tokens"] and not t["lanes"]]
    assert sum(t["prefill_tokens"] for t in prefill) == 60 + 45
    assert sum(t["routed_here"] for t in prefill) == here
    total = 3 * 8 * (60 + 45)
    assert 0.3 * total < here < 0.7 * total


@pytest.fixture()
def grouping_chunk64(monkeypatch):
    """An engine of 64-row chunks that group their rows by expert: as at
    many experts (4 held ones would keep the visit up to 512 rows)."""
    monkeypatch.setattr(moe, "_GROUPED_FROM_PRODUCTS", 0)
    monkeypatch.setattr(llm, "_CHUNK_TOP_ROWS", 0)     # the tier 64 alone
    c = _config()
    e = _engine(c, prefill_chunk=64)
    yield e, c
    e.shutdown()


def test_the_tick_log_counts_the_tiles_a_wide_chunk_multiplied(
        grouping_chunk64):
    """A 64-row chunk groups its rows by expert and counts the tiles it
    multiplied (`moe_tiles` of the tick log): at this width a
    tile holds a whole group, so a tile a held expert that was hit in
    each of the 8 layers, and the rows routed here fit the tiles.  The
    prompt's last 20 tokens go in 64 rows too (this model has no tier
    under `prefill_chunk`) and count theirs; the bursts visit and count
    none."""
    e, c = grouping_chunk64
    tile = moe.grouped_tile_rows(64, e.cfg.moe)
    assert tile == 64 and not moe.grouped_tile_rows(32, e.cfg.moe)
    n_logged = len(e.engine_stats()["tick_log"])
    prompt = list(map(int, _seqs(1, 64 + 20, seed=31)[0]))
    out = e.generate(prompt, max_tokens=5)
    assert _is_greedy(e, c, prompt, out)
    with e._tick_lock:
        stats = e.engine_stats()
    assert stats["tick_fields"][-7] == "moe_tiles"
    ticks = [dict(zip(stats["tick_fields"], t))
             for t in stats["tick_log"]][n_logged:]
    wide = [t for t in ticks if t["prefill_tokens"] == 64]
    assert len(wide) == 1 and not wide[0]["lanes"]
    assert 8 * 1 <= wide[0]["moe_tiles"] <= 8 * 4
    assert 0 < wide[0]["routed_here"] <= wide[0]["moe_tiles"] * tile
    (last,) = [t for t in ticks if t["prefill_tokens"] == 20]
    assert 8 * 1 <= last["moe_tiles"] <= 8 * 4 and not last["lanes"]
    assert 0 < last["routed_here"] <= 20 * 3 * 8
    bursts = [t for t in ticks if t["lanes"]]
    assert bursts and all(t["moe_tiles"] == 0 and t["routed_here"] > 0
                          for t in bursts)


def test_a_preempted_stream_equals_the_undisturbed_one():
    """A pool too small for two streams' growth: the younger is
    preempted mid-decode, its state is zeroed with its lengths, and its
    re-prefill of prompt + emitted tokens rebuilds it."""
    c = _config()
    e = _engine(c, num_blocks=12, max_burst=4)
    try:
        prompts = [list(map(int, _seqs(1, 30, seed=s)[0])) for s in (21, 22)]
        outs = [None, None]

        def run(i):
            outs[i] = e.generate(prompts[i], max_tokens=24)

        import threading
        threads = [threading.Thread(target=run, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
            time.sleep(0.05)
        for t in threads:
            t.join(timeout=120)
        stats = e.engine_stats()
        assert stats["preemptions"] >= 1
        assert stats["state"]["state_rebuilds"] >= 1
        assert all(len(o) == 24 for o in outs)
        assert all(_is_greedy(e, c, p, o) for p, o in zip(prompts, outs))
    finally:
        e.shutdown()


def test_streams_equal_the_step_reference_while_lanes_join_and_leave():
    """The engine launches a burst before it has read the one before
    (tests/test_burst_ahead.py), here on slots that hold recurrent state
    and a model whose burst hands out one more count: requests of
    different lengths join and leave mid-stream, the tiers go 4, 8, 4, a
    slot changes hands while its last burst is unread, and every stream
    is the step-by-step reference's."""
    from burst_ahead_cases import join_and_leave, park, ticks_of

    e = park(_engine(_config(), num_slots=8))
    try:
        join_and_leave(e)
        launched = [t for t in ticks_of(e) if t["lanes"]]
        for t in launched:      # lanes x 8 steps x 8 layers x top-3
            assert 0 < t["routed_here"] < t["lanes"] * 8 * 8 * 3
    finally:
        e.shutdown()


# -- (d) what this model cannot have yet is refused --------------------------
def test_refusals():
    c = _config()
    cfg = spec.family(c).program_config(c)
    params = cfg.init_params(jax.random.key(0))
    with pytest.raises(ValueError, match="speculation_k"):
        PagedLLMEngine(cfg, params, num_slots=2, max_len=64, block_size=8,
                       prefill_chunk=16, speculation_k=4)
    e = PagedLLMEngine(cfg, params, num_slots=2, max_len=64, block_size=8,
                       prefill_chunk=16, prefix_sharing=True)
    try:
        assert not e.allocator.prefix_sharing     # off by itself
        prompt = list(range(1, 30))
        e.generate(prompt, max_tokens=2)
        e.generate(prompt, max_tokens=2)
        assert e.stats["prefix_hits"] == 0
        with pytest.raises(ValueError, match="export_streams"):
            e.export_streams()
        with pytest.raises(ValueError, match="import_prefix"):
            e.import_prefix(prompt, np.zeros((2, 2, 4, 8, 2, 16)), 8)
    finally:
        e.shutdown()
    for kw in ({"disagg": True}, {"tensor_parallel": 2}):
        with pytest.raises(ValueError, match="recurrent state"):
            LLMDeployment(cfg, num_slots=2, max_len=64, **kw)
    with pytest.raises(ValueError, match="mesh"):
        from jax.sharding import Mesh
        PagedLLMEngine(cfg, params, num_slots=2, max_len=64, block_size=8,
                       prefill_chunk=16,
                       mesh=Mesh(np.array(jax.devices()[:1]), ("tp",)))


def test_deployment_takes_the_configuration_by_name():
    dep = LLMDeployment("tiny-mamba2-moe", num_slots=2, max_len=64,
                        block_size=8, prefill_chunk=16, engine="paged")
    try:
        assert dep._disagg is None
        out = dep({"tokens": list(range(1, 20)), "max_tokens": 3})
        assert len(out["tokens"]) == 3
        with pytest.raises(ValueError, match="import_prefix"):
            dep.adopt_kv(list(range(8)), np.zeros((2, 2, 1, 8, 2, 16)), 8)
        state = dep.stats()["state"]
        assert state["kv_window"] == 0 and state["recurrent"] > 0
        assert state["kv_paged"] > 0
    finally:
        dep.engine.shutdown()


# -- (e) the other models lower to the programs they lowered to --------------
# sha256 of the StableHLO text of the served programs, taken on PR 35's
# tree (commit dab40ad) with the shapes below: the held range in
# `MoEConfig`, `live` by row, the scale argument of `paged_attention`, the
# routed count and the fifth value of `_served_forward` leave them as they
# were, to the letter.  (`tiny-moe` is held by tests/
# test_window_moe_serving.py since PR 34, and still is.)
_LOWERED_AT_PR_35 = {
    ("tiny-moe", "chunk"): "46948ed989777a6e",
    ("tiny-moe", "burst"): "b9dd8b33061cf86f",
    ("tiny-window-moe", "chunk"): "c240418b7a1b34bd",
    ("tiny-window-moe", "burst"): "04989cf7fe6f4582",
    ("tiny-hybrid", "chunk"): "cdfa4237730d621f",
    ("tiny-hybrid", "burst"): "ba0b5af0011aa764",
}


@pytest.mark.parametrize("name,program", list(_LOWERED_AT_PR_35),
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

    def arr(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    if program == "chunk":
        lowered = chunk.lower(params, cache, arr(32), arr(8), arr(), arr(),
                              **({"slot": arr()} if by_slot else {}))
    else:
        lowered = burst.lower(
            params, cache, arr(4), arr(4, 8), arr(4),
            arr(4, dtype=jnp.bool_), arr(4, dtype=jnp.float32),
            jax.eval_shape(lambda: jax.random.key(0)), n_steps=4,
            **({"slots": arr(4)} if by_slot else {}))
    digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
    assert digest == _LOWERED_AT_PR_35[(name, program)]


# -- the benchmark's comparison has teeth ------------------------------------
def _padded_tail_advances(monkeypatch):
    inner = mamba2_moe._mamba2
    monkeypatch.setattr(
        mamba2_moe, "_mamba2", lambda bp, x, conv, h, valid, *a:
        inner(bp, x, conv, h, jnp.ones_like(valid), *a))


def _usual_attention_scale(monkeypatch):
    inner = mamba2_moe.paged_attention
    monkeypatch.setattr(
        mamba2_moe, "paged_attention", lambda *a, scale=None: inner(*a))


def _residual_multiplier_dropped(monkeypatch):
    inner = mamba2_moe._served_step
    monkeypatch.setattr(
        mamba2_moe, "_served_step", lambda *a: inner(
            *a[:7], dataclasses.replace(a[7], residual_multiplier=1.0),
            *a[8:]))


def _one_expert_fewer_a_token(monkeypatch):
    prop = mamba2_moe.Mamba2MoEConfig.moe
    monkeypatch.setattr(
        mamba2_moe.Mamba2MoEConfig, "moe", property(
            lambda self: dataclasses.replace(
                prop.fget(self), top_k=self.expert_top_k - 1)))


def _one_held_expert_dropped(monkeypatch):
    inner = mamba2_moe.moe_mlp_dropless

    def dropped(h, params, *a, **kw):
        return inner(h, dict(params, w_down=params["w_down"].at[:, 1].set(0)),
                     *a, **kw)

    monkeypatch.setattr(mamba2_moe, "moe_mlp_dropless", dropped)


@pytest.mark.parametrize("fault", [
    None, _padded_tail_advances, _usual_attention_scale,
    _residual_multiplier_dropped, _one_expert_fewer_a_token,
    _one_held_expert_dropped],
    ids=lambda f: f.__name__.strip("_") if f else "as_it_is")
def test_logits_check_has_teeth(fault, monkeypatch):
    """`deployment.logits_check` (3 lanes x (the last of 100 prompt
    positions + 8 decode steps), bfloat16 compute and cache as the
    benchmark's configuration has them, the program's routing handed
    over, held to the family's own ROUTER_SLACK and to twice its
    LOGITS_REL_EXPERTS: a width of 64 rounds more than one of 4096, the
    program as it is reads up to 0.031 here and 0.0235 on the chip)
    passes the program as it is and fails each fault."""
    from bench.harness.deployment import logits_check

    c = _config(param_dtype="bfloat16", compute_dtype="bfloat16",
                cache_dtype="bfloat16")
    if fault:
        fault(monkeypatch)
    fam = spec.family(c)
    monkeypatch.setitem(fam.TOLERANCES, "LOGITS_REL_EXPERTS",
                        2 * fam.TOLERANCES["LOGITS_REL_EXPERTS"])
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


def test_state_kept_in_bfloat16_shows_in_float32_arithmetic():
    """With everything else in float32 a recurrent state kept in
    bfloat16 is far over the engine's own error of 1e-6 from the first
    position that reads what a launch left in the slot: every decode
    step (the prompt's last position is inside its one launch, where
    the state is handed on in float32)."""
    e = _engine(_config(state_dtype="bfloat16"))
    try:
        assert e.cache.h.dtype == jnp.bfloat16
        errs = _errors(e, _config(), _seqs(2, 100 + 6), 100).reshape(2, 7)
        assert errs[:, 0].max() < EXACT < 5 * EXACT < errs[:, 1:].min(), errs
    finally:
        e.shutdown()


# -- the served path: serve.run -> proxy -> handle -> replica -> engine -----
def test_served_through_the_front_like_any_model():
    import urllib.request

    import ray_tpu
    from ray_tpu import serve

    cfg = configs.get("tiny-mamba2-moe")
    prompt = list(range(3, 40))
    twin = PagedLLMEngine(cfg, cfg.init_params(jax.random.key(0)),
                          num_slots=2, max_len=128, block_size=8,
                          prefill_chunk=16)
    try:
        want = twin.generate(prompt, max_tokens=10)
    finally:
        twin.shutdown()
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    try:
        serve.run(serve.deployment(LLMDeployment).bind(
            "tiny-mamba2-moe", num_slots=2, max_len=128, block_size=8,
            prefill_chunk=16, engine="paged"), name="mamba2moe", _http=True,
            route_prefix="/mamba2moe")
        handle = serve.get_app_handle("mamba2moe")
        streamed = [it["token"] for it in handle.options(
            method_name="stream").remote_streaming(
                {"tokens": prompt, "max_tokens": 10})]
        assert streamed == want
        req = urllib.request.Request(
            f"http://127.0.0.1:{serve.http_port()}/mamba2moe",
            data=json.dumps({"tokens": prompt, "max_tokens": 10}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert json.loads(resp.read())["tokens"] == want
        stats = handle.options(method_name="stats").remote({}).result(
            timeout=60)
        assert stats["state"]["state_resets"] == 2
        assert stats["prefix_hits"] == 0
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
