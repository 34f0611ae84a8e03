"""The decoder-hybrid-decoder model (`ray_tpu.models.hybrid`) on the
served path, held to the phi4flash family's plain float32 reference
(`bench/families/phi4flash.py`, which imports nothing of the program):
prefill and decode through a real `PagedLLMEngine`, whose slots now hold
a block table, a ring of window KV and recurrent state.  Tiny widths,
seeded weights, float32 compute where the claim is that the engine
computes the same function (errors of 1e-6), bfloat16 where it is that
the benchmark's comparison tells a fault from rounding."""
import dataclasses
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
from ray_tpu.models import configs, decoding, hybrid  # noqa: E402
from ray_tpu.serve.llm import LLMDeployment, PagedLLMEngine  # noqa: E402

TINY = os.path.join(ROOT, "bench", "tests", "data", "phi4flashfamily",
                    "configs", "tinyphi-serve.json")
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


def _want(e, c, seq):
    logits, margin = spec.family(c).forward(
        e.params, jnp.asarray(seq, jnp.int32), c, jit=jax.jit)
    assert bool(jnp.all(jnp.isinf(margin)))
    return logits


def _errors(e, c, seqs, n_prompt):
    got = e.score(seqs, n_prompt)
    return np.concatenate([
        np.asarray(reference.position_errors(
            jnp.stack(got[lane]), _want(e, c, seqs[lane])[n_prompt - 1:]))
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
        configs.get("tiny-hybrid"), name=c["name"],
        compute_dtype=jnp.dtype("float32"))
    assert cfg.n_window == 2 and cfg.n_cross == 1 and cfg.n_mamba == 3


def test_published_sizes_give_the_published_parameter_count():
    cfg = configs.get("phi4-mini-flash")
    assert round(cfg.num_params / 1e6) == 3851       # "3.8B" published
    shapes = jax.eval_shape(
        lambda: hybrid.init_params(jax.random.key(0), cfg))
    total = sum(x.size for x in jax.tree.leaves(shapes))
    # norms, biases, conv rows, A_log, D and the lambda vectors on top
    assert 0 < total - cfg.num_params < 0.001 * cfg.num_params


# -- (i) the window slides and the ring wraps ------------------------------
def test_prompt_longer_than_window_and_chunk(served):
    """100 prompt tokens against a ring of 24 + 32 rows: every window
    layer's ring wraps, and the last chunk (4 tokens of 32) is padded."""
    e, c = served
    ring = e.cfg.ring_len(e.prefill_chunk)
    assert e.cache.wk.shape[2] == ring == 56 < 100
    errs = _errors(e, c, _seqs(3, 100 + 10), 100)
    assert errs.shape == (33,) and errs.max() < EXACT, errs


# -- (ii) every chunk tier, and a padded last chunk ------------------------
@pytest.fixture(scope="module")
def served_chunk64():
    c = _config()
    e = _engine(c, prefill_chunk=64)
    yield e, c
    e.shutdown()


@pytest.mark.parametrize("n_prompt", [64, 81, 97, 128])
def test_every_chunk_tier_and_a_padded_tail(served_chunk64, n_prompt):
    """prefill_chunk 64 has the tiers 32 and 64: 64 = one whole chunk,
    81 = 64 + 17 (tier 32, padded), 97 = 64 + 33 (tier 64, padded),
    128 = two whole.  The padded tail must not advance the recurrence."""
    e, c = served_chunk64
    assert e._chunk_tiers == [32, 64]
    errs = _errors(e, c, _seqs(2, n_prompt + 4, seed=n_prompt), n_prompt)
    assert errs.max() < EXACT, errs


# -- (iii) unequal lanes and an idle lane between them ----------------------
def test_unequal_lanes_with_an_idle_lane_between(served):
    """The step the burst scans, over lanes 0 and 2 of different lengths
    with lane 1 idle and pointed at its own slot all the same (the engine
    points idle lanes at the null slot): the live lanes' logits are the
    reference's, and the idle lane's slot keeps its ring, conv rows and
    state to the bit."""
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
                e.cache, _ = e._prefill_chunk_fn(
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
    for name in ("wk", "wv", "conv", "h"):
        assert np.array_equal(getattr(after, name)[:, 1],
                              getattr(before, name)[:, 1]), name
        assert np.array_equal(getattr(after, name)[:, e.num_slots],
                              getattr(before, name)[:, e.num_slots]), name
    assert not np.array_equal(after.h[:, 0], before.h[:, 0])


def test_a_burst_equals_its_steps_on_every_kind_of_state(served):
    e, c = served
    cfg = e.cfg
    state = cfg.init_state(17, 8, 4, 32)
    tables = jnp.asarray(np.arange(1, 17, dtype=np.int32).reshape(4, 4))
    lengths = jnp.asarray([3, 0, 9, 1], jnp.int32)
    active = jnp.asarray([True, False, True, True])
    slots = jnp.asarray([2, 4, 0, 3], jnp.int32)
    toks = jnp.asarray([5, 0, 7, 9], jnp.int32)
    temps = jnp.zeros((4,), jnp.float32)
    key = jax.random.key(0)
    burst = jax.jit(decoding._bind_cfg(decoding.paged_decode_burst, cfg),
                    static_argnames=("n_steps",))
    b_state, b_toks, _, _ = burst(e.params, state, toks, tables, lengths,
                               active, temps, key, n_steps=3, slots=slots)
    step = jax.jit(decoding._bind_cfg(decoding.paged_decode_step, cfg))
    s_toks = []
    for _ in range(3):
        state, logits = step(e.params, state, toks, tables, lengths, active,
                             slots=slots)
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


# -- (iv), (v): the engine's own scheduling --------------------------------
def _is_greedy(e, c, prompt, out):
    """`out` is the reference's greedy continuation of `prompt`: one
    full forward over both, whose argmax at every position from the
    prompt's last is the token that follows."""
    logits = _want(e, c, list(prompt) + list(out))
    return out == [int(t) for t in
                   jnp.argmax(logits[len(prompt) - 1:-1], axis=-1)]


def test_a_slot_reused_by_a_second_request(served):
    e, c = served
    first = list(map(int, _seqs(1, 60, seed=11)[0]))
    second = list(map(int, _seqs(1, 45, seed=12)[0]))
    resets = e.engine_stats()["state"]["state_resets"]
    out1 = e.generate(first, max_tokens=6)
    out2 = e.generate(second, max_tokens=6)      # slot 0 again
    assert len(out1) == len(out2) == 6
    assert _is_greedy(e, c, first, out1) and _is_greedy(e, c, second, out2)
    stats = e.engine_stats()
    assert stats["state"]["state_resets"] == resets + 2
    assert stats["prefix_hits"] == 0
    fields = stats["tick_fields"]
    assert fields[-12:-10] == ("kv_read_tokens", "reset_s")
    ticks = [dict(zip(fields, t)) for t in stats["tick_log"]]
    assert any(t["reset_s"] > 0 for t in ticks)
    one = [t for t in ticks if t["lanes"] == 1][-1]
    # one lane of length n: 2 readers of the full KV (itself, one cross
    # layer) and two window layers of at most 24
    n = (one["kv_read_tokens"] - 2 * 24) // 2
    assert one["kv_read_tokens"] == e.cfg.kv_read_tokens([n]) and n > 24


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
    (tests/test_burst_ahead.py), here on slots that hold rings, conv rows
    and recurrent state: requests of different lengths join and leave
    mid-stream, the tiers go 4, 8, 4, a slot changes hands while its last
    burst is unread, and every stream is the step-by-step reference's."""
    from burst_ahead_cases import join_and_leave, park

    e = park(_engine(_config(), num_slots=8))
    try:
        join_and_leave(e)
    finally:
        e.shutdown()


# -- (vi) what this model cannot have yet is refused -------------------------
def test_refusals():
    c = _config()
    cfg = spec.family(c).program_config(c)
    params = hybrid.init_params(jax.random.key(0), cfg)
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
            e.import_prefix(prompt, np.zeros((2, 1, 4, 8, 4, 8)), 8)
    finally:
        e.shutdown()
    for kw in ({"disagg": True}, {"tensor_parallel": 2}):
        with pytest.raises(ValueError, match="recurrent state"):
            LLMDeployment(cfg, num_slots=2, max_len=64, **kw)


def test_deployment_takes_the_configuration_and_refuses_adoption():
    dep = LLMDeployment("tiny-hybrid", num_slots=2, max_len=64,
                        block_size=8, prefill_chunk=16)
    try:
        assert dep._disagg is None
        out = dep({"tokens": list(range(1, 20)), "max_tokens": 3})
        assert len(out["tokens"]) == 3
        with pytest.raises(ValueError, match="import_prefix"):
            dep.adopt_kv(list(range(8)), np.zeros((2, 1, 1, 8, 4, 8)), 8)
        state = dep.stats()["state"]
        assert state["kv_window"] > 0 and state["recurrent"] > 0
    finally:
        dep.engine.shutdown()


def test_a_transformer_s_state_is_the_pool_alone():
    from ray_tpu.models import init_params

    cfg = configs.get("tiny")
    e = PagedLLMEngine(cfg, init_params(jax.random.key(0), cfg),
                       num_slots=2, max_len=64, block_size=8,
                       prefill_chunk=16)
    try:
        e.generate(list(range(1, 20)), max_tokens=3)
        # generate() returns from inside the tick that finished the
        # request; that tick logs itself when it ends, under this lock.
        with e._tick_lock:
            stats = e.engine_stats()
        assert stats["state"] == {
            "kv_paged": 2 * 2 * 17 * 8 * 2 * 16 * 2, "kv_window": 0,
            "recurrent": 0, "state_resets": 0, "state_rebuilds": 0}
        # The last tick that decoded: a later one may have prefilled or
        # admitted only, with no lane and nothing read.
        ticks = [dict(zip(stats["tick_fields"], t))
                 for t in stats["tick_log"]]
        tick = [t for t in ticks if t["lanes"] > 0][-1]
        assert tick["reset_s"] == 0.0
        assert tick["kv_read_tokens"] % cfg.n_layers == 0
        assert tick["kv_read_tokens"] > 0
    finally:
        e.shutdown()


# -- (vii) the benchmark's comparison has teeth ------------------------------
def _padded_tail_advances(monkeypatch):
    inner = hybrid._mamba
    monkeypatch.setattr(
        hybrid, "_mamba", lambda bp, x, conv, h, valid, cfg:
        inner(bp, x, conv, h, jnp.ones_like(valid), cfg))


def _window_mask_dropped(monkeypatch):
    inner = hybrid.window_diff_attention
    monkeypatch.setattr(
        hybrid, "window_diff_attention", lambda q, k, v, pos, n, window:
        inner(q, k, v, pos, n, 1 << 30))


def _lambda_zero(monkeypatch):
    monkeypatch.setattr(hybrid.HybridConfig, "lambda_init",
                        lambda self, layer: jnp.zeros((), jnp.float32))


@pytest.mark.parametrize("fault", [
    None, _padded_tail_advances, _window_mask_dropped, _lambda_zero],
    ids=lambda f: f.__name__.strip("_") if f else "as_it_is")
def test_logits_check_has_teeth(fault, monkeypatch):
    """`deployment.logits_check` (3 lanes x (the last of 100 prompt
    positions + 8 decode steps), bfloat16 compute and cache as the
    benchmark's configuration has them, held to the family's own
    LOGITS_REL) passes the program as it is and fails each fault."""
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
    assert v["positions"] == 27 and v["decided"] == 27
    assert v["bound"] == fam.TOLERANCES["LOGITS_REL"]
    if fault is None:
        assert v["ok"], v
    else:
        assert not v["ok"] and v["worst"] > v["bound"], v


def test_state_kept_in_bfloat16_shows_in_float32_arithmetic():
    """The logits check in bfloat16 cannot tell a recurrent state in
    bfloat16 from one in float32 (the family's TOLERANCES says by how
    little it moves); with everything else in float32 it is a hundred
    times the engine's own error of 1e-6 and fails the exact bound."""
    c = _config(state_dtype="bfloat16")
    e = _engine(c)
    try:
        assert e.cache.h.dtype == jnp.bfloat16
        errs = _errors(e, _config(), _seqs(2, 100 + 6), 100)
        assert errs.min() > 5 * EXACT, errs
    finally:
        e.shutdown()


# -- the served path: serve.run -> proxy -> handle -> replica -> engine -----
def test_served_through_the_front_like_any_model():
    import urllib.request

    import ray_tpu
    from ray_tpu import serve

    cfg = configs.get("tiny-hybrid")
    prompt = list(range(3, 40))
    twin = PagedLLMEngine(cfg, hybrid.init_params(jax.random.key(0), cfg),
                          num_slots=2, max_len=128, block_size=8,
                          prefill_chunk=16)
    try:
        want = twin.generate(prompt, max_tokens=10)
    finally:
        twin.shutdown()
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    try:
        serve.run(serve.deployment(LLMDeployment).bind(
            "tiny-hybrid", num_slots=2, max_len=128, block_size=8,
            prefill_chunk=16), name="hybrid", _http=True,
            route_prefix="/hybrid")
        handle = serve.get_app_handle("hybrid")
        streamed = [it["token"] for it in handle.options(
            method_name="stream").remote_streaming(
                {"tokens": prompt, "max_tokens": 10})]
        assert streamed == want
        req = urllib.request.Request(
            f"http://127.0.0.1:{serve.http_port()}/hybrid",
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
