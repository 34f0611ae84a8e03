"""LLM decoding path: the paged programs vs the full forward; continuous
batching; tensor-parallel serving on the one engine."""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, forward, init_params
from ray_tpu.models.decoding import (
    init_paged_cache,
    paged_cache_shardings,
    paged_decode_step,
    paged_prefill_chunk,
    sample_logits,
)
from ray_tpu.serve.llm import LLMDeployment, PagedLLMEngine

CFG = configs.TINY
BS = 4                                            # block size
TABLE = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)   # 32 positions


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def _prefilled(cfg, params, prompt, width=16):
    """A fresh pool with `prompt` prefilled as one padded chunk into
    TABLE's blocks: (cache, logits of the last real token)."""
    cache = init_paged_cache(cfg, num_blocks=17, block_size=BS)
    toks = jnp.zeros((width,), jnp.int32).at[:len(prompt)].set(
        jnp.asarray(prompt, jnp.int32))
    return paged_prefill_chunk(params, cache, toks, TABLE[0], jnp.int32(0),
                               jnp.int32(len(prompt)), cfg)


def _greedy(cfg, params, prompt, n):
    """`n` greedy tokens by one prefill chunk and n - 1 decode steps."""
    cache, last = _prefilled(cfg, params, prompt)
    out = [int(jnp.argmax(last))]
    lengths = jnp.asarray([len(prompt)], jnp.int32)
    for _ in range(n - 1):
        cache, logits = paged_decode_step(
            params, cache, jnp.asarray([out[-1]], jnp.int32), TABLE,
            lengths, jnp.asarray([True]), cfg)
        out.append(int(jnp.argmax(logits[0])))
        lengths = lengths + 1
    return out


def _engine(cfg, params, **kw):
    kw = dict(dict(num_slots=2, max_len=64, block_size=BS,
                   prefill_chunk=16), **kw)
    return PagedLLMEngine(cfg, params, **kw)


def test_prefill_matches_forward(params):
    toks = jax.random.randint(jax.random.key(1), (1, 10), 0, CFG.vocab_size)
    cache, last_logits = _prefilled(CFG, params, np.asarray(toks)[0])
    ref = forward(params, toks, CFG)[0, -1]
    np.testing.assert_allclose(np.asarray(last_logits, np.float32),
                               np.asarray(ref, np.float32), atol=0.15)
    # The chunk (its padded tail too) went into the table's blocks; the
    # blocks of no table are as they were.
    k = np.asarray(cache.k, np.float32)
    assert np.abs(k[:, 1:4]).sum() > 0
    assert np.abs(k[:, 9:]).sum() == 0


def test_decode_matches_forward(params):
    """Greedy decode via the pool == greedy decode via full re-forward."""
    prompt = jax.random.randint(jax.random.key(2), (1, 8), 0,
                                CFG.vocab_size)
    # reference: iterative full forward
    seq = np.asarray(prompt)[0].tolist()
    for _ in range(5):
        logits = forward(params, jnp.asarray([seq]), CFG)
        seq.append(int(jnp.argmax(logits[0, -1])))
    assert _greedy(CFG, params, seq[:8], 5) == seq[8:]


def test_sample_logits_greedy_and_topk():
    logits = jnp.asarray([[1.0, 5.0, 2.0], [0.1, 0.2, 9.0]])
    greedy = sample_logits(logits, jax.random.key(0), temperature=0.0)
    np.testing.assert_array_equal(np.asarray(greedy), [1, 2])
    topk = sample_logits(logits, jax.random.key(0), temperature=1.0,
                         top_k=1)
    np.testing.assert_array_equal(np.asarray(topk), [1, 2])


def test_engine_single_and_concurrent(params):
    eng = _engine(CFG, params)
    out = eng.generate([1, 2, 3], max_tokens=5)
    assert len(out) == 5

    # concurrent requests exceed slot count -> continuous batching
    results = [None] * 5
    def run(i):
        results[i] = eng.generate([i + 1, i + 2], max_tokens=4)
    threads = [threading.Thread(target=run, args=(i,)) for i in range(5)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(len(r) == 4 for r in results)
    st = eng.engine_stats()
    assert st["completed"] == 6
    assert all(r["ttft_s"] > 0 for r in st["request_phases"])
    eng.shutdown()


def test_engine_determinism_matches_decode(params):
    """Engine greedy output equals the manual pool path (same tokens)."""
    eng = _engine(CFG, params)
    prompt = [5, 6, 7, 8]
    out = eng.generate(prompt, max_tokens=6)
    eng.shutdown()
    assert out == _greedy(CFG, params, prompt, 6)


def test_paged_verify_step_exact_acceptance(params):
    """The PAGED speculative verifier is exact under greedy decoding:
    correct proposals accept through the block pool, the first wrong
    proposal rejects, and the continuation after the rejected draft is
    bit-identical to sequential paged decode — the stale KV the wrong
    candidate scattered into the slot's own block is masked by length
    arithmetic and overwritten in place (no device rollback)."""
    from ray_tpu.models.decoding import (
        init_paged_cache,
        paged_decode_step,
        paged_prefill_chunk,
        paged_verify_step,
    )

    prompt = [5, 6, 7, 8]
    bs = 4
    table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)   # 16 positions

    def fresh_prefilled():
        cache = init_paged_cache(CFG, num_blocks=9, block_size=bs)
        toks = jnp.zeros((8,), jnp.int32).at[:4].set(jnp.asarray(prompt))
        cache, last = paged_prefill_chunk(params, cache, toks, table[0],
                                          jnp.int32(0), jnp.int32(4), CFG)
        return cache, int(jnp.argmax(last))

    # Reference: sequential greedy paged decode of 6 tokens.
    cache, t0 = fresh_prefilled()
    ref = [t0]
    lengths = jnp.asarray([4], jnp.int32)
    for _ in range(5):
        cache, logits = paged_decode_step(
            params, cache, jnp.asarray([ref[-1]], jnp.int32), table,
            lengths, jnp.asarray([True]), CFG)
        ref.append(int(jnp.argmax(logits[0])))
        lengths = lengths + 1

    # Speculative: candidates = [t0, ref[1], ref[2], WRONG].
    cache2, t0b = fresh_prefilled()
    assert t0b == ref[0]
    wrong = (ref[3] + 1) % CFG.vocab_size
    cand = jnp.asarray([[t0b, ref[1], ref[2], wrong]], jnp.int32)
    cache2, tok_out, accepted, _ = paged_verify_step(
        params, cache2, cand, table, jnp.asarray([4], jnp.int32),
        jnp.asarray([True]), jnp.asarray([0.0], jnp.float32),
        jax.random.key(0), CFG)
    a = int(accepted[0])
    assert a == 2                        # two correct proposals
    emitted = [int(t) for t in np.asarray(tok_out[0, :a + 1])]
    assert emitted == ref[1:4]           # accepted + bonus == reference

    # Rollback is length arithmetic: advance by a+1 only and keep
    # decoding — exact despite the rejected draft's stale KV at the
    # very next position (the decode scatter overwrites it first).
    lengths = jnp.asarray([4 + 1 + a], jnp.int32)
    cont = [emitted[-1]]
    for _ in range(2):
        cache2, logits = paged_decode_step(
            params, cache2, jnp.asarray([cont[-1]], jnp.int32), table,
            lengths, jnp.asarray([True]), CFG)
        cont.append(int(jnp.argmax(logits[0])))
        lengths = lengths + 1
    assert cont[1:] == ref[4:6]

    # A sampling slot (temp>0) accepts nothing — exact fallback.
    cache3, _ = fresh_prefilled()
    _, _, accepted3, _ = paged_verify_step(
        params, cache3, cand, table, jnp.asarray([4], jnp.int32),
        jnp.asarray([True]), jnp.asarray([0.7], jnp.float32),
        jax.random.key(1), CFG)
    assert int(accepted3[0]) == 0


def test_engine_speculative_matches_plain_greedy():
    """With prompt-lookup speculation on, greedy generation must be
    BIT-IDENTICAL to the plain engine (speculation is exact — only
    faster), and drafts must actually be proposed on a repetitive
    prompt.  On the experts here (`tests/test_paged_kv.py` has the dense
    twin): a verify window routes K rows a lane, a burst one."""
    mcfg = dataclasses.replace(configs.TINY_MOE, compute_dtype=jnp.float32)
    mparams = init_params(jax.random.key(0), mcfg)
    # Small bursts make the drafter check often; a long-enough greedy
    # continuation settles into repetition the n-gram lookup can mine.
    prompt = [1, 2, 3, 1, 2, 3, 1, 2]
    kw = dict(max_len=256, max_burst=2, prefix_sharing=False)
    plain = _engine(mcfg, mparams, **kw)
    ref = plain.generate(prompt, max_tokens=96)
    plain.shutdown()

    spec = _engine(mcfg, mparams, speculation_k=4, **kw)
    out = spec.generate(prompt, max_tokens=96)
    assert out == ref
    st = spec.engine_stats()
    assert st["spec_proposed"] > 0
    # Sampling path still works alongside (falls back per slot).
    sampled = spec.generate(prompt, max_tokens=6, temperature=0.8)
    assert len(sampled) == 6
    spec.shutdown()


# ---------------------------------------------------------------------------
# tensor-parallel serving: the same engine, given a mesh
# ---------------------------------------------------------------------------
def _tp_mesh(tp):
    from jax.sharding import Mesh

    from ray_tpu.parallel.mesh import AXIS_TENSOR

    return Mesh(np.array(jax.devices()[:tp]), (AXIS_TENSOR,))


def _assert_pool_is_split(cache, tp):
    """The pool spans the mesh and every shard holds 1/tp of the KV
    heads."""
    for a in (cache.k, cache.v):
        assert len(a.sharding.device_set) == tp
        assert {s.data.shape[3] for s in a.addressable_shards} == {
            a.shape[3] // tp}


@pytest.mark.parametrize("name,tp", [("tiny", 2), ("tiny-moe", 2),
                                     ("tiny-moe", 4)])
def test_tensor_parallel_engine_matches_single_device(name, tp):
    """TP serving: the engine with params and the pool's KV heads sharded
    over a tp mesh gives the single-device engine's greedy tokens — the
    sharding is a layout change, not a math change (XLA inserts the
    all-reduces).  float32 compute: in bfloat16 each shard's partial sum
    is rounded before the all-reduce, and a router's near tie then flips
    on some prompts.  A 39-token prompt in chunks of 16, then bursts."""
    cfg = dataclasses.replace(configs.get(name), compute_dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    prompt = [(7 * i + 3) % 200 + 1 for i in range(39)]
    rep = [1, 2, 3, 1, 2, 3, 1, 2]
    kw = dict(max_len=128, block_size=8)
    plain = _engine(cfg, params, **kw)
    ref = plain.generate(prompt, max_tokens=12)
    ref_rep = plain.generate(rep, max_tokens=24)
    plain.shutdown()

    mesh = _tp_mesh(tp)
    tp_eng = _engine(cfg, params, mesh=mesh, **kw)
    try:
        assert tp_eng.generate(prompt, max_tokens=12) == ref
        # Params really are distributed: a tp-sharded weight spans the
        # mesh, and the pool is still split after the donated bursts.
        wq = tp_eng.params["blocks"]["wq"]
        assert len(wq.sharding.device_set) == tp
        _assert_pool_is_split(tp_eng.cache, tp)
    finally:
        tp_eng.shutdown()

    # Indivisible tp fails with a clear error, not a sharding crash.
    with pytest.raises(ValueError, match="does not divide"):
        _engine(cfg, params, mesh=_tp_mesh(3), **kw)

    # Prefix sharing (a whole-prompt hit, its tail block copied) and
    # speculation compose with the sharded layout.
    tp2 = _engine(cfg, params, mesh=mesh, speculation_k=4, max_burst=2,
                  prefix_sharing=True, **kw)
    try:
        assert tp2.generate(rep, max_tokens=24) == ref_rep
        assert tp2.generate(rep, max_tokens=24) == ref_rep
        assert tp2.stats["prefix_hits"] == 1
        assert tp2.stats["spec_proposed"] > 0
        _assert_pool_is_split(tp2.cache, tp)
    finally:
        tp2.shutdown()


def test_deployment_tensor_parallel_serves_through_the_paged_engine():
    """`LLMDeployment(tensor_parallel=2)` builds the mesh and hands it to
    the one engine; its tokens are `tensor_parallel=0`'s."""
    prompt = [4, 5, 6, 7]
    kw = dict(num_slots=2, max_len=64, block_size=BS, prefill_chunk=16)
    one = LLMDeployment("tiny", **kw)
    ref = one({"tokens": prompt, "max_tokens": 10})["tokens"]
    one.engine.shutdown()
    dep = LLMDeployment("tiny", tensor_parallel=2, **kw)
    try:
        assert isinstance(dep.engine, PagedLLMEngine)
        _assert_pool_is_split(dep.engine.cache, 2)
        assert dep({"tokens": prompt, "max_tokens": 10})["tokens"] == ref
        assert dep.engine_gauges()["occupancy"] >= 0.0
    finally:
        dep.engine.shutdown()


def test_a_model_with_state_of_its_own_is_refused_a_mesh():
    cfg = configs.get("tiny-hybrid")
    with pytest.raises(ValueError, match="recurrent state"):
        PagedLLMEngine(cfg, cfg.init_params(jax.random.key(0)),
                       num_slots=2, max_len=64, block_size=8,
                       prefill_chunk=16, mesh=_tp_mesh(2))


def test_sharded_pool_is_never_whole_on_one_device():
    """`init_paged_cache` with shardings allocates shard by shard: a pool
    that fits only across chips never exists whole on chip 0."""
    shape = (CFG.n_layers, 33, 8, CFG.n_kv_heads, CFG.head_dim)
    before = {id(a) for a in jax.live_arrays()}
    cache = init_paged_cache(CFG, 33, 8,
                             shardings=paged_cache_shardings(_tp_mesh(2)))
    assert cache.k.shape == cache.v.shape == shape
    _assert_pool_is_split(cache, 2)
    whole = [a for a in jax.live_arrays() if id(a) not in before
             and a.shape == shape and len(a.sharding.device_set) == 1]
    assert not whole
    assert float(jnp.abs(cache.k).sum() + jnp.abs(cache.v).sum()) == 0.0


def test_moe_engine_decode_matches_reprefill():
    """Mixtral-style MoE config serves through the SAME engine paths:
    cached greedy decode == re-prefilling the growing sequence from
    scratch each step. Inference uses DROPLESS exact routing
    (moe_mlp_dropless), so the function is batch-size independent —
    capacity-based train routing would make these disagree (TINY_MOE
    is the CPU stand-in for the Mixtral-8x7B expert-parallel config)."""
    mcfg = configs.TINY_MOE
    mparams = init_params(jax.random.key(3), mcfg)

    prompt = jax.random.randint(jax.random.key(4), (1, 8), 0,
                                mcfg.vocab_size)

    # reference: re-prefill the whole growing sequence every step
    seq = np.asarray(prompt)[0].tolist()
    ref_out = []
    for _ in range(4):
        _, last = _prefilled(mcfg, mparams, seq)
        ref_out.append(int(jnp.argmax(last)))
        seq.append(ref_out[-1])

    # cached path: one prefill + incremental decode
    assert _greedy(mcfg, mparams, seq[:8], 4) == ref_out


def test_moe_engine_generates():
    """End-to-end engine generation on the MoE config."""
    mcfg = configs.TINY_MOE
    mparams = init_params(jax.random.key(5), mcfg)
    engine = _engine(mcfg, mparams, max_len=32)
    out = engine.generate([3, 1, 4, 1, 5], max_tokens=6,
                          temperature=0.0)
    assert len(out) == 6
    assert all(0 <= t < mcfg.vocab_size for t in out)
    engine.shutdown()
