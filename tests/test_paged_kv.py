"""Paged KV-cache serving engine: block allocator, prefix sharing + COW,
allocator-full admission queueing, chunked-prefill ITL bound, bounded
stream queues, controller autoscale-stats TTL."""
import hashlib
import os
import random
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

import jax

from ray_tpu.core.config import reset_config
from ray_tpu.models import configs, init_params
from ray_tpu.serve.kv_cache import KVBlockAllocator
from ray_tpu.serve.llm import PagedLLMEngine, StreamQueueFullError


@pytest.fixture(scope="module")
def tiny_model():
    cfg = configs.get("tiny")
    return cfg, init_params(jax.random.key(0), cfg)


def make_engine(tiny_model, **kw):
    cfg, params = tiny_model
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    return PagedLLMEngine(cfg, params, **kw)


# ---------------------------------------------------------------------------
# allocator unit behavior
# ---------------------------------------------------------------------------
def test_alloc_free_roundtrip():
    a = KVBlockAllocator(9, 4)     # 8 usable blocks (block 0 reserved)
    blocks = a.alloc(5)
    assert blocks is not None and len(blocks) == 5
    assert 0 not in blocks         # null block never allocated
    assert a.snapshot()["blocks_active"] == 5
    assert a.alloc(4) is None      # only 3 left: all-or-nothing
    a.free(blocks)
    snap = a.snapshot()
    assert snap["blocks_active"] == 0 and snap["blocks_free"] == 8


def test_prefix_refcount_and_reuse():
    a = KVBlockAllocator(9, 4)
    prompt = list(range(1, 9))     # 8 tokens = 2 aligned blocks
    blocks = a.alloc(2)
    a.register_prefix(prompt, blocks, meta="logits")
    # registration does not change ownership
    assert a.snapshot()["blocks_active"] == 2
    a.free(blocks)                 # refcount 0 -> cached, contents kept
    snap = a.snapshot()
    assert snap["blocks_active"] == 0 and snap["blocks_cached"] == 2
    got, covered, meta = a.lookup_prefix(prompt)
    assert got == blocks and covered == 8 and meta == "logits"
    assert a.stats["reuse_hits"] > 0
    # revived: active again, a second reader shares the same blocks
    got2, covered2, _ = a.lookup_prefix(prompt)
    assert got2 == blocks and covered2 == 8
    a.free(got)
    assert a.snapshot()["blocks_active"] == 2   # got2 still holds them
    a.free(got2)
    assert a.snapshot()["blocks_cached"] == 2


def test_cow_shared_block_copies():
    a = KVBlockAllocator(9, 4)
    prompt = list(range(1, 7))     # 6 tokens: 1 aligned + partial tail
    blocks = a.alloc(2)
    a.register_prefix(prompt, blocks, meta="m")
    got, covered, meta = a.lookup_prefix(prompt)   # second owner
    assert covered == 6 and meta == "m"
    tail = got[-1]
    new, copied = a.cow(tail)      # shared -> must copy
    assert copied and new != tail
    assert a.stats["cow_copies"] == 1
    # original owner's tail untouched; new owner holds the copy
    a.free(blocks)
    a.free(got[:-1] + [new])
    assert a.snapshot()["blocks_active"] == 0


def test_cow_sole_owner_unregistered_in_place():
    a = KVBlockAllocator(9, 4)
    blocks = a.alloc(1)
    new, copied = a.cow(blocks[0])
    assert not copied and new == blocks[0]
    a.free(blocks)


def test_cached_prefix_evicted_under_pressure():
    a = KVBlockAllocator(5, 4)     # 4 usable
    prompt = list(range(1, 9))
    blocks = a.alloc(2)
    a.register_prefix(prompt, blocks)
    a.free(blocks)                 # 2 cached + 2 free
    more = a.alloc(4)              # must evict the cached prefix
    assert more is not None and len(more) == 4
    assert a.stats["evictions"] == 2
    got, covered, _ = a.lookup_prefix(prompt)
    assert got == [] and covered == 0   # registration gone with eviction
    a.free(more)


# ---------------------------------------------------------------------------
# prefix keys and digests in one pass over a prompt's blocks (PR 39)
# ---------------------------------------------------------------------------
def _digest_per_token(tokens):
    """The digest as it was defined: SHA-1 fed 8 bytes a token."""
    h = hashlib.sha1()
    for t in tokens:
        h.update(int(t).to_bytes(8, "little", signed=True))
    return h.hexdigest()[:16]


_ODD_TOKENS = (0, -1, 2 ** 31, 2 ** 40)
_AS = {"list": list, "tuple": tuple,
       "array": lambda t: np.asarray(t, dtype=np.int64)}


@pytest.mark.parametrize("kind", sorted(_AS))
@pytest.mark.parametrize("n", [1, 15, 16, 17, 3968])
def test_digests_equal_the_per_token_definition(n, kind):
    """`prefix_digest`, the digests a registration stores and the
    handle's `request_digests` are a cluster-wide contract: every string
    equals the per-token loop's, whatever the container and the token
    values."""
    from ray_tpu.serve.disagg import request_digests
    from ray_tpu.serve.kv_cache import prefix_digest

    bs = 16
    rng = random.Random(n)
    plain = [_ODD_TOKENS[i % 7] if i % 7 < 4 else rng.randrange(32768)
             for i in range(n)]
    tokens = _AS[kind](plain)
    want = [_digest_per_token(plain[:k * bs])
            for k in range(1, n // bs + 1)]
    assert prefix_digest(tokens) == _digest_per_token(plain)
    a = KVBlockAllocator(n // bs + 3, bs)
    a.register_prefix(tokens, a.alloc(-(-n // bs)))
    assert a.prefix_digests() == want
    assert request_digests(tokens, bs) == [
        (k * bs, want[k - 1])
        for k in range(n // bs, max(0, n // bs - 8), -1)]
    assert request_digests(tokens, bs, max_bounds=3) == \
        request_digests(tokens, bs)[:3]


def test_digest_path_imports_no_numpy():
    """The handle and the proxy import no numpy until they hash a
    request's prefixes through `disagg.request_digests`; that path has
    to stay that way (the import cost the first routed request of a
    front process ~0.4 s on the chip's host)."""
    code = ("import sys; from ray_tpu.serve.disagg import request_digests; "
            "assert request_digests(list(range(40)), 16); "
            "sys.exit('numpy' in sys.modules)")
    assert subprocess.run([sys.executable, "-c", code], timeout=120,
                          env=dict(os.environ, JAX_PLATFORMS="cpu")
                          ).returncode == 0


class _CountingSha1:
    """`hashlib.sha1` that adds up the bytes passed to `update`."""

    def __init__(self, hashed, inner=None):
        self._hashed, self._inner = hashed, inner or hashlib.sha1()

    def update(self, data):
        self._hashed[0] += memoryview(data).nbytes
        self._inner.update(data)

    def copy(self):
        return _CountingSha1(self._hashed, self._inner.copy())

    def hexdigest(self):
        return self._inner.hexdigest()


@pytest.fixture
def hashed(monkeypatch):
    """Bytes `kv_cache` (and nobody else) fed to SHA-1, as a one-item
    list."""
    from ray_tpu.serve import kv_cache

    count = [0]
    monkeypatch.setattr(kv_cache, "hashlib", types.SimpleNamespace(
        sha1=lambda: _CountingSha1(count)))
    return count


def _token_slots(key):
    return sum(map(_token_slots, key)) if isinstance(key, tuple) else 1


def test_register_and_lookup_are_linear_in_the_prompt(hashed):
    n, bs = 3968, 16
    prompt = [(7 * i) % 32768 for i in range(n)]
    a = KVBlockAllocator(n // bs + 2, bs)
    blocks = a.alloc(n // bs)
    a.register_prefix(prompt, blocks, meta="logits")
    assert a.lookup_prefix(prompt) == (blocks, n, "logits")
    assert 8 * n <= hashed[0] <= 8 * n + 64 * len(blocks)
    assert len(a._by_key) == len(blocks)
    held = sum(_token_slots(k) for k in a._by_key)
    assert n <= held <= n + 4 * len(blocks)
    # A second owner of the same prompt registers nothing and hashes
    # nothing; a hit never hashes.
    a.register_prefix(prompt, blocks)
    assert hashed[0] <= 8 * n + 64 * len(blocks)


def test_lookup_is_exact_not_positional():
    """Two prompts that differ only in their first block share no block,
    though every later block holds equal tokens."""
    bs = 4
    a = KVBlockAllocator(9, bs)
    one = [1, 2, 3, 4] + list(range(10, 18))
    two = [1, 2, 3, 5] + list(range(10, 18))
    b_one = a.alloc(3)
    a.register_prefix(one, b_one)
    assert a.lookup_prefix(two) == ([], 0, None)
    b_two = a.alloc(3)
    a.register_prefix(two, b_two)
    assert a.lookup_prefix(two)[:2] == (b_two, 12)
    assert a.lookup_prefix(one)[:2] == (b_one, 12)
    assert not set(b_one) & set(b_two)
    assert len(a._by_key) == 6
    a.free(b_one + b_one + b_two + b_two)
    snap = a.snapshot()
    assert snap["blocks_active"] == 0 and snap["blocks_cached"] == 6


def test_evicted_middle_block_ends_the_chain_until_reregistered():
    bs = 4
    a = KVBlockAllocator(6, bs)    # 5 usable
    prompt = list(range(1, 13))    # 3 aligned blocks
    b0, b1, b2 = blocks = a.alloc(3)
    a.register_prefix(prompt, blocks)
    a.free([b1])                   # parked first: the LRU's next victim
    a.free([b0, b2])
    other = a.alloc(3)             # 2 free + the evicted middle block
    assert a.stats["evictions"] == 1 and b1 in other
    got, covered, _ = a.lookup_prefix(prompt)
    assert got == [b0] and covered == bs   # nothing beyond the break
    a.free(got)
    assert a.snapshot()["blocks_active"] == 3
    a.free(other)
    # Registered again, the whole chain is reachable: the surviving
    # head keeps its block (first registration wins), the rest is new.
    again = a.alloc(3)
    a.register_prefix(prompt, again)
    got, covered, _ = a.lookup_prefix(prompt)
    assert got == [b0, again[1], again[2]] and covered == 12
    a.free(got)
    a.free(again)
    snap = a.snapshot()
    assert snap["blocks_active"] == 0
    assert snap["blocks_free"] + snap["blocks_cached"] == 5
    # What hung below the break (b2) was never reachable again, and is
    # reclaimed like any cached block.
    assert a.alloc(5) is not None
    assert a.snapshot()["prefixes_registered"] == 0


def test_whole_prompt_hit_on_partial_tail_returns_meta():
    bs = 4
    a = KVBlockAllocator(9, bs)
    prompt = list(range(1, 11))    # 2 aligned blocks + a tail of 2
    blocks = a.alloc(3)
    a.register_prefix(prompt, blocks, meta="logits")
    assert a.lookup_prefix(prompt) == (blocks, 10, "logits")
    # The tail is a whole-prompt key: a longer prompt takes the aligned
    # chain alone, and so does this one once the tail's key is gone.
    assert a.lookup_prefix(prompt + [11]) == (blocks[:2], 8, None)
    assert a.prefix_digests() == [_digest_per_token(prompt[:4]),
                                  _digest_per_token(prompt[:8])]
    a.unregister_block(blocks[2])
    assert a.lookup_prefix(prompt) == (blocks[:2], 8, None)
    short = [21, 22, 23]           # no aligned block at all
    tail = a.alloc(1)
    a.register_prefix(short, tail, meta="m")
    assert a.lookup_prefix(short) == (tail, 3, "m")


def test_engine_registers_a_finished_prompt_in_one_pass(tiny_model,
                                                        hashed):
    """Through the engine: the leaf `book` of a finished prompt hashes
    the prompt once, and the same prompt again hits the whole prefix."""
    n, bs = 300, 16
    prompt = [(11 * i) % 250 + 1 for i in range(n)]
    eng = make_engine(tiny_model, max_len=512, block_size=bs,
                      prefill_chunk=64)
    try:
        first = eng.generate(prompt, max_tokens=4, timeout=120)
        n_blocks = -(-n // bs)
        assert 8 * (n - n % bs) <= hashed[0] <= 8 * n + 64 * n_blocks
        assert eng.stats["prefix_hits"] == 0
        assert len(eng.allocator.prefix_digests()) == n // bs
        assert eng.generate(prompt, max_tokens=4, timeout=120) == first
        assert eng.stats["prefix_hits"] == 1
        assert eng.allocator.stats["reuse_hits"] == n_blocks
        assert hashed[0] <= 8 * n + 64 * n_blocks
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# shm-arena leak guard
# ---------------------------------------------------------------------------
def test_arena_reservation_and_store_quiescence(tmp_path):
    from ray_tpu.core.object_store import ObjectStore

    store = ObjectStore(str(tmp_path / "kvstore"),
                        capacity=8 * 1024 * 1024, num_slots=64)
    try:
        base_used, base_objs = store.used, store.num_objects
        a = KVBlockAllocator(17, 4, store=store, bytes_per_block=1024)
        assert a.arena_bytes == 17 * 1024
        assert store.used > base_used          # reservation is visible
        blocks = a.alloc(8)
        a.free(blocks)
        a.release()
        # quiescence: the arena fully returns to the store
        assert store.used == base_used
        assert store.num_objects == base_objs
    finally:
        store.disconnect()
        ObjectStore.destroy(str(tmp_path / "kvstore"))


def test_engine_release_returns_store_to_baseline(tmp_path, tiny_model):
    from ray_tpu.core.object_store import ObjectStore

    store = ObjectStore(str(tmp_path / "kvstore2"),
                        capacity=32 * 1024 * 1024, num_slots=64)
    try:
        base_used, base_objs = store.used, store.num_objects
        eng = make_engine(tiny_model, store=store)
        assert eng.allocator.arena_bytes > 0
        assert store.used > base_used
        out = eng.generate([1, 2, 3, 4, 5], max_tokens=4, timeout=120)
        assert len(out) == 4
        eng.shutdown()
        assert store.used == base_used
        assert store.num_objects == base_objs
    finally:
        store.disconnect()
        ObjectStore.destroy(str(tmp_path / "kvstore2"))


# ---------------------------------------------------------------------------
# engine: prefix sharing + COW correctness
# ---------------------------------------------------------------------------
def test_prefix_share_outputs_identical_to_unshared(tiny_model):
    cfg, params = tiny_model
    prompt = list(range(1, 11))    # 10 tokens: partial tail at bs=4
    # Reference: sharing disabled — every request prefills from scratch.
    ref_eng = make_engine(tiny_model, prefix_sharing=False)
    ref = ref_eng.generate(prompt, max_tokens=6, timeout=120)
    ref_div = ref_eng.generate(prompt[:8] + [99, 98], max_tokens=6,
                               timeout=120)
    ref_eng.shutdown()

    eng = make_engine(tiny_model, prefix_sharing=True)
    first = eng.generate(prompt, max_tokens=6, timeout=120)
    assert first == ref
    # Whole-prompt hit: block reuse counter must move, output identical.
    second = eng.generate(prompt, max_tokens=6, timeout=120)
    assert second == ref
    snap = eng.allocator.snapshot()
    assert snap["reuse_hits"] > 0
    assert snap["cow_copies"] >= 1    # shared partial tail was COWed
    # Divergent continuation off the shared aligned prefix: COW keeps
    # the cached blocks pristine, so output matches the unshared run.
    div = eng.generate(prompt[:8] + [99, 98], max_tokens=6, timeout=120)
    assert div == ref_div
    # ... and the original prompt STILL reproduces (its cached prefix
    # was not corrupted by the divergent writer).
    third = eng.generate(prompt, max_tokens=6, timeout=120)
    assert third == ref
    eng.shutdown()


# ---------------------------------------------------------------------------
# engine: speculative decoding on the paged pool
# ---------------------------------------------------------------------------
def test_paged_engine_speculative_matches_plain_greedy(tiny_model):
    """With prompt-lookup speculation on, the paged engine's greedy
    output is BIT-IDENTICAL to the non-speculative paged engine
    (speculation is exact — only faster), drafts are actually proposed
    on a repetitive prompt, and sampling requests fall back per slot."""
    # Small bursts make the drafter check often; a long-enough greedy
    # continuation settles into repetition the n-gram lookup can mine.
    prompt = [1, 2, 3, 1, 2, 3, 1, 2]
    kw = dict(max_len=256, max_burst=2, prefix_sharing=False)
    plain = make_engine(tiny_model, **kw)
    ref = plain.generate(prompt, max_tokens=96, timeout=300)
    plain.shutdown()

    spec = make_engine(tiny_model, speculation_k=4, **kw)
    out = spec.generate(prompt, max_tokens=96, timeout=300)
    assert out == ref
    st = spec.engine_stats()
    assert st["spec_proposed"] > 0
    assert st["spec_accepted"] > 0     # drafts actually advanced decode
    # Sampling path still works alongside (falls back per slot).
    sampled = spec.generate(prompt, max_tokens=6, temperature=0.8,
                            timeout=120)
    assert len(sampled) == 6
    spec.shutdown()


def test_paged_spec_rejected_drafts_with_shared_prefix_cow(tiny_model):
    """Speculation composes with prefix sharing: generations over a
    registered (shared, COW-tailed) prefix spec-decode into the COW
    copy; rejected drafts leave the registered blocks pristine, so
    repeated and divergent generations all match the unshared
    non-speculative reference bit-for-bit."""
    prompt = [1, 2, 3, 1, 2, 3]    # 6 tokens: partial tail at bs=4
    kw = dict(max_len=256, max_burst=2)
    ref_eng = make_engine(tiny_model, prefix_sharing=False, **kw)
    ref = ref_eng.generate(prompt, max_tokens=64, timeout=300)
    ref_div = ref_eng.generate(prompt[:4] + [9, 9], max_tokens=8,
                               timeout=120)
    ref_eng.shutdown()

    eng = make_engine(tiny_model, prefix_sharing=True, speculation_k=4,
                      **kw)
    first = eng.generate(prompt, max_tokens=64, timeout=300)
    assert first == ref
    # Prefix hit: the shared tail block is COWed, then speculation
    # writes (including rejected drafts) land only in the copy.
    second = eng.generate(prompt, max_tokens=64, timeout=300)
    assert second == ref
    snap = eng.allocator.snapshot()
    assert snap["cow_copies"] >= 1
    # Divergent continuation off the shared aligned prefix still
    # matches; the registered blocks were never corrupted by the
    # speculative writer.
    div = eng.generate(prompt[:4] + [9, 9], max_tokens=8, timeout=120)
    assert div == ref_div
    third = eng.generate(prompt, max_tokens=64, timeout=300)
    assert third == ref
    assert eng.stats["spec_proposed"] > 0
    eng.shutdown()


def test_engine_fixed_is_refused_and_the_default_is_paged():
    """There is one served engine: `engine='fixed'` (any value but
    'paged') raises and names it; the default builds a PagedLLMEngine
    without a warning."""
    import warnings as _warnings

    from ray_tpu.serve.llm import LLMDeployment

    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        dep = LLMDeployment("tiny", num_slots=2, max_len=32)
        assert isinstance(dep.engine, PagedLLMEngine)
        dep.engine.shutdown()
    with pytest.raises(ValueError, match="PagedLLMEngine"):
        LLMDeployment("tiny", engine="fixed", num_slots=2, max_len=32)


# ---------------------------------------------------------------------------
# engine: allocator-full admission queues (waits, not errors)
# ---------------------------------------------------------------------------
def test_allocator_full_requests_wait_then_complete(tiny_model):
    # Pool of 6 usable blocks (bs=4): one 16-token prompt plus one burst
    # of growth headroom needs all 6, so the second request cannot be
    # admitted until the first completes — it queues, it does not error.
    eng = make_engine(tiny_model, num_slots=2, max_len=32,
                      block_size=4, num_blocks=7, prefix_sharing=False)
    prompt_a = list(range(1, 17))
    prompt_b = list(range(101, 117))
    done = {}

    def run(key, prompt):
        done[key] = eng.generate(prompt, max_tokens=8, timeout=180)

    ta = threading.Thread(target=run, args=("a", prompt_a))
    tb = threading.Thread(target=run, args=("b", prompt_b))
    ta.start()
    tb.start()
    ta.join(timeout=180)
    tb.join(timeout=180)
    # Both completed — the loser of the block race WAITED (no error).
    assert len(done) == 2
    assert len(done["a"]) == 8 and len(done["b"]) == 8
    assert eng.stats["queue_waits"] >= 1
    assert eng.allocator.snapshot()["blocks_active"] == 0
    eng.shutdown()


def test_pool_deadlock_preempts_and_recomputes(tiny_model):
    # Both requests are admitted (8 usable blocks, 2 + headroom each) but
    # their decode growth needs 12 blocks total, and with max_burst=4
    # each grows one block per tick — the pool is exhausted with both
    # mid-flight no matter how admission interleaves.  When both stall
    # on growth the engine must preempt the younger one (free its
    # blocks, recompute its KV later) instead of deadlocking — and the
    # preempted stream's output must be identical to an uncontended run.
    prompts = [list(range(1, 9)), list(range(101, 109))]
    kw = dict(num_slots=2, max_len=32, block_size=4, prefill_chunk=16,
              max_burst=4, prefix_sharing=False)
    ref = make_engine(tiny_model, num_blocks=33, **kw)
    expect = [ref.generate(p, max_tokens=16, timeout=180) for p in prompts]
    ref.shutdown()

    eng = make_engine(tiny_model, num_blocks=9, **kw)
    done = {}

    def run(key, prompt):
        done[key] = eng.generate(prompt, max_tokens=16, timeout=180)

    threads = [threading.Thread(target=run, args=(i, p))
               for i, p in enumerate(prompts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert eng.stats["preemptions"] >= 1
    assert done[0] == expect[0] and done[1] == expect[1]
    assert eng.allocator.snapshot()["blocks_active"] == 0
    eng.shutdown()


# ---------------------------------------------------------------------------
# engine: chunked prefill bounds active streams' ITL
# ---------------------------------------------------------------------------
def test_chunked_prefill_bounds_itl_of_active_stream(tiny_model):
    eng = make_engine(tiny_model, num_slots=4, max_len=256,
                      block_size=16, num_blocks=65, prefill_chunk=16,
                      prefix_sharing=False)
    gaps = []
    got = []

    def stream_a():
        last = None
        for tok in eng.generate_stream(list(range(1, 9)),
                                       max_tokens=48, timeout=300):
            now = time.perf_counter()
            if last is not None:
                gaps.append(now - last)
            last = now
            got.append(tok)

    ta = threading.Thread(target=stream_a)
    ta.start()
    # Wait until A is decoding, then slam in a max-length prompt whose
    # full prefill takes many chunks.
    deadline = time.monotonic() + 60
    while not got and time.monotonic() < deadline:
        time.sleep(0.01)
    assert got, "stream A never started"
    long_prompt = list(range(1, 200))
    out_b = eng.generate(long_prompt, max_tokens=4, timeout=300)
    ta.join(timeout=300)
    assert len(got) == 48
    assert len(out_b) == 4
    # A's inter-token gap stays bounded while B's 199-token prompt
    # prefills 16 tokens per tick: decode was never starved for the
    # whole prefill (one unchunked prefill would be one giant gap).
    assert max(gaps) < 3.0, f"max ITL {max(gaps):.3f}s"
    eng.shutdown()


# ---------------------------------------------------------------------------
# bounded stream queues (both engines)
# ---------------------------------------------------------------------------
def _slow_consumer_drops(engine):
    stream = engine.generate_stream([1, 2, 3], max_tokens=64,
                                    timeout=120)
    with pytest.raises(StreamQueueFullError):
        for i, _ in enumerate(stream):
            time.sleep(1.0)        # consumer stalls; engine keeps going
            if i > 10:
                raise AssertionError("stream never dropped")
    # the engine is still healthy for other requests
    out = engine.generate([4, 5, 6], max_tokens=4, timeout=120)
    assert len(out) == 4


def test_stream_queue_bound_paged(tiny_model, monkeypatch):
    monkeypatch.setenv("RAY_TPU_SERVE_STREAM_QUEUE_MAX", "4")
    reset_config()
    try:
        eng = make_engine(tiny_model)
        _slow_consumer_drops(eng)
        eng.shutdown()
    finally:
        monkeypatch.delenv("RAY_TPU_SERVE_STREAM_QUEUE_MAX")
        reset_config()


# ---------------------------------------------------------------------------
# controller: per-handle autoscale stats expire
# ---------------------------------------------------------------------------
def test_controller_handle_stats_ttl():
    from ray_tpu.serve.controller import ServeController

    ctl = ServeController.__new__(ServeController)   # no cluster
    ctl._lock = threading.RLock()
    ctl._targets = {"app": {
        "num_replicas": 1,
        "config": {"autoscaling_config": {
            "target_ongoing_requests": 2, "min_replicas": 1,
            "max_replicas": 4, "upscale_delay_s": 0.0,
            "downscale_delay_s": 0.0}},
    }}
    ctl._last_scale = {}
    ctl._handle_stats = {}
    ctl._handle_stats_ttl_s = 0.2
    ctl._merged_gauges = None

    ctl.record_autoscale_stats("app", 10.0, handle_id="h1")
    ctl.record_autoscale_stats("app", 6.0, handle_id="h2")
    assert ctl._autoscale_signal("app") == 16.0
    # h2 keeps reporting; h1 goes silent and must age out
    time.sleep(0.25)
    ctl.record_autoscale_stats("app", 6.0, handle_id="h2")
    assert ctl._autoscale_signal("app") == 6.0
    assert "h1" not in ctl._handle_stats["app"]
    # all handles silent -> no signal at all (not a stale zero)
    time.sleep(0.25)
    assert ctl._autoscale_signal("app") is None


def test_controller_prefers_syncer_merged_gauges():
    from ray_tpu.serve.controller import ServeController

    ctl = ServeController.__new__(ServeController)
    ctl._lock = threading.RLock()
    ctl._targets = {"app": {
        "num_replicas": 1,
        "config": {"autoscaling_config": {
            "target_ongoing_requests": 2, "min_replicas": 1,
            "max_replicas": 4, "upscale_delay_s": 0.0,
            "downscale_delay_s": 1e9}},
    }}
    ctl._last_scale = {}
    ctl._handle_stats = {}
    ctl._handle_stats_ttl_s = 5.0
    # Syncer-merged replica gauges beat handle reports when present.
    ctl._merged_gauges = {"app": {"replicas": 1.0, "ongoing": 5.0,
                                  "queue_depth": 3.0}}
    ctl.record_autoscale_stats("app", 100.0, handle_id="h1")
    assert ctl._autoscale_signal("app") == 8.0
    # scaling decision consumes the merged signal: 8 > target 2 -> up
    with ctl._lock:
        tgt = ctl._targets["app"]
        asc = tgt["config"]["autoscaling_config"]
        per = ctl._autoscale_signal("app") / tgt["num_replicas"]
        assert per > asc["target_ongoing_requests"]


# ---------------------------------------------------------------------------
# daemon-side gauge aggregation TTL
# ---------------------------------------------------------------------------
def test_daemon_serve_state_aggregates_and_expires(monkeypatch):
    from ray_tpu.core.distributed.node_daemon import NodeDaemon

    d = NodeDaemon.__new__(NodeDaemon)   # no cluster
    d._serve_gauges = {}
    now = time.monotonic()
    d._serve_gauges[("app", "r0")] = {
        "ts": now, "gauges": {"ongoing": 2.0, "queue_depth": 1.0}}
    d._serve_gauges[("app", "r1")] = {
        "ts": now, "gauges": {"ongoing": 3.0, "queue_depth": 0.0}}
    d._serve_gauges[("app", "dead")] = {
        "ts": now - 3600, "gauges": {"ongoing": 50.0}}
    state = d._serve_state()
    assert state["app"]["replicas"] == 2       # dead replica swept
    assert state["app"]["ongoing"] == 5.0
    assert state["app"]["queue_depth"] == 1.0
    assert ("app", "dead") not in d._serve_gauges


def test_queue_full_drop_releases_kv_blocks_promptly(tmp_path, tiny_model,
                                                     monkeypatch):
    """Leak guard: a stream failed by StreamQueueFullError must release
    its KV blocks promptly (the engine frees them in _maybe_finish on
    the dropped flag, not at consumer GC time), and the arena still
    returns the store to baseline afterwards (store-quiescence)."""
    from ray_tpu.core.object_store import ObjectStore

    monkeypatch.setenv("RAY_TPU_SERVE_STREAM_QUEUE_MAX", "4")
    reset_config()
    store = ObjectStore(str(tmp_path / "kvleak"),
                        capacity=32 * 1024 * 1024, num_slots=64)
    try:
        base_used, base_objs = store.used, store.num_objects
        eng = make_engine(tiny_model, store=store)
        stream = eng.generate_stream([1, 2, 3], max_tokens=64,
                                     timeout=120)
        with pytest.raises(StreamQueueFullError):
            for i, _ in enumerate(stream):
                time.sleep(1.0)    # stalled consumer: queue overflows
                if i > 10:
                    raise AssertionError("stream never dropped")
        # The dropped request's blocks free on the engine loop's next
        # finish pass — promptly, NOT when the consumer object dies.
        deadline = time.monotonic() + 10
        active = None
        while time.monotonic() < deadline:
            active = eng.allocator.snapshot()["blocks_active"]
            if active == 0:
                break
            time.sleep(0.05)
        assert active == 0, f"dropped stream leaked {active} blocks"
        # Engine stays healthy and the pool is genuinely reusable.
        assert len(eng.generate([4, 5, 6], max_tokens=4,
                                timeout=120)) == 4
        eng.shutdown()
        assert store.used == base_used
        assert store.num_objects == base_objs
    finally:
        reset_config()
        store.disconnect()
        ObjectStore.destroy(str(tmp_path / "kvleak"))
