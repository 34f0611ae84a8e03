"""Disaggregated serving plane: prefix-registry lifecycle, KV frame
gather/scatter, migration tickets, and warm-migrated streams.

Covers the registry write side (allocator digests, gauge-loop `state`
push), the federation read side (daemon `_replicas` submap -> GCS merge
-> controller `prefix_owners` routing, swept when the owner dies), the
handle's prefix-affinity pick, migration-ticket roundtrip through the
GCS KV, and the headline invariant: a warm-migrated stream's output is
byte-identical to its recompute-fallback twin.
"""
import contextlib
import os
import signal
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve

BS = 4  # block size used throughout


@pytest.fixture(scope="module", autouse=True)
def ray_cluster():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _tiny_engine(**kw):
    import jax

    from ray_tpu.models import configs, init_params
    from ray_tpu.serve.llm import PagedLLMEngine

    cfg = configs.get("tiny")
    params = init_params(jax.random.key(0), cfg)
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", BS)
    kw.setdefault("prefill_chunk", 8)
    return PagedLLMEngine(cfg, params, **kw)


def _stopped_engine(**kw):
    """Engine with the loop thread parked: ticks run only when the test
    calls _tick, so mid-flight state is deterministic."""
    e = _tiny_engine(**kw)
    e._stop = True
    e._work.set()
    e._thread.join(timeout=10)
    return e


def _tick(e):
    with e._tick_lock:
        e._tick()


def _counter_val(c, tags):
    return dict(c.samples()).get(c.key(tags), 0.0)


# ---------------------------------------------------------------------------
# allocator digests: aligned-only publication, eviction unregisters
# ---------------------------------------------------------------------------
def test_prefix_digests_aligned_only_and_deterministic():
    from ray_tpu.serve.kv_cache import KVBlockAllocator, prefix_digest

    a = KVBlockAllocator(16, BS, prefix_sharing=True)
    aligned = list(range(1, 9))       # 8 tokens = 2 full blocks
    ragged = list(range(11, 17))      # 6 tokens = partial tail
    b1 = a.alloc(2)
    a.register_prefix(aligned, b1)
    b2 = a.alloc(2)
    a.register_prefix(ragged, b2)
    digests = a.prefix_digests()
    # Only block-ALIGNED keys publish (a partial-tail chain can't be
    # adopted block-for-block by a remote pool).
    assert prefix_digest(tuple(aligned)) in digests
    assert prefix_digest(tuple(aligned[:BS])) in digests
    assert prefix_digest(tuple(ragged)) not in digests
    # Deterministic across allocators/processes: the digest is a pure
    # function of the token values.
    b = KVBlockAllocator(16, BS, prefix_sharing=True)
    bb = b.alloc(2)
    b.register_prefix(aligned, bb)
    assert prefix_digest(tuple(aligned)) in b.prefix_digests()


def test_eviction_retires_published_digest():
    """Refcount correctness: once the owning allocator evicts a
    registered prefix (cached-free blocks reclaimed under pressure),
    its digest must leave the published set — a remote hit on it would
    route to a replica that no longer holds the blocks."""
    from ray_tpu.serve.kv_cache import KVBlockAllocator, prefix_digest

    a = KVBlockAllocator(9, BS, prefix_sharing=True)  # blocks 1..8 usable
    aligned = list(range(1, 9))
    blocks = a.alloc(2)
    a.register_prefix(aligned, blocks)
    a.free(blocks)  # parks cached-free, still registered + published
    assert prefix_digest(tuple(aligned)) in a.prefix_digests()
    # Pool pressure reclaims the cached-free registered blocks.
    grab = a.alloc(8)
    assert grab is not None
    assert a.prefix_digests() == []


def test_prefix_digest_limit_bounds_publication():
    from ray_tpu.serve.kv_cache import KVBlockAllocator, prefix_digest

    a = KVBlockAllocator(64, BS, prefix_sharing=True)
    keys = []
    for i in range(6):
        toks = [100 * (i + 1) + j for j in range(BS)]
        blocks = a.alloc(1)
        a.register_prefix(toks, blocks)
        keys.append(prefix_digest(tuple(toks)))
    out = a.prefix_digests(limit=2)
    assert len(out) == 2
    assert set(out) <= set(keys)


# ---------------------------------------------------------------------------
# frame gather/scatter + import geometry
# ---------------------------------------------------------------------------
def test_gather_scatter_roundtrip():
    import jax
    import numpy as np

    from ray_tpu.models import configs
    from ray_tpu.models.decoding import (
        gather_blocks,
        init_paged_cache,
        scatter_blocks,
    )

    cfg = configs.get("tiny")
    src = init_paged_cache(cfg, 8, BS)
    key = jax.random.key(1)
    src = type(src)(k=jax.random.normal(key, src.k.shape, src.k.dtype),
                    v=jax.random.normal(key, src.v.shape, src.v.dtype))
    frame = np.asarray(jax.device_get(gather_blocks(src, [2, 5, 3])))
    assert frame.shape[:3] == (2, cfg.n_layers, 3)
    dst = init_paged_cache(cfg, 8, BS)
    dst = scatter_blocks(dst, [1, 2, 3], frame)
    np.testing.assert_array_equal(np.asarray(dst.k[:, 1]),
                                  np.asarray(src.k[:, 2]))
    np.testing.assert_array_equal(np.asarray(dst.v[:, 3]),
                                  np.asarray(src.v[:, 3]))


def test_import_prefix_rejects_bad_geometry():
    import numpy as np

    eng = _tiny_engine()
    try:
        toks = list(range(1, 9))
        L, H, D = eng.cfg.n_layers, eng.cfg.n_kv_heads, eng.cfg.head_dim
        # Wrong block size for this pool.
        good = np.zeros((2, L, 2, BS, H, D), np.float32)
        assert eng.import_prefix(toks, good, BS * 2) == 0
        # Wrong layer count.
        bad = np.zeros((2, L + 1, 2, BS, H, D), np.float32)
        assert eng.import_prefix(toks, bad, BS) == 0
        # Too few blocks for the tokens.
        short = np.zeros((2, L, 1, BS, H, D), np.float32)
        assert eng.import_prefix(toks, short, BS) == 0
        # Well-formed frame still imports.
        assert eng.import_prefix(toks, good, BS) == 2
    finally:
        eng.shutdown()


def test_request_digests_longest_first():
    from ray_tpu.serve.disagg import request_digests
    from ray_tpu.serve.kv_cache import prefix_digest

    toks = list(range(1, 15))  # 14 tokens: boundaries at 4, 8, 12
    out = request_digests(toks, BS)
    assert [n for n, _ in out] == [12, 8, 4]
    assert out[0][1] == prefix_digest(tuple(toks[:12]))
    assert request_digests([1, 2], BS) == []
    # Bounded for very long prompts.
    long = list(range(1, 401))
    assert len(request_digests(long, BS, max_bounds=8)) == 8


# ---------------------------------------------------------------------------
# migration tickets: GCS-KV roundtrip, at-most-once, TTL, size bound
# ---------------------------------------------------------------------------
def test_migration_ticket_roundtrip_and_at_most_once():
    import numpy as np

    from ray_tpu.serve.disagg import (
        consume_migration_ticket,
        publish_migration_tickets,
    )

    kv = np.arange(2 * 2 * 2 * BS * 4 * 16, dtype=np.float32).reshape(
        (2, 2, 2, BS, 4, 16))
    t = {"request_id": "rid-roundtrip", "tokens": list(range(8)),
         "block_size": BS, "kv": kv}
    assert publish_migration_tickets("serve:app#g1#0", [t]) == 1
    got = consume_migration_ticket("rid-roundtrip")
    assert got is not None
    assert got["tokens"] == list(range(8))
    assert got["block_size"] == BS
    np.testing.assert_array_equal(got["kv"], kv)
    assert got["replica"] == "serve:app#g1#0"
    # Fetch-and-delete: a second consumer sees nothing.
    assert consume_migration_ticket("rid-roundtrip") is None
    assert consume_migration_ticket("rid-never-published") is None


def test_migration_publish_emits_trace_span():
    """A published ticket emits a serve.kv.migrate span carrying the
    REQUEST's id as its trace id, so `ray-tpu serve trace <id>` shows
    the migration hop on the same track as the request's other legs."""
    import numpy as np

    from ray_tpu.core.config import get_config
    from ray_tpu.serve.disagg import (
        consume_migration_ticket,
        publish_migration_tickets,
    )
    from ray_tpu.util import tracing

    cfg = get_config()
    saved = cfg.serve_trace_enabled
    cfg.serve_trace_enabled = True
    try:
        tracing.drain()
        kv = np.zeros((2, 2, 2, BS, 4, 16), np.float32)
        assert publish_migration_tickets(
            "serve:app#g1#0",
            [{"request_id": "rid-span", "tokens": list(range(8)),
              "block_size": BS, "kv": kv}]) == 1
        spans = [s for s in tracing.drain()
                 if s["name"] == "serve.kv.migrate"]
        assert len(spans) == 1
        assert spans[0]["trace_id"] == "rid-span"
        assert spans[0]["attrs"]["side"] == "publish"
        assert spans[0]["attrs"]["nbytes"] == kv.nbytes
    finally:
        cfg.serve_trace_enabled = saved
        consume_migration_ticket("rid-span")  # delete the ticket


def test_migration_ticket_size_bound_and_ttl():
    import pickle

    import numpy as np

    from ray_tpu.api import _global_worker
    from ray_tpu.core.config import get_config
    from ray_tpu.serve.disagg import (
        consume_migration_ticket,
        publish_migration_tickets,
    )

    cfg = get_config()
    # Oversized frame: dropped, the stream takes the recompute fallback.
    per_block = 2 * 2 * BS * 4 * 16 * 4  # bytes per block in this frame
    n_big = cfg.serve_kv_migrate_inline_max_bytes // per_block + 2
    big = np.zeros((2, 2, n_big, BS, 4, 16), np.float32)
    assert publish_migration_tickets(
        "r", [{"request_id": "rid-big", "tokens": [1], "block_size": BS,
               "kv": big}]) == 0
    assert consume_migration_ticket("rid-big") is None
    # Stale ticket: published, but past the TTL on consume.
    kv = np.zeros((2, 2, 2, BS, 4, 16), np.float32)
    assert publish_migration_tickets(
        "r", [{"request_id": "rid-stale", "tokens": [1, 2, 3, 4],
               "block_size": BS, "kv": kv}]) == 1
    w = _global_worker()
    key = b"migrate:rid-stale"
    blob = pickle.loads(w.kv_get("serve", key))
    blob["ts"] = time.time() - cfg.serve_kv_migrate_ttl_s - 10
    w.kv_put("serve", key, pickle.dumps(blob))
    assert consume_migration_ticket("rid-stale") is None


# ---------------------------------------------------------------------------
# the headline invariant: warm-migrated stream == recompute twin
# ---------------------------------------------------------------------------
def test_warm_migration_byte_identical_to_recompute_twin():
    prompt = list(range(1, 19))
    ref_eng = _tiny_engine()
    try:
        ref = ref_eng.generate(prompt, max_tokens=24, timeout=120)
    finally:
        ref_eng.shutdown()

    # Source engine, manually ticked so the export happens mid-decode.
    src = _stopped_engine()
    gen = src.generate_stream(prompt, max_tokens=24,
                              trace={"trace_id": "rid-mig"})
    out = []

    def consume():      # abandoned at the export: its wait then times out
        with contextlib.suppress(TimeoutError):
            out.extend(gen)

    th = threading.Thread(target=consume, daemon=True)
    th.start()
    for _ in range(200):
        _tick(src)
        req = src._slots[0]
        if req is not None and not req.prefilling and req.out_tokens:
            break
        time.sleep(0.005)
    time.sleep(0.2)  # let the consumer drain what's emitted so far
    delivered = list(out)
    assert delivered, "no tokens delivered before export"
    tickets = src.export_streams()
    assert tickets and tickets[0]["request_id"] == "rid-mig"
    tkt = tickets[0]
    # Exported context covers written KV only: the last emitted token's
    # KV is the next decode input and must stay out.
    assert len(tkt["tokens"]) < len(prompt) + len(src._slots[0].out_tokens)

    def run_resumed(eng):
        rest = []
        res = eng.generate_stream(prompt, max_tokens=24,
                                  resume_tokens=delivered,
                                  trace={"trace_id": "rid-mig"})
        t2 = threading.Thread(
            target=lambda: [rest.append(t) for t in res], daemon=True)
        t2.start()
        deadline = time.monotonic() + 60
        while t2.is_alive() and time.monotonic() < deadline:
            _tick(eng)
            time.sleep(0.002)
        t2.join(timeout=10)
        assert not t2.is_alive(), "resumed stream never finished"
        return rest

    # Warm twin: adopts the exported frame, then resumes.
    warm = _stopped_engine()
    n = warm.import_prefix(tkt["tokens"], tkt["kv"], tkt["block_size"])
    assert n > 0
    hits0 = warm.stats["prefix_hits"]
    warm_rest = run_resumed(warm)
    assert warm.stats["prefix_hits"] > hits0  # resumed ctx hit the chain

    # Recompute twin: no import, same resume.
    cold = _stopped_engine()
    cold_rest = run_resumed(cold)

    assert delivered + warm_rest == ref
    assert delivered + cold_rest == ref
    assert warm_rest == cold_rest


# ---------------------------------------------------------------------------
# registry federation: replica state -> daemon -> GCS -> routing
# ---------------------------------------------------------------------------
_REG_TOKENS = [7, 11, 13, 17, 19, 23, 29, 31]  # two aligned blocks


def _routing(app):
    from ray_tpu.serve.controller import get_or_create_controller

    return ray_tpu.get(
        get_or_create_controller().get_routing.remote(app), timeout=30)


@pytest.mark.slow
def test_registry_publish_lookup_and_death_sweep(tmp_path):
    from ray_tpu.serve.kv_cache import prefix_digest

    reg_tokens = list(_REG_TOKENS)
    # The supervisor restarts a SIGKILLed replica under the SAME name,
    # so the fake app must model a real engine honestly: a restarted
    # incarnation starts with an EMPTY allocator and publishes no
    # digests.  First boot leaves a sentinel; later boots see it.
    sentinel = str(tmp_path / "first_incarnation")

    class RegistryApp:
        """Minimal deployment exercising the registry write side without
        an engine: publishes the digests of reg_tokens like a paged
        replica whose allocator registered that prompt.  Defined inside
        the test so it pickles by value into the worker."""

        def __init__(self):
            self._first = not os.path.exists(sentinel)
            if self._first:
                with open(sentinel, "w") as f:
                    f.write("x")

        def serve_state(self):
            from ray_tpu.serve.kv_cache import prefix_digest as pd

            prefixes = [pd(tuple(reg_tokens)),
                        pd(tuple(reg_tokens[:BS]))] if self._first else []
            return {"role": "decode", "block_size": BS,
                    "prefixes": prefixes}

        def __call__(self, request):
            return {"pid": os.getpid()}

    serve.run(serve.deployment(RegistryApp).bind(), name="disagg_reg")
    try:
        digest = prefix_digest(tuple(_REG_TOKENS))
        owner, routing = None, {}
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            routing = _routing("disagg_reg")
            owner = (routing.get("prefix_owners") or {}).get(digest)
            if owner:
                break
            time.sleep(0.5)
        assert owner, f"digest never published into routing: {routing}"
        assert owner in routing["replicas"]
        assert routing.get("roles", {}).get(owner) == "decode"
        assert routing.get("kv_block_size") == BS
        # Cross-replica lookup: a fresh handle (a different "replica"'s
        # view) resolves the owner for a token-shaped request.
        h = serve.get_app_handle("disagg_reg")
        h._refresh(force=True)
        prefer, applicable = h._prefix_hint(
            ({"tokens": list(_REG_TOKENS) + [99, 98]},), {})
        assert applicable and prefer == owner
        pid = ray_tpu.get(ray_tpu.get_actor(owner).getpid.remote(),
                          timeout=30)
        # SIGKILL the owner: its registry entries must stop routing.
        os.kill(pid, signal.SIGKILL)
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            routing = _routing("disagg_reg")
            owners = routing.get("prefix_owners") or {}
            live = routing["replicas"]
            if owners.get(digest) != owner or owner not in live:
                # Either swept, or remapped to a live replacement
                # replica — never the dead name.
                assert all(o in live for o in owners.values())
                break
            time.sleep(0.5)
        else:
            raise AssertionError(f"stale owner survived SIGKILL: {routing}")
    finally:
        serve.delete("disagg_reg")


def test_handle_prefix_affinity_pick_and_counters():
    """Unit-level affinity: prefer the owner while its load allows, fall
    back (and count a miss) when it is clearly overloaded."""
    from ray_tpu.serve.handle import DeploymentHandle
    from ray_tpu.serve.kv_cache import prefix_digest

    h = DeploymentHandle.__new__(DeploymentHandle)
    h._app = "affinity_unit"
    h._lock = threading.Lock()
    h._replicas = {"r1": object(), "r2": object()}
    h._outstanding = {"r1": 0, "r2": 0}
    h._model_id = None
    h._model_affinity = {}
    toks = list(range(1, 9))
    h._prefix_owners = {prefix_digest(tuple(toks)): "r2"}
    h._kv_block_size = BS

    prefer, applicable = h._prefix_hint(({"tokens": toks + [50]},), {})
    assert (prefer, applicable) == ("r2", True)
    name, _ = h._pick_replica(prefer=prefer)
    assert name == "r2"
    h._outstanding["r2"] = 0  # undo the pick's increment
    # Non-token request: affinity not applicable.
    assert h._prefix_hint(({"x": 1},), {}) == (None, False)
    # Unknown prefix: applicable, no owner.
    assert h._prefix_hint(({"tokens": [200, 201, 202, 203, 204]},),
                          {}) == (None, True)
    # Overloaded owner: the load guard rejects the hint.
    h._outstanding["r2"] = 50
    name, _ = h._pick_replica(prefer="r2")
    assert name == "r1"
    # Counters: hit and miss both land in the kv_events counter.
    from ray_tpu.serve import observability

    c = observability.metrics()["kv_events"]
    hit_tags = {"app": "affinity_unit", "event": "remote_prefix_hit"}
    miss_tags = {"app": "affinity_unit", "event": "remote_prefix_miss"}
    base_hit = _counter_val(c, hit_tags)
    base_miss = _counter_val(c, miss_tags)
    h._count_prefix_route("r2", True, "r2")
    h._count_prefix_route("r2", True, "r1")
    h._count_prefix_route(None, False, "r1")  # not applicable: no count
    assert _counter_val(c, hit_tags) == base_hit + 1
    assert _counter_val(c, miss_tags) == base_miss + 1


# ---------------------------------------------------------------------------
# end-to-end: drain mid-stream migrates warm, output byte-identical
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_drain_migrates_stream_warm_end_to_end():
    from ray_tpu.serve.llm import LLMDeployment

    prompt = list(range(1, 25))
    serve.run(
        serve.deployment(LLMDeployment).options(num_replicas=2).bind(
            "tiny", engine="paged", num_slots=4, max_len=128,
            block_size=BS, prefill_chunk=8),
        name="disagg_drain")
    try:
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if serve.status().get("disagg_drain", {}).get("ready", 0) >= 2:
                break
            time.sleep(1.0)
        h = serve.get_app_handle("disagg_drain").options(
            method_name="stream")
        # Reference output from a local twin engine (same cfg/seed).
        ref_eng = _tiny_engine(max_len=128)
        try:
            ref = ref_eng.generate(prompt, max_tokens=48, timeout=300)
        finally:
            ref_eng.shutdown()

        resp = h.remote_streaming({"tokens": prompt, "max_tokens": 48})
        it = iter(resp)
        got = [next(it)["token"] for _ in range(4)]
        # Find the serving replica and drain it mid-stream.
        serving = None
        for name in _routing("disagg_drain")["replicas"]:
            st = ray_tpu.get(ray_tpu.get_actor(name).stats.remote(),
                             timeout=30)
            if st["streams"] > 0:
                serving = name
                break
        assert serving is not None
        ray_tpu.get_actor(serving).drain.remote(timeout_s=10)
        got += [item["token"] for item in it]
        assert got == ref, "migrated stream diverged from reference"
        assert resp.resumes >= 1
        # Warm, not recompute: a survivor's engine imported the blocks.
        migrated = 0
        for name in _routing("disagg_drain")["replicas"]:
            if name == serving:
                continue
            try:
                st = ray_tpu.get(
                    ray_tpu.get_actor(name).handle_request.remote(
                        "stats", (), {}), timeout=30)
                migrated += st.get("migrated_blocks", 0)
            except Exception:  # noqa: BLE001 replica mid-restart
                pass
        assert migrated > 0, "drain did not migrate any KV blocks"
    finally:
        serve.delete("disagg_drain")
