"""KV block allocator: refcounted block pool with prefix sharing + COW.

The host half of the paged KV cache (device half: PagedKVCache in
models/decoding.py).  The allocator owns which pool blocks belong to which
request, shares blocks between requests with a common prompt prefix
(refcounted, vLLM automatic-prefix-caching at block granularity), and
duplicates a shared partial block before a new owner appends into it
(copy-on-write — the engine runs the device-side copy_block, then swaps
the table entry the allocator hands back).

Block 0 is the reserved NULL block: never allocated, every unused table
entry points at it, so the compiled gather/scatter is always in-bounds.

A registered prefix is a chain of blocks, and everything about it costs
one pass over the prompt.  A block's KEY is (the registration of the
block before it, the block's own tokens): exact, because a registration
number is never handed out twice, yet one block's worth of tokens to
build, hash and hold.  A boundary's DIGEST is what the cluster exchanges
(`prefix_digests()` -> the handle's owner map <- `disagg.request_digests`;
a prefill actor's choice): SHA-1 over every token up to the boundary,
8 bytes each, cut to 16 hex digits.  The strings are a contract between
processes; `block_digests` reads a prompt's off one running hash.

What the blocks are to a sequence depends on the model
(`models.decoding.init_sequence_state`).  For a model whose every layer
keeps every position they are the whole sequence, which is what prefix
sharing, copy-on-write, speculation and KV shipping rest on.  A model
may keep two more kinds of state that this allocator does not own: a
bounded window of KV a slot for its sliding-window layers (a ring, not
recurrent and never zeroed: `models.decoding.PagedKVCache.wk / wv` for a
`TransformerConfig` with a layer pattern, `models.hybrid.HybridState`),
and recurrent state a slot for its state-space layers (`HybridState`),
both indexed by the engine's slot and held by whoever holds the slot.
The blocks then carry only the layers that keep every position, a prefix
hit would skip positions whose state nobody kept, and
`PagedLLMEngine` runs this allocator with `prefix_sharing=False` for
such a model (`lookup_prefix` finds nothing, `register_prefix` keeps
nothing, `cow` never copies).

The pool's bytes are carved out of the node's shared-memory object store
through the create-then-fill seam (ObjectStore.create_arena).
`bytes_per_block` is the engine's to say and is the pooled leaves' bytes
over the blocks (`resident_bytes()["kv_paged"]` of whatever state the
model brought: K and V rows of the full layers, or one latent row a
layer), never a product of head counts taken here.  The arena
reservation makes KV pressure visible to the store accounting/syncer
plane, and releasing it returns the store to quiescence — the leak-guard
test asserts used/num_objects return to baseline.  Engines running
without a store (standalone, unit tests) skip the arena.
"""
from __future__ import annotations

import hashlib
import struct
import threading
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple


def _token_bytes(tokens) -> bytes:
    """The bytes every digest is taken over: each token as 8 bytes,
    little-endian, signed.  Packed by `struct`, not numpy: the handle
    and the proxy hash a request's prefixes through this module and
    import no numpy otherwise (its import cost the first routed request
    of a process ~0.4 s)."""
    if not hasattr(tokens, "__len__"):
        tokens = list(tokens)
    return struct.pack("<%dq" % len(tokens), *tokens)


def prefix_digest(tokens) -> str:
    """Stable cluster-wide digest of a cumulative token prefix.  Keyed
    on the raw token values (not positions), so two replicas that
    prefilled the same prompt prefix — or a prefill actor that shipped
    it — derive the SAME digest and the prefix registry can match them
    without ever moving token lists through the GCS."""
    h = hashlib.sha1()
    h.update(_token_bytes(tokens))
    return h.hexdigest()[:16]


def block_digests(tokens, block_size: int, first: int = 1) -> List[str]:
    """``prefix_digest(tokens[:k * block_size])`` for k = ``first`` ..
    ``len(tokens) // block_size``, from ONE running hash: a block's
    bytes go in once and the boundary's digest is read off a copy, so
    the whole list costs one pass over the prompt, not one a block.
    The strings are `prefix_digest`'s own (SHA-1 is a stream: the copy
    after k blocks has seen exactly the first k blocks' bytes)."""
    n_full = len(tokens) // block_size
    if first > n_full:
        return []
    step = 8 * block_size
    buf = memoryview(_token_bytes(tokens))
    h = hashlib.sha1()
    h.update(buf[:(first - 1) * step])
    out = []
    for k in range(first, n_full + 1):
        h.update(buf[(k - 1) * step:k * step])
        out.append(h.copy().hexdigest()[:16])
    return out


class KVBlockAllocator:
    """Free-list + refcounts + prefix map over ``num_blocks`` pool blocks
    of ``block_size`` tokens each (block 0 reserved).

    Prefix map: a block's key = (the registration of the block before
    it, the block's own <= ``block_size`` tokens); the first block's
    parent is registration 0.  A registration number is handed out once
    per (block, key) and never again, so a key names one exact token
    prefix: a hit means every token of the covered prefix is equal
    (exact, not positional, and no digest stands in for identity), yet
    a key costs a block, not a prefix, to build, hash and hold.  Every
    walk starts at the root and follows the prompt block by block; a
    block that lost its registration (evicted, unregistered, re-keyed)
    ends the chain there, and what hung below it stays unreachable
    until it is evicted in its turn or the prompt is registered again.
    Freed blocks that carry a prefix key become "cached-free": refcount
    0, contents intact, LRU-evictable when the free list runs dry.  A
    lookup hit on a cached-free block revives it (refcount 1) without
    re-prefilling — that is the block-reuse counter the acceptance
    criterion asserts on.
    """

    def __init__(self, num_blocks: int, block_size: int, *,
                 store: Any = None, bytes_per_block: int = 0,
                 prefix_sharing: bool = True, arena_name: str = "kv-pool"):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the null block)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.prefix_sharing = prefix_sharing
        self._lock = threading.Lock()
        self._free: deque = deque(range(1, num_blocks))
        self._ref = [0] * num_blocks
        # prefix key (parent registration, block tokens) -> block id;
        # insertion order over CACHED (refcount 0) entries is the
        # eviction LRU.
        self._by_key: Dict[tuple, int] = {}
        self._key_of: Dict[int, tuple] = {}
        # block id -> its registration (0: none), the parent half of
        # its children's keys; never reused, unlike the block id.
        self._reg = [0] * num_blocks
        self._next_reg = 1
        # aligned key -> cluster-stable digest of the token prefix it
        # ends (computed once at registration; the gauge loop publishes
        # these to the cluster prefix registry).
        self._digest_of: Dict[tuple, str] = {}
        self._cached: "OrderedDict[int, None]" = OrderedDict()
        # last block's key -> metadata (last-token logits) so a whole-
        # prompt hit can sample its first token without any forward.
        self._meta: Dict[tuple, Any] = {}
        self.stats = {"reuse_hits": 0, "reuse_misses": 0, "cow_copies": 0,
                      "evictions": 0, "alloc_failures": 0}
        self._arena = None
        self.arena_bytes = 0
        if store is not None and bytes_per_block > 0:
            self._reserve_arena(store, bytes_per_block, arena_name)

    # -- shm arena ------------------------------------------------------
    def _reserve_arena(self, store, bytes_per_block: int,
                       arena_name: str) -> None:
        from ray_tpu.core.ids import ObjectID

        oid = ObjectID.from_random()
        size = self.num_blocks * bytes_per_block
        try:
            self._arena = store.create_arena(oid, size)
            self.arena_bytes = size
        except Exception:  # noqa: BLE001 — pool works unreserved
            self._arena = None

    def release(self) -> None:
        """Drop the shm arena reservation (engine shutdown)."""
        if self._arena is not None:
            self._arena.release()
            self._arena = None
            self.arena_bytes = 0

    # -- core alloc/free ------------------------------------------------
    def _evict_cached(self) -> Optional[int]:
        """Reclaim the least-recently-registered cached-free block."""
        if not self._cached:
            return None
        blk, _ = self._cached.popitem(last=False)
        self._forget_locked(blk)
        self.stats["evictions"] += 1
        return blk

    def _forget_locked(self, blk: int) -> None:
        """Drop a block's registration: key, meta and digest together."""
        key = self._key_of.pop(blk, None)
        if key is not None:
            self._by_key.pop(key, None)
            self._meta.pop(key, None)
            self._digest_of.pop(key, None)
        self._reg[blk] = 0

    def can_alloc(self, n: int) -> bool:
        with self._lock:
            return len(self._free) + len(self._cached) >= n

    def alloc(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` exclusive blocks (refcount 1 each) or None if
        the pool can't cover it even after evicting cached prefixes —
        the engine queues the request instead of erroring."""
        with self._lock:
            if len(self._free) + len(self._cached) < n:
                self.stats["alloc_failures"] += 1
                return None
            out = []
            for _ in range(n):
                blk = self._free.popleft() if self._free \
                    else self._evict_cached()
                self._ref[blk] = 1
                out.append(blk)
            return out

    def free(self, blocks: List[int]) -> None:
        """Drop one reference per block.  A block reaching refcount 0
        returns to the free list unless it carries a prefix key — then
        it parks in the cached-free LRU with contents intact."""
        with self._lock:
            for blk in blocks:
                if blk <= 0:
                    continue
                self._ref[blk] -= 1
                if self._ref[blk] > 0:
                    continue
                self._ref[blk] = 0
                if self.prefix_sharing and blk in self._key_of:
                    self._cached[blk] = None
                    self._cached.move_to_end(blk)
                else:
                    self._free.append(blk)

    # -- prefix sharing -------------------------------------------------
    def lookup_prefix(self, tokens: List[int]
                      ) -> Tuple[List[int], int, Optional[Any]]:
        """Longest registered prefix of ``tokens``: returns (blocks,
        covered_tokens, meta) with every returned block increffed.
        Coverage is block-aligned except a whole-prompt hit, whose
        (possibly partial) tail block and stored last-token logits ride
        back too — the engine skips prefill entirely on that path."""
        if not self.prefix_sharing:
            return [], 0, None
        bs = self.block_size
        n = len(tokens)
        with self._lock:
            blocks, key, parent = self._walk_locked(tokens)
            if len(blocks) == n // bs and n % bs:
                # The whole aligned chain: a whole-prompt key on the
                # partial tail extends it.
                tail_key = (parent, tuple(tokens[n - n % bs:]))
                tail = self._by_key.get(tail_key)
                if tail is not None:
                    blocks.append(tail)
                    key = tail_key
            if not blocks:
                self.stats["reuse_misses"] += 1
                return [], 0, None
            # Nothing was taken during the walk, so a chain is all or
            # nothing and refcounts stay balanced.
            for blk in blocks:
                if self._ref[blk] == 0:
                    self._cached.pop(blk, None)
                self._ref[blk] += 1
            self.stats["reuse_hits"] += len(blocks)
            covered = min(len(blocks) * bs, n)
            meta = self._meta.get(key) if covered == n else None
            return blocks, covered, meta

    def _walk_locked(self, tokens
                     ) -> Tuple[List[int], Optional[tuple], int]:
        """Follow ``tokens`` from the root through the registered
        aligned blocks: (the chain up to the first missing link, the
        last link's key, its registration).  One block's tokens are
        built and hashed a step, whatever the depth."""
        bs = self.block_size
        blocks, key, parent = [], None, 0
        for i in range(len(tokens) // bs):
            k = (parent, tuple(tokens[i * bs:(i + 1) * bs]))
            blk = self._by_key.get(k)
            if blk is None:
                break
            blocks.append(blk)
            key, parent = k, self._reg[blk]
        return blocks, key, parent

    def register_prefix(self, tokens: List[int], blocks: List[int],
                        meta: Any = None) -> None:
        """Publish a prefilled prompt's blocks for reuse: each aligned
        chunk keyed under the registration of the chunk before it, plus
        the whole-prompt key on the tail (which may be partial).
        ``meta`` (last-token logits) is stored under the last block's
        key.  Does NOT change refcounts — the caller still owns its
        references; blocks become cached-free when the last owner frees
        them."""
        if not self.prefix_sharing:
            return
        bs = self.block_size
        n_full = len(tokens) // bs
        n_keys = -(-len(tokens) // bs)
        with self._lock:
            digests = None
            key, parent = None, 0
            for i, blk in enumerate(blocks[:n_keys]):
                key = (parent, tuple(tokens[i * bs:(i + 1) * bs]))
                if self._register_locked(key, blk) and i < n_full:
                    # One pass over the prompt, and only for a prompt
                    # that registers something new.
                    digests = digests or block_digests(tokens, bs)
                    self._digest_of[key] = digests[i]
                parent = self._reg[self._by_key[key]]
            if meta is not None and len(blocks) >= n_keys > 0:
                self._meta[key] = meta

    def _register_locked(self, key: tuple, blk: int) -> bool:
        """Register ``blk`` under ``key`` unless the key is taken: by
        ``blk`` itself (nothing to do) or by another block (the first
        registration wins; its content already matches the key).  True
        when a new registration was made."""
        if key in self._by_key:
            return False
        # A block registered under another key gives that one up.
        self._forget_locked(blk)
        self._by_key[key] = blk
        self._key_of[blk] = key
        self._reg[blk] = self._next_reg
        self._next_reg += 1
        return True

    def adopt(self, tokens: List[int], meta: Any = None
              ) -> Optional[List[int]]:
        """Adopt-path for KV frames received over the transfer plane
        (disaggregated prefill handoff / live migration): allocate
        blocks covering ``tokens``, register them as a reusable prefix,
        and return the block ids STILL REFERENCED — the engine scatters
        the received frame into them on-device, then calls ``free`` to
        park them cached-free (contents intact, LRU-evictable).  The
        next lookup of the prompt walks the normal prefix-hit path with
        zero recompute.  None when the pool can't cover the frame (the
        caller falls back to recompute)."""
        if not self.prefix_sharing or not tokens:
            return None
        bs = self.block_size
        need = -(-len(tokens) // bs)
        blocks = self.alloc(need)
        if blocks is None:
            return None
        self.register_prefix(tokens, blocks, meta=meta)
        return blocks

    def prefix_digests(self, limit: int = 0) -> List[str]:
        """Digests of the block-ALIGNED registered prefixes (the
        publishable half of the prefix map: whole-prompt partial-tail
        keys stay local — a remote replica can only splice aligned
        chains into a longer prompt).  Most-recently-registered last;
        ``limit`` > 0 keeps the newest that many (gauge-payload bound)."""
        with self._lock:
            out = list(self._digest_of.values())
        if limit > 0 and len(out) > limit:
            out = out[-limit:]
        return out

    def unregister_block(self, blk: int) -> None:
        """Drop a block's prefix key (its content is about to diverge
        from the key — the sole-owner in-place-append path)."""
        with self._lock:
            self._forget_locked(blk)
            self._cached.pop(blk, None)

    def cow(self, blk: int) -> Tuple[int, bool]:
        """Prepare ``blk`` for in-place writes by its caller (who holds
        one reference).  Shared or registered blocks are duplicated:
        returns (new_block, True) and the caller must device-copy
        blk -> new_block and swap its table entry (its reference moves
        to the copy).  A sole-owner unregistered block is returned
        as-is: (blk, False)."""
        with self._lock:
            shared = self._ref[blk] > 1
            registered = blk in self._key_of
            if not shared and not registered:
                return blk, False
            if not shared and registered:
                # Sole owner of a registered block: cheaper to keep the
                # pristine copy for future hits only when a spare block
                # exists; otherwise just unregister and write in place.
                if not self._free and not self._cached:
                    self._forget_locked(blk)
                    return blk, False
            new = self._free.popleft() if self._free \
                else self._evict_cached()
            if new is None:
                # Pool exhausted and the block is SHARED: the caller
                # must wait for capacity like any other allocation.
                raise MemoryError("KV pool exhausted during COW")
            self._ref[new] = 1
            # Caller's reference migrates to the copy.
            self._ref[blk] -= 1
            if self._ref[blk] == 0:
                if blk in self._key_of:
                    self._cached[blk] = None
                    self._cached.move_to_end(blk)
                else:
                    self._free.append(blk)
            self.stats["cow_copies"] += 1
            return new, True

    # -- introspection --------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            usable = self.num_blocks - 1
            free = len(self._free)
            cached = len(self._cached)
            active = usable - free - cached
            return {
                "blocks_total": usable,
                "blocks_free": free,
                "blocks_cached": cached,
                "blocks_active": active,
                "occupancy": round(active / usable, 4) if usable else 0.0,
                "prefixes_registered": len(self._by_key),
                "arena_bytes": self.arena_bytes,
                **self.stats,
            }
