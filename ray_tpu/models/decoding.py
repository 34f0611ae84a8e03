"""Autoregressive decoding: KV cache, compiled prefill/decode steps.

The serving-side compute path (reference has none in-repo; BASELINE.json
north-star names "Serve req/s + p50 TTFT" with continuous batching).
Design for XLA: fixed-shape slot-batched KV cache — `prefill` fills one
slot from a (padded) prompt, `decode_step` advances ALL active slots one
token in a single fused program.  Shapes never depend on request count, so
both functions compile once per (slot_count, bucket) and the continuous-
batching engine (ray_tpu.serve.llm) swaps requests in and out of slots
between steps.

Cache layout: k/v (L, S, T_max, H_kv, D) with S = slots; per-slot lengths
(S,) drive the attention mask.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.ops.attention import paged_attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rotary import apply_rope

_NEG_INF = -1e30


@dataclasses.dataclass
class KVCache:
    k: jax.Array          # (L, S, T, Hkv, D)
    v: jax.Array
    lengths: jax.Array    # (S,) int32 — tokens currently in each slot


jax.tree_util.register_dataclass(KVCache, ["k", "v", "lengths"], [])


def cache_shardings(mesh):
    """NamedShardings for the KVCache leaves, defined NEXT TO the
    (L, S, T, Hkv, D) layout they index: kv-heads split over the mesh
    `tp` axis, lengths replicated (tensor-parallel serving)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.mesh import AXIS_TENSOR

    kv = NamedSharding(mesh, P(None, None, None, AXIS_TENSOR, None))
    return KVCache(k=kv, v=kv, lengths=NamedSharding(mesh, P()))


def init_cache(cfg: TransformerConfig, num_slots: int, max_len: int,
               dtype=None, shardings: "KVCache | None" = None) -> KVCache:
    """Zero cache; with `shardings` the arrays are allocated DIRECTLY
    sharded (no single-device materialization — a cache that only fits
    split across chips must never exist whole on chip 0)."""
    dtype = dtype or cfg.compute_dtype
    shape = (cfg.n_layers, num_slots, max_len, cfg.n_kv_heads, cfg.head_dim)

    def zeros(s, d, sh):
        return jnp.zeros(s, d, device=sh) if sh is not None else \
            jnp.zeros(s, d)

    k_sh = shardings.k if shardings else None
    v_sh = shardings.v if shardings else None
    l_sh = shardings.lengths if shardings else None
    return KVCache(k=zeros(shape, dtype, k_sh),
                   v=zeros(shape, dtype, v_sh),
                   lengths=zeros((num_slots,), jnp.int32, l_sh))


def _qkv(bp, x, cfg, positions):
    cd = cfg.compute_dtype
    h = rms_norm(x, bp["attn_norm"], eps=cfg.norm_eps)
    b, t = x.shape[:2]
    q = jnp.einsum("btd,dh->bth", h, bp["wq"].astype(cd)).reshape(
        b, t, cfg.n_heads, cfg.head_dim)
    k = jnp.einsum("btd,dh->bth", h, bp["wk"].astype(cd)).reshape(
        b, t, cfg.n_kv_heads, cfg.head_dim)
    v = jnp.einsum("btd,dh->bth", h, bp["wv"].astype(cd)).reshape(
        b, t, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, theta=cfg.rope_theta)
    k = apply_rope(k, positions, theta=cfg.rope_theta)
    return q, k, v


_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _layer_xs(blocks, cfg):
    """`blocks` split for a scan over layers: (the scan's xs, the expert
    weights of all layers kept whole, None without experts).  The second
    goes to `_mlp` with the layer's index: see `moe_mlp_dropless`."""
    if cfg.n_experts <= 0:
        return blocks, None
    return ({k: v for k, v in blocks.items() if k not in _EXPERT_WEIGHTS},
            {k: blocks[k] for k in _EXPERT_WEIGHTS})


def _mlp(bp, x, cfg, experts=None, li=None, live=None):
    """The block's FFN over x (S, K, d).  Returns (out, experts visited):
    with `experts` (the stacks of `_layer_xs`; `li` the layer), only
    those that a row of a `live` lane (S,) bool is routed to are read
    (None: every lane is live); 0 for a dense FFN."""
    cd = cfg.compute_dtype
    h = rms_norm(x, bp["mlp_norm"], eps=cfg.norm_eps)
    if cfg.n_experts > 0:
        # Dropless exact routing: decode must compute the same function
        # regardless of batch size (capacity routing is train-only) —
        # see moe_mlp_dropless.
        from ray_tpu.ops.moe import moe_mlp_dropless

        return moe_mlp_dropless(h, {"router": bp["router"], **experts},
                                cfg.moe, live=live, layer=li)
    gate = jnp.einsum("btd,df->btf", h, bp["w_gate"].astype(cd))
    up = jnp.einsum("btd,df->btf", h, bp["w_up"].astype(cd))
    return jnp.einsum("btf,fd->btd", jax.nn.silu(gate) * up,
                      bp["w_down"].astype(cd)), jnp.int32(0)


def _gqa(q, k, v, cfg):
    if cfg.n_kv_heads != cfg.n_heads:
        rep = cfg.n_heads // cfg.n_kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return q, k, v


def _final_logits(params, x, cfg):
    cd = cfg.compute_dtype
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps)
    if cfg.tie_embeddings:
        return jnp.einsum("btd,vd->btv", x, params["embed"].astype(cd))
    return jnp.einsum("btd,dv->btv", x, params["lm_head"].astype(cd))


def prefill(params, cache: KVCache, tokens: jax.Array, slot: jax.Array,
            length: jax.Array, cfg: TransformerConfig
            ) -> Tuple[KVCache, jax.Array]:
    """Run a (1, T_pad) prompt through the model, writing k/v into `slot`.

    `length` is the true prompt length (<= T_pad); returns (cache, logits
    of the last real token (vocab,))."""
    cd = cfg.compute_dtype
    _, t = tokens.shape
    positions = jnp.arange(t, dtype=jnp.int32)
    x = params["embed"].astype(cd)[tokens]
    mask = (positions[:, None] >= positions[None, :]) \
        & (positions[None, :] < length)

    def layer(x, layer_params_and_idx):
        bp, li = layer_params_and_idx
        q, k, v = _qkv(bp, x, cfg, positions)
        qh, kh, vh = _gqa(q, k, v, cfg)
        s = jnp.einsum("bqhd,bkhd->bhqk", qh.astype(jnp.float32),
                       kh.astype(jnp.float32)) * (cfg.head_dim ** -0.5)
        s = jnp.where(mask[None, None], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", p, vh.astype(jnp.float32))
        attn = attn.reshape(1, t, cfg.n_heads * cfg.head_dim).astype(cd)
        x = x + jnp.einsum("bth,hd->btd", attn, bp["wo"].astype(cd))
        x = x + _mlp(bp, x, cfg, experts, li)[0]
        return x, (k[0], v[0])  # (T, Hkv, D) for cache write

    idx = jnp.arange(cfg.n_layers)
    blocks, experts = _layer_xs(params["blocks"], cfg)
    x, kv = jax.lax.scan(layer, x, (blocks, idx))
    k_new, v_new = kv  # (L, T, Hkv, D)
    t_cache = cache.k.shape[2]
    pad = t_cache - t
    k_new = jnp.pad(k_new.astype(cache.k.dtype),
                    ((0, 0), (0, pad), (0, 0), (0, 0)))
    v_new = jnp.pad(v_new.astype(cache.v.dtype),
                    ((0, 0), (0, pad), (0, 0), (0, 0)))
    new_cache = KVCache(
        k=jax.lax.dynamic_update_index_in_dim(cache.k, k_new, slot, 1),
        v=jax.lax.dynamic_update_index_in_dim(cache.v, v_new, slot, 1),
        lengths=cache.lengths.at[slot].set(length))
    logits = _final_logits(params, x, cfg)[0]          # (T, vocab)
    last = logits[length - 1]                           # (vocab,)
    return new_cache, last


def _wide_decode(params, cache: KVCache, tokens: jax.Array,
                 cfg: TransformerConfig):
    """Shared width-K decode core: process `tokens` (S, K) at positions
    lengths[s]..lengths[s]+K-1, writing their KV into each slot and
    attending to cache[:len] plus the in-window causal prefix. Returns
    (logits (S, K, vocab), new_k, new_v) — callers decide how far
    `lengths` advances (decode: +1; speculative verify: +accepted+1).
    decode_step is exactly the K=1 case."""
    cd = cfg.compute_dtype
    s_count, k_w = tokens.shape
    t_cache = cache.k.shape[2]
    start = cache.lengths                                  # (S,)
    positions = start[:, None] + jnp.arange(k_w)           # (S, K)
    x = params["embed"].astype(cd)[tokens]                 # (S, K, d)

    kv_pos = jnp.arange(t_cache)
    # window token i attends to cache[:len] plus window tokens 0..i.
    attn_mask = kv_pos[None, None, :] <= positions[:, :, None]  # (S,K,T)

    def layer(carry, layer_in):
        x = carry
        bp, li, k_cache, v_cache = layer_in
        q, k, v = _qkv(bp, x, cfg, positions)              # (S,K,H,D)
        k_cache = jax.vmap(
            lambda kc, kn, p: jax.lax.dynamic_update_slice(
                kc, kn.astype(kc.dtype), (p, 0, 0)))(k_cache, k, start)
        v_cache = jax.vmap(
            lambda vc, vn, p: jax.lax.dynamic_update_slice(
                vc, vn.astype(vc.dtype), (p, 0, 0)))(v_cache, v, start)
        kh, vh = k_cache, v_cache
        if cfg.n_kv_heads != cfg.n_heads:
            rep = cfg.n_heads // cfg.n_kv_heads
            kh = jnp.repeat(kh, rep, axis=2)
            vh = jnp.repeat(vh, rep, axis=2)
        s = jnp.einsum("sqhd,sthd->sqht", q.astype(jnp.float32),
                       kh.astype(jnp.float32)) * (cfg.head_dim ** -0.5)
        s = jnp.where(attn_mask[:, :, None, :], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        attn = jnp.einsum("sqht,sthd->sqhd", p, vh.astype(jnp.float32))
        attn = attn.reshape(s_count, k_w, cfg.n_heads * cfg.head_dim)
        x = x + jnp.einsum("bth,hd->btd", attn.astype(cd),
                           bp["wo"].astype(cd))
        x = x + _mlp(bp, x, cfg, experts, li)[0]
        return x, (k_cache, v_cache)

    blocks, experts = _layer_xs(params["blocks"], cfg)
    x, new_kv = jax.lax.scan(
        layer, x, (blocks, jnp.arange(cfg.n_layers), cache.k, cache.v))
    new_k, new_v = new_kv
    logits = _final_logits(params, x, cfg)                 # (S, K, vocab)
    return logits, new_k, new_v


def decode_step(params, cache: KVCache, tokens: jax.Array,
                active: jax.Array, cfg: TransformerConfig
                ) -> Tuple[KVCache, jax.Array]:
    """One token for every slot: tokens (S,) int32 (last sampled token per
    slot), active (S,) bool.  Returns (cache, logits (S, vocab)).

    Inactive slots still flow through the matmuls (fixed shapes) but their
    cache/lengths are left untouched."""
    logits, new_k, new_v = _wide_decode(params, cache, tokens[:, None],
                                        cfg)
    keep = active[None, :, None, None, None]
    new_cache = KVCache(
        k=jnp.where(keep, new_k, cache.k),
        v=jnp.where(keep, new_v, cache.v),
        lengths=jnp.where(active, cache.lengths + 1, cache.lengths))
    return new_cache, logits[:, 0]


def verify_step(params, cache: KVCache, cand_tokens: jax.Array,
                active: jax.Array, temps: jax.Array, rng: jax.Array,
                cfg: TransformerConfig):
    """Speculative verification: K candidate tokens PER SLOT in one
    call (prompt-lookup decoding — the draft comes from n-gram matches
    in the slot's own context, no draft model; ref: the role vLLM's
    ngram speculator fills).

    cand_tokens (S, K): column 0 is each slot's last sampled token
    (whose KV is not yet written), columns 1..K-1 are the proposals.
    Returns (cache, tok_out (S, K), accepted (S,)):
      - tok_out[s, i] = the model's token at position len+i+1 (greedy;
        for temps>0 column 0 is properly sampled and acceptance is
        forced to 0, degenerating to an exact normal decode step)
      - accepted[s] = a — proposals 1..a matched, so the engine emits
        tok_out[s, :a+1] (a accepted + 1 bonus) and lengths advance by
        a+1. KV for ALL K candidates is written; positions beyond the
        new length hold stale values that every attention mask already
        ignores — acceptance is just length arithmetic, no rollback
        copy.

    Cost intuition: decode is HBM-bandwidth-bound; widening the query
    from 1 to K reuses the same weight/cache streams, so a verify call
    costs about one decode step while advancing up to K tokens.
    """
    start = cache.lengths                                  # (S,)
    logits, new_k, new_v = _wide_decode(params, cache, cand_tokens, cfg)

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (S,K)
    # Proposal i (column i of cand) is correct iff the model's greedy
    # token at the PREVIOUS position equals it; acceptance is the run
    # of correct proposals. Sampling slots accept nothing.
    match = (cand_tokens[:, 1:] == greedy[:, :-1])
    acc = jnp.cumprod(match.astype(jnp.int32), axis=1)
    accepted = jnp.where(temps > 0.0, 0, acc.sum(axis=1))   # (S,)
    rng, sub = jax.random.split(rng)
    first_sampled = sample_per_slot(logits[:, 0], sub, temps)
    tok_out = greedy.at[:, 0].set(first_sampled)

    keep = active[None, :, None, None, None]
    new_lengths = jnp.where(
        active, start + 1 + accepted.astype(jnp.int32), start)
    new_cache = KVCache(
        k=jnp.where(keep, new_k, cache.k),
        v=jnp.where(keep, new_v, cache.v),
        lengths=new_lengths)
    return new_cache, tok_out, accepted, rng


def sample_logits(logits: jax.Array, rng: jax.Array, *,
                  temperature: float = 1.0, top_k: int = 0) -> jax.Array:
    """(S, vocab) → (S,) sampled token ids; temperature 0 = greedy."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / max(temperature, 1e-6)
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, _NEG_INF, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def sample_per_slot(logits: jax.Array, rng: jax.Array,
                    temps: jax.Array, top_k: int = 0) -> jax.Array:
    """(S, vocab) logits + per-slot temperature (0 = greedy) → (S,) ids."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / jnp.maximum(temps, 1e-6)[:, None]
    if top_k > 0:
        kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
        scaled = jnp.where(scaled < kth, _NEG_INF, scaled)
    sampled = jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


def decode_and_sample(params, cache: KVCache, tokens, active, temps, rng,
                      cfg: TransformerConfig):
    """One fused device call per engine tick: decode + per-slot sampling.
    Returns (cache, next_tokens (S,), rng').  Keeps the host↔device
    traffic to (S,) int32 per tick: the round trip, not the transfer,
    bounds tick rate."""
    cache, logits = decode_step(params, cache, tokens, active, cfg)
    rng, sub = jax.random.split(rng)
    return cache, sample_per_slot(logits, sub, temps), rng


def prefill_and_sample(params, cache: KVCache, tokens, slot, length, temp,
                       rng, cfg: TransformerConfig):
    """Returns (cache, first_token, last_logits, rng) — the logits ride
    back so the engine's prefix cache can re-sample them under a
    different temperature on a later hit."""
    cache, last_logits = prefill(params, cache, tokens, slot, length, cfg)
    rng, sub = jax.random.split(rng)
    tok = sample_per_slot(last_logits[None], sub, temp[None])[0]
    return cache, tok, last_logits, rng


def extract_prefix(cache: KVCache, slot, t: int):
    """Snapshot the first `t` positions of one slot's KV
    (L, t, Hkv, D) — `t` is the prompt's prefill bucket (static: one
    compile per bucket, like prefill itself), so an entry costs
    t/max_len of a slot's HBM rather than a whole slot. Jit outputs
    are fresh buffers, so the snapshot survives later donation of
    `cache`."""
    k = jax.lax.dynamic_index_in_dim(cache.k, slot, 1, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(cache.v, slot, 1, keepdims=False)
    return k[:, :t], v[:, :t]


def insert_prefix(cache: KVCache, k_slice, v_slice, slot, length
                  ) -> KVCache:
    """Write a snapshotted prefix back into `slot` (prefix-cache hit:
    replaces the whole prefill computation with one HBM copy). Only
    the snapshot's positions are written; staler KV beyond `length`
    is masked out by the per-slot length exactly as prefill padding
    is."""
    zero = jnp.zeros((), jnp.int32)
    start = (zero, jnp.asarray(slot, jnp.int32), zero, zero, zero)
    return KVCache(
        k=jax.lax.dynamic_update_slice(cache.k, k_slice[:, None], start),
        v=jax.lax.dynamic_update_slice(cache.v, v_slice[:, None], start),
        lengths=cache.lengths.at[slot].set(length))


def sample_one(last_logits, temp, rng):
    """Re-sample a stored last-logits vector (prefix-cache hit path)."""
    rng, sub = jax.random.split(rng)
    return sample_per_slot(last_logits[None], sub, temp[None])[0], rng


def decode_burst(params, cache: KVCache, tokens, active, temps, rng,
                 cfg: TransformerConfig, n_steps: int):
    """`n_steps` fused decode+sample ticks in ONE device call (lax.scan) —
    amortizes the host↔device round trip of a tick over n_steps tokens.
    Returns (cache, token_matrix (n_steps, S), rng)."""

    def tick(carry, _):
        cache, toks, rng = carry
        cache, nxt, rng = decode_and_sample(params, cache, toks, active,
                                            temps, rng, cfg)
        return (cache, nxt, rng), nxt

    (cache, _, rng), toks = jax.lax.scan(
        tick, (cache, tokens, rng), None, length=n_steps)
    return cache, toks, rng


def _bind_cfg(f, cfg: TransformerConfig):
    """`functools.partial(f, cfg=cfg)` under `f`'s own name.  jax.jit
    names the compiled program after the function it is given, and a
    bare partial has no name: every engine program would be
    `jit__unknown` in a profile's `XLA Modules` line and in HLO dumps."""
    bound = functools.partial(f, cfg=cfg)
    bound.__name__, bound.__qualname__ = f.__name__, f.__qualname__
    return bound


def make_engine_fns(cfg: TransformerConfig, *, num_slots: int,
                    max_len: int, donate: bool = True):
    """Jitted (prefill_fn, burst_decode_fn) with cache donation.  The
    decode fn takes a static `n_steps` (one compile per distinct burst)."""
    pf = _bind_cfg(prefill_and_sample, cfg)
    df = _bind_cfg(decode_burst, cfg)
    prefill_jit = jax.jit(pf, donate_argnums=(1,) if donate else ())
    decode_jit = jax.jit(df, static_argnames=("n_steps",),
                         donate_argnums=(1,) if donate else ())
    return prefill_jit, decode_jit


def ngram_propose(context, k_minus_1: int, ngram: int = 2):
    """Host-side draft: match the trailing `ngram` tokens against the
    earlier context; propose the tokens that followed the most recent
    match. Returns a list of <= k_minus_1 proposals (possibly empty)."""
    n = len(context)
    if n < ngram + 1:
        return []
    tail = tuple(context[n - ngram:])
    # scan backwards for the most recent earlier occurrence
    for i in range(n - ngram - 1, -1, -1):
        if tuple(context[i:i + ngram]) == tail:
            j = i + ngram
            return list(context[j:j + k_minus_1])
    return []


def make_spec_fns(cfg: TransformerConfig, donate: bool = True):
    """Jitted speculative verifier (K rides in the candidate shape:
    one compile per K, same discipline as prefill buckets)."""
    return jax.jit(_bind_cfg(verify_step, cfg),
                   donate_argnums=(1,) if donate else ())


# ---------------------------------------------------------------------------
# Paged KV cache (vLLM-style block tables; serve/kv_cache.py allocator)
# ---------------------------------------------------------------------------
#
# The contiguous cache above reserves S * T_max positions of HBM up front
# and caps concurrency at the slot count.  The paged layout stores KV in a
# flat pool of fixed-size blocks — (L, N_blocks, block_size, Hkv, D) — and
# each request holds an int32 block table mapping its sequence positions to
# pool blocks.  Compiled shapes depend only on (S, B_max, block_size), so
# memory management (alloc/free/share/COW) moves entirely to the host-side
# allocator while the decode step stays a single fused program
# (arXiv:2011.03641: keep the compiled step shape-stable).
#
# A served step never moves the pool.  Decode, prefill chunk and verify are
# one body (`_paged_forward`): the whole pool is the carry of the layer loop
# (and of the burst's step loop), each layer scatters its new tokens' KV at
# [layer, block, offset] and then reads, per lane, only the blocks below the
# lane's length, in the cache dtype (`ops.attention.paged_attention`).  With
# the cache donated, XLA does all of it in the one buffer: what a step moves
# is the weights and the live KV, whatever the pool's and the table's size.
#
# Convention: pool block 0 is the NULL block.  The allocator never hands it
# out; unallocated table entries and inactive slots point at it, so every
# gather/scatter is in-bounds without conditionals.  Writes routed to block
# 0 are garbage that no attention mask ever reads.


@dataclasses.dataclass
class PagedKVCache:
    k: jax.Array          # (L, N_blocks, block_size, Hkv, D)
    v: jax.Array

    def resident_bytes(self) -> dict:
        """Bytes a replica keeps for its sequences, by kind of state."""
        return {"kv_paged": int(sum(a.size * a.dtype.itemsize
                                    for a in (self.k, self.v))),
                "kv_window": 0, "recurrent": 0}


jax.tree_util.register_dataclass(PagedKVCache, ["k", "v"], [])


def init_paged_cache(cfg: TransformerConfig, num_blocks: int,
                     block_size: int, dtype=None) -> PagedKVCache:
    dtype = dtype or cfg.compute_dtype
    shape = (cfg.n_layers, num_blocks, block_size, cfg.n_kv_heads,
             cfg.head_dim)
    return PagedKVCache(k=jnp.zeros(shape, dtype),
                        v=jnp.zeros(shape, dtype))


def init_sequence_state(cfg, num_blocks: int, block_size: int, *,
                        num_slots: int, prefill_chunk: int):
    """What the sequences of one engine keep on the device, asked of the
    model: the paged pool alone for a `TransformerConfig`; whatever
    `cfg.init_state` says for a model that brings its own (paged KV of
    the layers that keep every position, bounded window KV and recurrent
    state by slot: `models.hybrid`).  Either is the `cache` argument of
    the served programs below."""
    own = getattr(cfg, "init_state", None)
    if own is None:
        return init_paged_cache(cfg, num_blocks, block_size)
    return own(num_blocks, block_size, num_slots, prefill_chunk)


def _served_forward(params, cache, tokens, block_tables, positions, kv_len,
                    cfg, slots):
    """The served step of `cfg`'s model: `_paged_forward`, or the model's
    own over its own sequence state, which also takes the lanes' engine
    `slots` (S,) (None for a model whose state is the pool alone).
    Returns (cache, hidden, experts visited: 0 for a model's own step)."""
    own = getattr(cfg, "served_step", None)
    if own is None:
        return _paged_forward(params, cache, tokens, block_tables, positions,
                              kv_len, cfg)
    return (*own(params, cache, tokens, block_tables, positions, kv_len,
                 slots), jnp.int32(0))


def _served_logits(params, x, cfg):
    own = getattr(cfg, "final_logits", None)
    return _final_logits(params, x, cfg) if own is None else own(params, x)


def _paged_forward(params, cache: PagedKVCache, tokens: jax.Array,
                   block_tables: jax.Array, positions: jax.Array,
                   kv_len: jax.Array, cfg: TransformerConfig):
    """The one served step: `tokens` (S, K) at absolute `positions` (S, K)
    through every layer, over the tables (S, B_max) of the lanes' blocks.
    `kv_len` (S,) is each lane's length once its tokens are in (0: an idle
    lane, which writes the null block and is routed to no expert).
    Returns (cache, hidden (S, K, d), experts visited summed over the
    layers: `ops.moe.moe_mlp_dropless`; 0 without experts).

    Write-then-read, in place: a layer scatters the tokens' KV into the
    pool at [layer, table[pos // bs], pos % bs] first, so the attention
    that follows finds them there and its mask is simply kv_pos <= pos,
    for the context and the in-call causal prefix alike.  The pool is the
    layer loop's carry, never its xs/ys: no slice of it is taken out or
    stacked back.
    """
    cd = cfg.compute_dtype
    bs = cache.k.shape[2]
    live_lane = kv_len > 0
    live = live_lane[:, None]
    wb = jnp.where(live, jnp.take_along_axis(
        block_tables, positions // bs, axis=1), 0)         # (S, K)
    off = jnp.where(live, positions % bs, 0)
    x = params["embed"].astype(cd)[tokens]                 # (S, K, d)

    def layer(carry, layer_in):
        x, k_pool, v_pool, visited = carry
        bp, li = layer_in
        q, k, v = _qkv(bp, x, cfg, positions)              # (S,K,H,D)
        k_pool = k_pool.at[li, wb, off].set(k.astype(k_pool.dtype))
        v_pool = v_pool.at[li, wb, off].set(v.astype(v_pool.dtype))
        attn = paged_attention(q, k_pool, v_pool, li, block_tables,
                               positions, kv_len)
        attn = attn.reshape(*tokens.shape, cfg.n_heads * cfg.head_dim)
        x = x + jnp.einsum("bth,hd->btd", attn.astype(cd),
                           bp["wo"].astype(cd))
        out, n = _mlp(bp, x, cfg, experts, li, live_lane)
        return (x + out, k_pool, v_pool, visited + n), None

    blocks, experts = _layer_xs(params["blocks"], cfg)
    (x, k_pool, v_pool, visited), _ = jax.lax.scan(
        layer, (x, cache.k, cache.v, jnp.int32(0)),
        (blocks, jnp.arange(cfg.n_layers)))
    return PagedKVCache(k=k_pool, v=v_pool), x, visited


def paged_decode_step(params, cache: PagedKVCache, tokens: jax.Array,
                      block_tables: jax.Array, lengths: jax.Array,
                      active: jax.Array, cfg: TransformerConfig,
                      slots: Optional[jax.Array] = None
                      ) -> Tuple[PagedKVCache, jax.Array]:
    """One token for every slot through the block pool: tokens (S,),
    block_tables (S, B_max) int32, lengths (S,) int32, active (S,) bool.
    Returns (cache, logits (S, vocab)).

    Each slot's new KV goes to table[len // bs] at offset len % bs and the
    slot then attends to its len + 1 positions; what is read is the blocks
    below the longest active slot's length, not the table's width.
    Inactive slots write the null block and return garbage that the
    engine drops.  `slots` (S,): the lanes' engine slots, for a model
    whose sequences keep state by slot (`init_sequence_state`).
    """
    cache, logits, _ = _paged_decode_logits(
        params, cache, tokens, block_tables, lengths, active, cfg, slots)
    return cache, logits


def _paged_decode_logits(params, cache, tokens, block_tables, lengths,
                         active, cfg, slots):
    """`paged_decode_step` with the step's count of experts visited."""
    cache, x, visited = _served_forward(
        params, cache, tokens[:, None], block_tables, lengths[:, None],
        jnp.where(active, lengths + 1, 0), cfg, slots)
    return cache, _served_logits(params, x, cfg)[:, 0], visited  # (S, V)


def paged_decode_burst(params, cache: PagedKVCache, tokens, block_tables,
                       lengths, active, temps, rng,
                       cfg: TransformerConfig, n_steps: int, slots=None):
    """`n_steps` fused paged decode+sample ticks in one device call.
    Block tables are static across the burst — the engine pre-extends
    each active slot's table to cover lengths + n_steps before issuing.
    The pool is the step loop's carry too: n_steps in-place writes.
    Returns (cache, token_matrix (n_steps, S), rng, experts visited:
    int32, summed over the steps and the layers)."""

    def tick(carry, _):
        cache, toks, lengths, rng, visited = carry
        cache, logits, n = _paged_decode_logits(
            params, cache, toks, block_tables, lengths, active, cfg, slots)
        rng, sub = jax.random.split(rng)
        nxt = sample_per_slot(logits, sub, temps)
        lengths = jnp.where(active, lengths + 1, lengths)
        return (cache, nxt, lengths, rng, visited + n), nxt

    (cache, _, _, rng, visited), toks = jax.lax.scan(
        tick, (cache, tokens, lengths, rng, jnp.int32(0)), None,
        length=n_steps)
    return cache, toks, rng, visited


def paged_prefill_chunk(params, cache: PagedKVCache, tokens: jax.Array,
                        block_tables: jax.Array, start: jax.Array,
                        n_valid: jax.Array, cfg: TransformerConfig,
                        slot: Optional[jax.Array] = None
                        ) -> Tuple[PagedKVCache, jax.Array]:
    """One chunk of a prompt through the block pool: tokens (C,) (padded
    with zeros past `n_valid`), block_tables (B_max,), start = absolute
    position of tokens[0].  The one-lane, C-wide case of the served step:
    chunk KV goes into the table's blocks at positions start..start+C-1,
    and attention reads the blocks below start + n_valid: the
    already-prefilled context plus the in-chunk causal prefix.  Padded
    positions write garbage that the next chunk overwrites and no real
    query's mask reaches (true of KV; a model with state by `slot`, a
    scalar, leaves it untouched by them).  Returns (cache, logits of
    token n_valid-1 (vocab,)) — the engine samples from the FINAL
    chunk's logits.
    """
    positions = start + jnp.arange(tokens.shape[0], dtype=jnp.int32)
    cache, x, _ = _served_forward(
        params, cache, tokens[None], block_tables[None], positions[None],
        (start + n_valid)[None], cfg,
        None if slot is None else jnp.asarray(slot, jnp.int32)[None])
    if getattr(cfg, "final_logits", None) is None:
        return cache, _final_logits(params, x, cfg)[0, n_valid - 1]
    # A model with a head of its own: over the one position asked for.
    last = jax.lax.dynamic_slice_in_dim(x, jnp.maximum(n_valid - 1, 0), 1,
                                        axis=1)
    return cache, cfg.final_logits(params, last)[0, 0]


def paged_verify_step(params, cache: PagedKVCache, cand_tokens: jax.Array,
                      block_tables: jax.Array, lengths: jax.Array,
                      active: jax.Array, temps: jax.Array, rng: jax.Array,
                      cfg: TransformerConfig):
    """Speculative verification through the block pool: K candidate
    tokens PER SLOT in one call (the paged analogue of `verify_step` —
    same prompt-lookup drafting, same greedy acceptance rule), the
    K-wide case of the served step.

    cand_tokens (S, K): column 0 is each slot's last sampled token
    (whose KV is not yet written), columns 1..K-1 the proposals.
    block_tables (S, B_max) / lengths (S,) are the host-side paged
    state; each table must already cover positions up to lengths+K
    (the engine extends tables before issuing, exactly as it does for
    a decode burst).

    Returns (cache, tok_out (S, K), accepted (S,)).  KV for ALL K
    candidates goes into the slot's OWN blocks at positions
    lengths..lengths+K-1 — rejected drafts need no device rollback:
    the engine advances lengths by accepted+1 and every paged mask
    (kv_pos <= position) treats the stale tail as garbage until the
    next decode overwrites it in place.  The blocks are exclusively
    owned by construction (COW at decode start + fresh growth allocs),
    so stale writes can never corrupt a registered/shared prefix.
    """
    k_w = cand_tokens.shape[1]
    positions = lengths[:, None] + jnp.arange(k_w, dtype=jnp.int32)  # (S,K)
    cache, x, _ = _paged_forward(
        params, cache, cand_tokens, block_tables, positions,
        jnp.where(active, lengths + k_w, 0), cfg)
    logits = _final_logits(params, x, cfg)               # (S, K, vocab)
    # Same acceptance rule as the contiguous verify_step: proposal i is
    # correct iff the model's greedy token at the previous position
    # equals it; acceptance is the run of correct proposals.  Sampling
    # slots (temps > 0) accept nothing and degrade to an exact normal
    # decode step via the properly-sampled column 0.
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (S, K)
    match = (cand_tokens[:, 1:] == greedy[:, :-1])
    acc = jnp.cumprod(match.astype(jnp.int32), axis=1)
    accepted = jnp.where(temps > 0.0, 0, acc.sum(axis=1))   # (S,)
    rng, sub = jax.random.split(rng)
    first_sampled = sample_per_slot(logits[:, 0], sub, temps)
    tok_out = greedy.at[:, 0].set(first_sampled)
    return cache, tok_out, accepted, rng


def make_paged_spec_fns(cfg: TransformerConfig, donate: bool = True):
    """Jitted paged speculative verifier (K rides in the candidate
    shape, slot width S in every row dim: one compile per (S, K) pair,
    the same tier discipline as the paged burst)."""
    return jax.jit(_bind_cfg(paged_verify_step, cfg),
                   donate_argnums=(1,) if donate else ())


def copy_block(cache: PagedKVCache, dst: jax.Array, src: jax.Array
               ) -> PagedKVCache:
    """Copy one pool block across all layers (the device half of
    copy-on-write: a shared partial block is duplicated before its new
    owner appends into it)."""
    return PagedKVCache(k=cache.k.at[:, dst].set(cache.k[:, src]),
                        v=cache.v.at[:, dst].set(cache.v[:, src]))


def gather_blocks(cache: PagedKVCache, block_ids) -> "jnp.ndarray":
    """Extract pool blocks as one host-transferable KV frame: shape
    (2, L, n, block_size, Hkv, D) with k stacked over v.  The frame is
    the disaggregated-serving wire unit — a prefill actor gathers its
    finished blocks, `jax.device_get` turns them into a plain ndarray,
    and the bytes ride the zero-copy transfer plane like any sealed shm
    object (serve/disagg.py ships them; import is `scatter_blocks`).
    Exact roundtrip: no dtype change, so a migrated stream's decode is
    bit-identical to never having moved."""
    import numpy as np

    ids = jnp.asarray(np.asarray(block_ids, np.int32))
    return jnp.stack([cache.k[:, ids], cache.v[:, ids]])


def scatter_blocks(cache: PagedKVCache, block_ids, frame) -> PagedKVCache:
    """Write a `gather_blocks` frame into freshly-allocated pool blocks
    of ANOTHER engine's cache (the decode-side adopt path).  The frame's
    layer/head/dim geometry must match the receiving cache — the caller
    (PagedLLMEngine.import_prefix) validates shapes before touching the
    device."""
    import numpy as np

    ids = jnp.asarray(np.asarray(block_ids, np.int32))
    frame = jnp.asarray(frame, cache.k.dtype)
    return PagedKVCache(k=cache.k.at[:, ids].set(frame[0]),
                        v=cache.v.at[:, ids].set(frame[1]))


def make_paged_engine_fns(cfg: TransformerConfig, donate: bool = True):
    """Jitted (prefill_chunk, decode_burst, copy_block) with cache
    donation.  Chunk width C and table depth B_max ride in the argument
    shapes (one compile per distinct pair, same discipline as prefill
    buckets); the burst takes a static n_steps."""
    chunk_jit = jax.jit(_bind_cfg(paged_prefill_chunk, cfg),
                        donate_argnums=(1,) if donate else ())
    burst_jit = jax.jit(_bind_cfg(paged_decode_burst, cfg),
                        static_argnames=("n_steps",),
                        donate_argnums=(1,) if donate else ())
    copy_jit = jax.jit(copy_block, donate_argnums=(0,) if donate else ())
    return chunk_jit, burst_jit, copy_jit


def make_prefix_cache_fns(donate: bool = True):
    """Jitted (extract, insert, sample) for the engine's prefix cache.
    Insert donates the live cache (it is immediately replaced); extract
    never donates — its output must outlive the donated original."""
    extract_jit = jax.jit(extract_prefix, static_argnames=("t",))
    insert_jit = jax.jit(insert_prefix,
                         donate_argnums=(0,) if donate else ())
    sample_jit = jax.jit(sample_one)
    return extract_jit, insert_jit, sample_jit
