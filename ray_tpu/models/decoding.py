"""Autoregressive decoding: the paged KV pool and the served step.

KV lives in a flat pool of fixed-size blocks, (L, N_blocks, block_size,
*row), and each request holds an int32 block table that maps its
positions to pool blocks (vLLM-style; `serve/kv_cache.py` is the
allocator).  What a row is the model chooses: (Hkv, D) of K and of V for
a `TransformerConfig` (`PagedKVCache.k` / `.v`), one latent row of all
heads for `models.mla_moe` (`LatentState.kv`); a state names its pooled
leaves (`pooled_leaves`) and the block operations (`copy_block`,
`gather_blocks`, `scatter_blocks`) act on those, whatever their row.
Compiled shapes depend only on (S, B_max, block_size), so
memory management (alloc/free/share/COW) lives on the host while the step
stays one fused program (arXiv:2011.03641: keep the compiled step
shape-stable) and the engine (`ray_tpu.serve.llm.PagedLLMEngine`) swaps
requests in and out of lanes between steps.

A served step never moves the pool.  Decode, prefill chunk and verify are
one body (`_paged_forward`): the whole pool is the carry of the layer loop
(and of the burst's step loop), each layer scatters its new tokens' KV at
[layer, block, offset] and then reads, per lane, only the blocks below the
lane's length, in the cache dtype (`ops.attention.paged_attention`).  With
the cache donated, XLA does all of it in the one buffer: what a step moves
is the weights and the live KV, whatever the pool's and the table's size.

A model with sliding-window layers (`TransformerConfig.layer_pattern`)
keeps, for those layers, a ring of window + prefill_chunk rows by the
engine's slot instead of pool blocks (`ops.attention`, above
`ring_rows`): the pool then holds the full layers alone, and a served
call also takes the lanes' `slots`.

A model with linear layers (`layer_pattern` names "linear":
`ops.gated_delta`) keeps for those no KV at all but, by the engine's slot,
a float32 state a head and the last rows of a short convolution
(`PagedKVCache.lstate` / `.lconv`): recurrent state, which the engine
zeroes when a slot changes hands (`TransformerConfig.reset_slot`), which a
launch of m x `linear_chunk` rows carries from chunk to chunk inside the
program, and which a row that is not valid leaves as it was.  A model with
conv layers (`ops.short_conv`) keeps for those, leading ones too, the
convolution's last rows alone, in the same leaf `lconv` (one stack a
model: the rows `ops.gated_delta.causal_conv` continues from, whichever
mixer calls it, zeroed and counted as the linear layers' are).

A model that generates by diffusion over blocks
(`TransformerConfig.diffusion_block` = B) runs the same body under
another mask: a row sees every position up to the end of its own block of
B (`sees`, below), a prompt is prefilled in whole blocks, and a decode
call is `paged_denoise_burst`: for each block its lanes fill, passes of B
rows a lane that rewrite the block's K / V in place, the last of them
over the finished block.  (A pool block, or page, is `block_size`
positions; a block of the model is B of them, and B divides a page.)

A model whose stack runs more than once (`TransformerConfig.loop_passes`
= R) keeps R planes of the pool a full layer: pass r of layer l writes
and reads plane r x L_full + l and no other (the keys of pass 2 are made
of pass 2's hidden states).  The block operations index [:, block], so a
block carries every pass's KV wherever it goes: a shared prefix, a copy
on write, a shipped frame.  The passes are a scan around the layers'
scan, under the scopes `loop_pass` (the layers) and `loop_exit` (the norm
after the pass, the exit gate and the choice of the state the head
reads).

Convention: pool block 0 is the NULL block.  The allocator never hands it
out; unallocated table entries and inactive slots point at it, so every
gather/scatter is in-bounds without conditionals.  Writes routed to block
0 are garbage that no attention mask ever reads.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.models.transformer import (
    TransformerConfig, gain_of, qk_normed)
from ray_tpu.ops.attention import (
    page_rows, paged_attention, pages_as_rows, ring_rows, slot_ring_reader,
    window_attention)
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rotary import apply_rope

_NEG_INF = -1e30


def _qkv(bp, x, cfg, positions, kind="full"):
    """A layer of `kind`'s roped queries, keys and values of x (S, K, d)."""
    cd = cfg.compute_dtype
    h = rms_norm(x, gain_of(bp["attn_norm"], cfg), eps=cfg.norm_eps)
    b, t = x.shape[:2]
    q = jnp.einsum("btd,dh->bth", h, bp["wq"].astype(cd)).reshape(
        b, t, cfg.heads(kind), cfg.head_dim)
    k = jnp.einsum("btd,dh->bth", h, bp["wk"].astype(cd)).reshape(
        b, t, cfg.n_kv_heads, cfg.head_dim)
    v = jnp.einsum("btd,dh->bth", h, bp["wv"].astype(cd)).reshape(
        b, t, cfg.n_kv_heads, cfg.head_dim)
    q, k = qk_normed(bp, q, k, cfg)
    q = apply_rope(q, positions, **cfg.rope(kind))
    k = apply_rope(k, positions, **cfg.rope(kind))
    return q, k, v


def _head_gate(bp, x, cfg):
    """The gate on a layer's attention output, (S, K, H, `cfg.attn_gate`)
    float32: one value a query head or one an element, the sigmoid of the
    layer's normed input (the norm `_qkv` takes too: one computation once
    compiled) through `head_gate`."""
    cd = cfg.compute_dtype
    h = rms_norm(x, gain_of(bp["attn_norm"], cfg), eps=cfg.norm_eps)
    with jax.named_scope("attn_gate"):
        gate = jax.nn.sigmoid(jnp.einsum(
            "btd,dh->bth", h, bp["head_gate"].astype(cd)
        ).astype(jnp.float32))
        if cfg.attn_gate == 1:
            return gate[..., None]
        return gate.reshape(*gate.shape[:2], -1, cfg.attn_gate)


_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _layer_xs(blocks, cfg):
    """`blocks` split for a scan over layers: (the scan's xs, the expert
    weights of all layers kept whole, None without experts).  The second
    goes to `_mlp` with the layer's index: see `moe_mlp_dropless`."""
    if cfg.n_experts <= 0:
        return blocks, None
    return ({k: v for k, v in blocks.items() if k not in _EXPERT_WEIGHTS},
            {k: blocks[k] for k in _EXPERT_WEIGHTS})


def _gated_norm(o, z, gain, eps):
    """A linear layer's output norm: o (S, K, Hv, d_v) float32 normalised
    over a head under a plain gain, then times silu(z)."""
    return rms_norm(o, gain, eps=eps) * jax.nn.silu(z.astype(jnp.float32))


def _linear_mixer(bp, x, conv_rows, states, rows, valid, cfg):
    """A linear layer's mixer (Gated DeltaNet, `ops.gated_delta`) over
    x (S, K, d), from the lanes' conv rows (S, J - 1, c) and their states,
    the rows `rows` (S,) of `states` (R, Hv, d_k, d_v): the slots' states
    of every linear layer as they lie in the cache, viewed as rows.  K = 1
    is the rule's step on those rows where they lie
    (`gated_delta_step_rows`: lowered for a TPU one kernel that reads a
    lane's state out of `states` once and writes it back once, elsewhere
    the plain step between a gather and a scatter of the lanes' rows);
    K > 1 gathers them once, runs the chunk form over chunks of
    `cfg.linear_chunk` with the state handed on inside, and scatters them
    back.  A row that is not `valid` (S, K; the valid ones are a prefix)
    takes beta = 0 and g = 0, so the state passes it, and the conv rows
    kept are the last valid ones'.  Returns (out (S, K, d), conv rows,
    `states` with the lanes' rows replaced)."""
    from ray_tpu.ops.gated_delta import (
        causal_conv, gated_delta_chunks, gated_delta_step_rows)

    cd, f32 = cfg.compute_dtype, jnp.float32
    hk, hv = cfg.linear_k_heads, cfg.linear_v_heads
    dk, dv, n_conv = cfg.linear_d_k, cfg.linear_d_v, cfg.linear_conv_dim
    s_w, k_w = x.shape[:2]
    u = rms_norm(x, gain_of(bp["attn_norm"], cfg), eps=cfg.norm_eps)
    qkvz = jnp.einsum("skd,de->ske", u, bp["in_qkvz"].astype(cd))
    ba = jnp.einsum("skd,de->ske", u, bp["in_ba"].astype(cd)).astype(f32)
    with jax.named_scope("gdn_conv"):
        conv, conv_rows = causal_conv(
            conv_rows, qkvz[..., :n_conv], bp["conv_w"],
            jnp.sum(valid, axis=1).astype(jnp.int32))
        qkv = jax.nn.silu(conv)                            # float32

        def unit(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

        q = unit(qkv[..., :hk * dk].reshape(s_w, k_w, hk, dk)) * dk ** -0.5
        k = unit(qkv[..., hk * dk:2 * hk * dk].reshape(s_w, k_w, hk, dk))
        v = qkv[..., 2 * hk * dk:].reshape(s_w, k_w, hv, dv)
        q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
        live = valid[..., None]
        beta = jnp.where(live, jax.nn.sigmoid(ba[..., :hv]), 0.0)
        g = jnp.where(live, -jnp.exp(bp["A_log"].astype(f32))
                      * jax.nn.softplus(ba[..., hv:]
                                        + bp["dt_bias"].astype(f32)), 0.0)
    if k_w == 1:
        with jax.named_scope("gdn_step"):
            o, states = gated_delta_step_rows(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], states, rows)
            o = o[:, None]
    else:
        with jax.named_scope("gdn_chunk"):
            o, state = gated_delta_chunks(q, k, v, g, beta, states[rows],
                                          chunk=cfg.linear_chunk, cd=cd)
            states = states.at[rows].set(state.astype(states.dtype))
    with jax.named_scope("gdn_gate_norm"):
        y = _gated_norm(o, qkvz[..., n_conv:].reshape(s_w, k_w, hv, dv),
                        bp["gate_norm"], cfg.norm_eps)
    out = jnp.einsum("ske,ed->skd", y.reshape(s_w, k_w, hv * dv).astype(cd),
                     bp["out_proj"].astype(cd))
    return out, conv_rows, states


def _conv_mixer(bp, x, conv_rows, valid, cfg):
    """A conv layer's mixer (`ops.short_conv`) over x (S, K, d), from the
    lanes' conv rows (S, J - 1, d): a decode step and a chunk of any K
    alike.  The rows kept are the last valid ones' (`valid` (S, K): the
    valid ones are a prefix).  Returns (out (S, K, d), conv rows)."""
    from ray_tpu.ops.short_conv import gated_short_conv

    cd = cfg.compute_dtype
    u = rms_norm(x, gain_of(bp["attn_norm"], cfg), eps=cfg.norm_eps)
    bcz = jnp.einsum("skd,de->ske", u, bp["in_proj"].astype(cd))
    with jax.named_scope("short_conv"):
        y, conv_rows = gated_short_conv(
            conv_rows, bcz, bp["conv_w"],
            jnp.sum(valid, axis=1).astype(jnp.int32))
    return jnp.einsum("ske,ed->skd", y.astype(cd),
                      bp["out_proj"].astype(cd)), conv_rows


def _swiglu(bp, h, cd, prefix="w_"):
    gate = jnp.einsum("btd,df->btf", h, bp[prefix + "gate"].astype(cd))
    up = jnp.einsum("btd,df->btf", h, bp[prefix + "up"].astype(cd))
    return jnp.einsum("btf,fd->btd", jax.nn.silu(gate) * up,
                      bp[prefix + "down"].astype(cd))


def _mlp(bp, x, cfg, experts=None, li=None, live=None, routing=False):
    """The block's FFN over x (S, K, d).  Returns (out, experts visited,
    the experts each row took (S, K, top_k) if `routing`, else None, the
    top-k choices that fell on experts held here from a model that
    counts them (`counts_routed`), else None): with `experts` (the
    stacks of `_layer_xs`; `li` the layer), only those that a `live`
    row ((S,) bool by lane or (S, K) by row; None: every row) is routed
    to are read, and the shared expert, where the model has one, is
    added once; without (a model that has none, or a leading layer of
    one that has), the dense FFN, 0 visited."""
    cd = cfg.compute_dtype
    h = rms_norm(x, gain_of(bp["mlp_norm"], cfg), eps=cfg.norm_eps)
    if experts is not None:
        # Dropless exact routing: decode must compute the same function
        # regardless of batch size (capacity routing is train-only) —
        # see moe_mlp_dropless.
        from ray_tpu.ops.moe import moe_mlp_dropless

        counted = counts_routed(cfg)
        with jax.named_scope("moe"):
            bias = {"router_bias": bp["router_bias"]} \
                if "router_bias" in bp else {}
            out, visited, *more = moe_mlp_dropless(
                h, {"router": bp["router"], **bias, **experts}, cfg.moe,
                live=live,
                layer=li, return_routing=routing, return_routed=counted)
        if cfg.d_shared:
            with jax.named_scope("shared_mlp"):
                shared = _swiglu(bp, h, cd, "shared_")
                if cfg.shared_gate:
                    shared = (shared * jax.nn.sigmoid(jnp.einsum(
                        "btd,do->bto", h, bp["shared_scale"].astype(cd)
                    ).astype(jnp.float32))).astype(cd)
                out = out + shared
        return (out, visited, more[0] if routing else None,
                more[-1] if counted else None)
    if routing and cfg.n_experts <= 0:
        raise ValueError(f"{cfg.name!r} has no experts: no routing to give")
    return _swiglu(bp, h, cd), jnp.int32(0), None, None


def _post_norm(out, bp, name, cfg):
    """A sub-block's output as it is added to the residual: under its
    second norm, `bp[name]`, where the model has one (`cfg.post_norm`)."""
    if not cfg.post_norm:
        return out
    return rms_norm(out, gain_of(bp[name], cfg), eps=cfg.norm_eps)


def _final_logits(params, x, cfg):
    """The head over x (S, K, d): under the final norm, but for a model
    whose stack runs more than once, whose every pass ends in that norm
    (`_paged_forward` hands out the chosen pass's normed state)."""
    cd = cfg.compute_dtype
    if getattr(cfg, "loop_passes", 1) == 1:
        x = rms_norm(x, gain_of(params["final_norm"], cfg),
                     eps=cfg.norm_eps)
    if cfg.tie_embeddings:
        return jnp.einsum("btd,vd->btv", x, params["embed"].astype(cd))
    return jnp.einsum("btd,dv->btv", x, params["lm_head"].astype(cd))


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


def sample_one(last_logits, temp, rng):
    """Re-sample a stored last-logits vector (prefix-cache hit path)."""
    rng, sub = jax.random.split(rng)
    return sample_per_slot(last_logits[None], sub, temp[None])[0], rng


def take_last(last: jax.Array, slots: jax.Array, host: jax.Array
              ) -> jax.Array:
    """A burst's input tokens without a read by the host: lane j carries
    on engine slot slots[j] from `last` (num_slots + 1,), the last token
    each slot sampled (`put_last`), unless the host holds its token
    (host[j] >= 0: a prompt's first token).  One shape a width tier."""
    return jnp.where(host >= 0, host, last[slots])


def put_last(last: jax.Array, slots: jax.Array, tok_mat: jax.Array
             ) -> jax.Array:
    """`last` with the final row of a burst's token matrix (n_steps, S)
    written at the lanes' slots; idle lanes (slot num_slots) write the
    entry that nobody reads."""
    return last.at[slots].set(tok_mat[-1])


def _bind_cfg(f, cfg: TransformerConfig):
    """`functools.partial(f, cfg=cfg)` under `f`'s own name.  jax.jit
    names the compiled program after the function it is given, and a
    bare partial has no name: every engine program would be
    `jit__unknown` in a profile's `XLA Modules` line and in HLO dumps."""
    bound = functools.partial(f, cfg=cfg)
    bound.__name__, bound.__qualname__ = f.__name__, f.__qualname__
    return bound


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


@dataclasses.dataclass
class PagedKVCache:
    """What a `TransformerConfig`'s sequences keep: the `k` / `v` pool of
    its full layers and, with window layers, their rings by slot.  A
    model that brings its own state (`cfg.init_state`) chooses its own
    pooled leaves, of any row shape (`models.mla_moe`: one latent row a
    position), and names them in `pooled`; the engine and the block
    operations below ask `pooled_leaves` and `resident_bytes()`
    (`kv_paged`: the pooled leaves' bytes, which the allocator's
    `bytes_per_block` is taken from) and never a leaf by name."""
    # (P, N_blocks, block_size, Hkv, D), P = `cfg.kv_planes`: the full
    # layers, times the passes of a stack run more than once (plane
    # r x L_full + l is pass r of full layer l); or, where such a page is
    # not whole tiles as the compiler stores it and is as the rows the
    # decode kernel reads (`ops.attention.pages_as_rows`;
    # `init_paged_cache` decides), (P, N_blocks, block_size x Hkv, D): row
    # t x Hkv + g is position t of KV head g; of heads of half a lane
    # tile, (P, N_blocks, block_size x Hkv / 2, 128), two of a position's
    # heads side by side.  `_paged_forward`'s write and
    # `paged_attention` know which; the block operations index [:, block].
    k: jax.Array
    v: jax.Array
    # The window layers' rings, by slot (the null slot last); None for a
    # model whose every layer is full, whose state is the pool alone.
    wk: Optional[jax.Array] = None    # (L_window, S + 1, R, Hkv, D)
    wv: Optional[jax.Array] = None
    # The linear layers' recurrent state, by slot (the null slot last):
    # the last conv inputs and the state a head; None without such layers.
    # A model with conv layers keeps theirs in `lconv`, (L_conv, S + 1,
    # conv_kernel - 1, d) with the leading layers' first, and no `lstate`.
    lconv: Optional[jax.Array] = None   # (L_linear, S + 1, J - 1, c)
    lstate: Optional[jax.Array] = None  # (L_linear, S + 1, Hv, d_k, d_v)

    def resident_bytes(self) -> dict:
        """Bytes a replica keeps for its sequences, by kind of state."""
        def nbytes(*arrays):
            return int(sum(a.size * a.dtype.itemsize for a in arrays
                           if a is not None))

        return {"kv_paged": nbytes(self.k, self.v),
                "kv_window": nbytes(self.wk, self.wv),
                "recurrent": nbytes(self.lconv, self.lstate)}


jax.tree_util.register_dataclass(
    PagedKVCache, ["k", "v", "wk", "wv", "lconv", "lstate"], [])


def paged_cache_shardings(mesh) -> PagedKVCache:
    """NamedShardings for the pool's leaves, defined next to the
    (L, N_blocks, block_size, Hkv, D) layout they index: KV heads split
    over the mesh's `tp` axis (tensor-parallel serving)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.mesh import AXIS_TENSOR

    kv = NamedSharding(mesh, P(None, None, None, AXIS_TENSOR, None))
    return PagedKVCache(k=kv, v=kv)


def init_paged_cache(cfg: TransformerConfig, num_blocks: int,
                     block_size: int, dtype=None,
                     shardings: Optional[PagedKVCache] = None, *,
                     num_slots: int = 0, prefill_chunk: int = 0
                     ) -> PagedKVCache:
    """Zero pool of the full layers (a plane a layer and pass of the
    stack: `cfg.kv_planes`); with `shardings`
    (`paged_cache_shardings`) it is allocated directly sharded: a pool
    that fits only across chips never exists whole on chip 0.  On one
    chip (no `shardings`: they split the KV heads' axis) a page is kept
    as rows of whole lanes where `ops.attention.pages_as_rows` says so
    (fewer than 4 KV heads of whole lanes, or heads of half a lane
    tile: `PagedKVCache.k`), a rule of shapes, the same on every
    platform.  A model
    with window layers also gets their rings, `num_slots` + 1 of
    window + `prefill_chunk` rows each (a row is a position's (Hkv, D),
    as in the pool: D is a whole lane tile or the layout is the
    compiler's and copied every step, `ops.attention` says).  A model
    with linear layers gets their conv rows (the cache dtype) and state
    (`cfg.linear_state_dtype`), zero, `num_slots` + 1 of each; one with
    conv layers their rows, likewise."""
    dtype = dtype or cfg.compute_dtype
    row = (cfg.n_kv_heads, cfg.head_dim)
    shape = (cfg.kv_planes, num_blocks, block_size, *row)
    if shardings is None and pages_as_rows(*row, block_size, dtype):
        shape = (*shape[:2], *page_rows(*row, block_size))
    k_sh, v_sh = (shardings.k, shardings.v) if shardings else (None, None)
    rings = {}
    if cfg.state_by_slot and not (num_slots and prefill_chunk):
        raise ValueError(f"{cfg.name!r} keeps state by slot (a ring for "
                         f"its window layers, a state for its linear "
                         f"ones, rows for its conv ones): num_slots and "
                         f"prefill_chunk size it")
    if cfg.window:
        ring = (cfg.n_of("window"), num_slots + 1,
                cfg.window + prefill_chunk, *row)
        rings = {"wk": jnp.zeros(ring, dtype), "wv": jnp.zeros(ring, dtype)}
    if cfg.n_of("conv"):
        rings = {"lconv": jnp.zeros(
            (cfg.n_of("conv"), num_slots + 1, cfg.conv_kernel - 1,
             cfg.d_model), dtype)}
    elif cfg.recurrent:
        if prefill_chunk % cfg.linear_chunk:
            raise ValueError(
                f"{cfg.name!r} carries its linear layers' state over "
                f"chunks of {cfg.linear_chunk} positions: a launch of "
                f"prefill_chunk {prefill_chunk} rows is not whole chunks")
        per_slot = (cfg.n_of("linear"), num_slots + 1)
        rings = {
            "lconv": jnp.zeros((*per_slot, cfg.linear_conv - 1,
                                cfg.linear_conv_dim), dtype),
            "lstate": jnp.zeros((*per_slot, cfg.linear_v_heads,
                                 cfg.linear_d_k, cfg.linear_d_v),
                                cfg.linear_state_dtype)}
    return PagedKVCache(k=jnp.zeros(shape, dtype, device=k_sh),
                        v=jnp.zeros(shape, dtype, device=v_sh), **rings)


def init_sequence_state(cfg, num_blocks: int, block_size: int, *,
                        num_slots: int, prefill_chunk: int,
                        shardings: Optional[PagedKVCache] = None):
    """What the sequences of one engine keep on the device, asked of the
    model.  For a `TransformerConfig`: the paged pool of its full layers
    (sharded as `shardings` says), and a ring a slot for each window
    layer where it has any (state by slot that is not recurrent: never
    zeroed, owned by whoever holds the slot).  Whatever `cfg.init_state`
    says for a model that brings its own (paged KV, rings and recurrent
    state by slot: `models.hybrid`).  The engine gives a model with
    state by slot no mesh.  Either is the `cache` argument of the served
    programs below."""
    own = getattr(cfg, "init_state", None)
    if own is None:
        return init_paged_cache(cfg, num_blocks, block_size,
                                shardings=shardings, num_slots=num_slots,
                                prefill_chunk=prefill_chunk)
    return own(num_blocks, block_size, num_slots, prefill_chunk)


def _served_forward(params, cache, tokens, block_tables, positions, kv_len,
                    cfg, slots, routing=False):
    """The served step of `cfg`'s model: `_paged_forward`, or the model's
    own over its own sequence state.  Both take the lanes' engine `slots`
    (S,) where the sequence keeps state by slot (None for a model whose
    state is the pool alone).  Returns (cache, hidden, experts visited,
    the routing if asked, the top-k choices that fell on experts held
    here or None from a model that does not count them: `_paged_forward`,
    the largest defect of the call's projected stream mixes or None from
    a model with one residual stream: `counts_defect`, how many of the
    call's layers read a learned selection as a mask or None from a model
    that selects nothing: `counts_masked`).  A model's own step that has
    experts (`n_experts`) takes `routing` and returns the first five
    itself, or more of them; one without returns (cache, hidden)."""
    own = getattr(cfg, "served_step", None)
    if own is None:
        out = _paged_forward(params, cache, tokens, block_tables,
                             positions, kv_len, cfg, slots, routing)
    elif getattr(cfg, "n_experts", 0) > 0:
        out = own(params, cache, tokens, block_tables, positions, kv_len,
                  slots, routing=routing)
    elif routing:
        raise ValueError(f"{cfg.name!r} has no experts: no routing to give")
    else:
        out = (*own(params, cache, tokens, block_tables, positions, kv_len,
                    slots), jnp.int32(0), None, None)
    return (*out, *(None,) * (7 - len(out)))


def counts_routed(cfg) -> bool:
    """Whether the served programs of `cfg` hand out, as a last output,
    the top-k choices that fell on experts held here: a model that holds
    one rank's share of its experts (`experts_held`) does."""
    return getattr(cfg, "experts_held", None) is not None


def counts_groups(cfg) -> bool:
    """Whether what `counts_routed` counts is three numbers, the last
    the live rows whose kept groups of experts hold an expert held here
    (`ops.moe.routed_zero`): a model that holds a share of experts that
    are chosen group by group."""
    return counts_routed(cfg) and getattr(cfg, "expert_groups", 1) > 1


def counts_defect(cfg) -> bool:
    """Whether the served programs of `cfg` hand out, last of all, how
    far the stream mixes of the launch stopped from the doubly stochastic
    matrices (`ops.hyper_connections.res_defect`, the largest over the
    launch's rows and mixes): a model whose residual is several streams
    (`hc_mult`) does."""
    return getattr(cfg, "hc_mult", 0) > 0


def counts_masked(cfg) -> bool:
    """Whether the burst of `cfg` hands out, last of all, how many reads
    of a learned selection took the mask (`ops.attention._attend_masked`'s
    own predicate, summed over the steps and the layers that select): a
    model that attends to an indexer's selection (`index_top_k`) does."""
    return getattr(cfg, "index_top_k", 0) > 0


def _served_logits(params, x, cfg):
    own = getattr(cfg, "final_logits", None)
    return _final_logits(params, x, cfg) if own is None else own(params, x)


def _take(tree, i):
    """Layer `i` of stacks (L, ..): an index, traced or not."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False), tree)


def _nth(i, per: int, rank: int):
    """Index of the `rank`-th of `per` layers a period in period `i` (a
    `rank` of `per` or more: a tail that is no prefix of a period)."""
    return i if per == 1 and not rank else i * per + rank


def _paged_forward(params, cache: PagedKVCache, tokens: jax.Array,
                   block_tables: jax.Array, positions: jax.Array,
                   kv_len: jax.Array, cfg: TransformerConfig, slots=None,
                   routing: bool = False):
    """The one served step: `tokens` (S, K) at absolute `positions` (S, K)
    through every layer, over the tables (S, B_max) of the lanes' blocks.
    `kv_len` (S,) is each lane's length once its tokens are in (0: an idle
    lane, which writes the null block and is routed to no expert).
    `slots` (S,): the lanes' engine slots, whose rings a model with window
    layers reads and writes (the null slot for an idle lane).
    Returns (cache, hidden (S, K, d), experts visited summed over the
    layers: `ops.moe.moe_mlp_dropless`; 0 without experts, with `routing`
    the experts every row took, (expert layers, S, K, top_k), else None,
    and from a model that holds a share of its experts (`counts_routed`)
    the top-k choices of its real rows that fell on the share, summed
    likewise, else None).

    Write-then-read, in place: a full layer scatters the tokens' KV into
    the pool at [layer, table[pos // bs], pos % bs] first, so the attention
    that follows finds them there and its mask is simply kv_pos <= pos,
    for the context and the in-call causal prefix alike (for a model with
    a `diffusion_block` B, kv_pos <= `sees` = pos // B * B + B - 1, the
    last position of the row's block: the call carries whole blocks, whose
    rows see each other both ways, and every earlier block); a window layer
    writes its slot's ring at [layer, slot, pos % R] and reads the ring.
    Pool and rings are the layer loop's carry, never its xs/ys: no slice
    of them is taken out or stacked back.  The loop runs over periods of
    the layer pattern (`cfg.period`), its body the period's layers; the
    leading layers (`cfg.lead_pattern`: blocks of their own, a dense FFN)
    run before it and the layers behind the last whole period after it,
    through the same body (`one`).  A model whose stack runs more than
    once (`cfg.loop_passes`) scans all of that once a pass (`loop_pass`),
    each pass on planes of the pool of its own, and hands out the normed
    state of the pass each row leaves at: the head norms nothing more.
    """
    if cfg.state_by_slot and slots is None:
        raise ValueError(f"{cfg.name!r} keeps state by slot: a served call "
                         f"needs the lanes' slots")
    cd = cfg.compute_dtype
    as_rows = cache.k.ndim == 4            # `ops.attention.pages_as_rows`
    # Rows of a page a position fills: its KV heads, or 128-lane rows of
    # two half-lane heads side by side.
    per_pos = cfg.n_kv_heads * cfg.head_dim // cache.k.shape[3] \
        if as_rows else 1
    bs = cache.k.shape[2] // per_pos
    live_lane = kv_len > 0
    live = live_lane[:, None]
    wb = jnp.where(live, jnp.take_along_axis(
        block_tables, positions // bs, axis=1), 0)         # (S, K)
    off = jnp.where(live, positions % bs, 0)
    # Where a layer's tokens' (Hkv, D) go: [block, position], or the
    # position's rows of a page kept as rows.
    written = (wb, off) if not as_rows else (
        wb[..., None], off[..., None] * per_pos + jnp.arange(per_pos))
    x = params["embed"].astype(cd)[tokens]                 # (S, K, d)
    period, lead, tail = cfg.period, cfg.lead_pattern, cfg.tail_pattern
    per = {kind: period.count(kind) for kind in set(period)}
    counted = counts_routed(cfg)
    # Whom an expert layer routes: a model that counts its choices, the
    # real rows (a chunk's padded tail is none); else the live lanes.
    routed_rows = positions < kv_len[:, None] if counted else live_lane
    valid_rows = positions < kv_len[:, None] if cfg.recurrent else None
    sees = None
    if cfg.diffusion_block:
        sees = positions // cfg.diffusion_block * cfg.diffusion_block \
            + cfg.diffusion_block - 1
    if cfg.window:
        ring_row = ring_rows(positions, kv_len, cache.wk.shape[2])
        lane = slots[:, None]
        read_ring = slot_ring_reader(window_attention, slots, positions,
                                     kv_len, cfg.window, cache.wk.shape[1])

    def one(carry, bp, kind, at, li=None, plane0=None):
        """One layer of `kind` with the weights `bp`, the `at`-th of its
        kind (its layer of the pool or of the rings), the `li`-th of the
        expert stacks (None: a leading layer); `plane0`: the first plane
        of the pool of this pass of the stack (None: a stack run once)."""
        x, k_pool, v_pool, wk, wv, visited, routed, lin = carry
        if kind == "conv":
            lconv, _ = lin
            out, rows = _conv_mixer(bp, x, lconv[at, slots], valid_rows,
                                    cfg)
            out = _post_norm(out, bp, "attn_post_norm", cfg)
            return ffn((x + out, k_pool, v_pool, wk, wv, visited, routed,
                        (lconv.at[at, slots].set(rows), None)), bp, li)
        if kind == "linear":
            lconv, lstate = lin
            # (L_linear, S + 1, ..) as rows: layer `at`'s slots lie at
            # at x (S + 1) + slot.  A reshape of leading dimensions: a
            # bitcast, no copy.
            out, rows, states = _linear_mixer(
                bp, x, lconv[at, slots],
                lstate.reshape(-1, *lstate.shape[2:]),
                at * lstate.shape[1] + slots, valid_rows, cfg)
            lin = (lconv.at[at, slots].set(rows),
                   states.reshape(lstate.shape))
            out = _post_norm(out, bp, "attn_post_norm", cfg)
            return ffn((x + out, k_pool, v_pool, wk, wv, visited, routed,
                        lin), bp, li)
        q, k, v = _qkv(bp, x, cfg, positions, kind)        # (S,K,H,D)
        if kind == "full":
            if plane0 is not None:
                at = plane0 + at
            with jax.named_scope("full_attn"):
                if as_rows and k_pool.shape[3] != cfg.head_dim:
                    # two half-lane heads side by side in a row
                    k, v = (a.reshape(*a.shape[:2], per_pos, -1)
                            for a in (k, v))
                k_pool = k_pool.at[(at, *written)].set(
                    k.astype(k_pool.dtype))
                v_pool = v_pool.at[(at, *written)].set(
                    v.astype(v_pool.dtype))
                attn = paged_attention(q, k_pool, v_pool, at,
                                       block_tables, positions, kv_len,
                                       sees=sees, kv_heads=cfg.n_kv_heads)
        else:
            with jax.named_scope("swa"):
                wk = wk.at[at, lane, ring_row].set(
                    k.astype(wk.dtype), mode="drop")
                wv = wv.at[at, lane, ring_row].set(
                    v.astype(wv.dtype), mode="drop")
                attn = read_ring(q, wk, wv, at)
        if cfg.attn_gate:
            attn = attn * _head_gate(bp, x, cfg)
        attn = attn.reshape(*tokens.shape, cfg.heads(kind) * cfg.head_dim)
        x = x + _post_norm(jnp.einsum("bth,hd->btd", attn.astype(cd),
                                      bp["wo"].astype(cd)), bp,
                           "attn_post_norm", cfg)
        return ffn((x, k_pool, v_pool, wk, wv, visited, routed, lin), bp,
                   li)

    def ffn(carry, bp, li):
        """The layer's FFN on the carry's x, behind either mixer."""
        x, *state, visited, routed, lin = carry
        if li is None:
            with jax.named_scope("dense_mlp"):
                out, n, idx, r = _mlp(bp, x, cfg)
        else:
            out, n, idx, r = _mlp(bp, x, cfg, experts, li, routed_rows,
                                  routing)
        return (x + _post_norm(out, bp, "mlp_post_norm", cfg), *state,
                visited + n, routed if r is None else routed + r, lin), idx

    def behind(i, j, kind, bps=None, among=period):
        """Layer `j` of period `i` behind the leading layers (a traced
        `i`: inside the scan; `cfg.n_periods`, `among` the tail's kinds:
        the tail), as `one` takes it: its weights (`bps` where the scan
        hands them), its kind, its layer of the pool or of the rings, its
        index in the stacks."""
        li = _nth(i, len(period), j)
        # A period's layers index the stacks themselves: the scan's
        # slice of a period, (p, ..), is copied out before a layer of
        # it can be taken (AOT for a v5e, PR 34).
        bp = bps if bps is not None else _take(blocks, li)
        at = _nth(i, per[kind], among[:j].count(kind))
        if "kinds" in params:
            bp = {**bp, **_take(params["kinds"][kind], at)}
        if kind in lead:                 # the leading layers' come first
            at = at + lead.count(kind)
        return bp, kind, at, li

    def layer(plane0, carry, layer_in):
        bps, i = layer_in
        taken = []
        for j, kind in enumerate(period):
            carry, idx = one(carry, *behind(i, j, kind, bps), plane0=plane0)
            taken.append(idx)
        return carry, (jnp.stack(taken) if routing else None)

    def stack(carry, plane0=None):
        """Every layer once, from the carry's x: the leading layers, the
        scan over periods, the tail."""
        for j, kind in enumerate(lead):
            carry, _ = one(carry, params["lead"][j], kind,
                           lead[:j].count(kind), plane0=plane0)
        carry, taken = jax.lax.scan(
            functools.partial(layer, plane0), carry,
            (blocks if len(period) == 1 else None,
             jnp.arange(cfg.n_periods)))
        if routing:                  # (periods, p, S, K, k) -> (L, S, K, k)
            taken = taken.reshape(cfg.n_periods * len(period),
                                  *taken.shape[2:])
        for j, kind in enumerate(tail):
            carry, idx = one(carry, *behind(cfg.n_periods, j, kind,
                                            among=tail), plane0=plane0)
            if routing:
                taken = jnp.concatenate([taken, idx[None]])
        return carry, taken

    def loop_pass(carry, r):
        """Pass `r` of a stack run more than once: the layers over this
        pass's planes of the pool, the final norm (the next pass's
        input), and the exit gate's choice.  `held`: the state the head
        reads, (S, K, d), and, where the model has a gate, what is left
        of each row's probability, the share that has left, and whether
        the row has."""
        inner, held = carry
        with jax.named_scope("loop_pass"):
            inner, _ = stack(inner, r * cfg.n_of("full"))
        with jax.named_scope("loop_exit"):
            x = rms_norm(inner[0], gain_of(params["final_norm"], cfg),
                         eps=cfg.norm_eps)
            if not cfg.exit_threshold:
                return ((x, *inner[1:]), (x,)), None
            chosen, left, gone, done = held
            gate = params["exit_gate"]
            lam = jax.nn.sigmoid(
                jnp.einsum("btd,d->bt", x, gate["w"].astype(cd)).astype(
                    jnp.float32) + gate["b"].astype(jnp.float32))
            last = r == cfg.loop_passes - 1
            gone = gone + jnp.where(last, left, lam * left)
            leaves = ~done & (last | (gone >= cfg.exit_threshold))
            held = (jnp.where(leaves[..., None], x, chosen),
                    left * (1.0 - lam), gone, done | leaves)
        return ((x, *inner[1:]), held), None

    blocks, experts = _layer_xs(params["blocks"], cfg)
    carry = (x, cache.k, cache.v, cache.wk, cache.wv, jnp.int32(0),
             _routed_zero(tokens.size, cfg) if counted else None,
             (cache.lconv, cache.lstate) if cfg.recurrent else None)
    if cfg.loop_passes == 1:
        carry, taken = stack(carry)
    else:
        rows = jnp.zeros(tokens.shape, jnp.float32)
        held = (x, rows + 1.0, rows, rows > 0) if cfg.exit_threshold \
            else (x,)
        (carry, held), taken = jax.lax.scan(
            loop_pass, (carry, held), jnp.arange(cfg.loop_passes))
        carry = (held[0], *carry[1:])
    x, k_pool, v_pool, wk, wv, visited, routed, lin = carry
    lconv, lstate = lin or (None, None)
    return (PagedKVCache(k=k_pool, v=v_pool, wk=wk, wv=wv, lconv=lconv,
                         lstate=lstate), x, visited, taken, routed)


def paged_decode_step(params, cache: PagedKVCache, tokens: jax.Array,
                      block_tables: jax.Array, lengths: jax.Array,
                      active: jax.Array, cfg: TransformerConfig,
                      slots: Optional[jax.Array] = None,
                      routing: bool = False):
    """One token for every slot through the block pool: tokens (S,),
    block_tables (S, B_max) int32, lengths (S,) int32, active (S,) bool.
    Returns (cache, logits (S, vocab)).

    Each slot's new KV goes to table[len // bs] at offset len % bs and the
    slot then attends to its len + 1 positions; what is read is the blocks
    below the longest active slot's length, not the table's width.
    Inactive slots write the null block and return garbage that the
    engine drops.  `slots` (S,): the lanes' engine slots, for a model
    whose sequences keep state by slot (`init_sequence_state`).
    `routing` (a scoring entry's): also the experts each lane took in
    every layer, (L, S, top_k).
    """
    cache, logits, _, taken, *_ = _paged_decode_logits(
        params, cache, tokens, block_tables, lengths, active, cfg, slots,
        routing)
    if routing:    # what the rows took: an array, or a model's tree of them
        return cache, logits, jax.tree.map(lambda a: a[:, :, 0], taken)
    return cache, logits


def _paged_decode_logits(params, cache, tokens, block_tables, lengths,
                         active, cfg, slots, routing=False):
    """`paged_decode_step` with the step's count of experts visited and,
    from a model that counts them, of top-k choices routed here, the
    defect of its stream mixes and its selections read as a mask."""
    cache, x, visited, taken, routed, defect, masked = _served_forward(
        params, cache, tokens[:, None], block_tables, lengths[:, None],
        jnp.where(active, lengths + 1, 0), cfg, slots, routing)
    return (cache, _served_logits(params, x, cfg)[:, 0], visited, taken,
            routed, defect, masked)


def paged_decode_burst(params, cache: PagedKVCache, tokens, block_tables,
                       lengths, active, temps, rng,
                       cfg: TransformerConfig, n_steps: int, slots=None):
    """`n_steps` fused paged decode+sample ticks in one device call.
    Block tables are static across the burst — the engine pre-extends
    each active slot's table to cover lengths + n_steps before issuing.
    The pool is the step loop's carry too: n_steps in-place writes.
    Returns (cache, token_matrix (n_steps, S), rng, experts visited:
    int32, summed over the steps and the layers) and, from a model that
    holds a share of its experts (`counts_routed`), the top-k choices
    that fell on it as a fifth, summed likewise; from a model of several
    residual streams (`counts_defect`), then, the largest defect of the
    steps' mixes; from a model that attends to a learned selection
    (`counts_masked`), last, how many of the steps' selecting layers
    read it as a mask: int32."""
    counted, mixed = counts_routed(cfg), counts_defect(cfg)
    selects = counts_masked(cfg)

    def tick(carry, _):
        cache, toks, lengths, rng, visited, routed, defect, masked = carry
        cache, logits, n, _, r, short, took = _paged_decode_logits(
            params, cache, toks, block_tables, lengths, active, cfg, slots)
        rng, sub = jax.random.split(rng)
        nxt = sample_per_slot(logits, sub, temps)
        lengths = jnp.where(active, lengths + 1, lengths)
        return (cache, nxt, lengths, rng, visited + n,
                routed + r if counted else None,
                jnp.maximum(defect, short) if mixed else None,
                masked + took if selects else None), nxt

    (cache, _, _, rng, visited, routed, defect, masked), toks = jax.lax.scan(
        tick, (cache, tokens, lengths, rng, jnp.int32(0),
               _routed_zero(tokens.size, cfg) if counted else None,
               jnp.float32(0.0) if mixed else None,
               jnp.int32(0) if selects else None), None,
        length=n_steps)
    return (cache, toks, rng, visited, *([routed] if counted else []),
            *([defect] if mixed else []), *([masked] if selects else []))


def _fills(cfg: TransformerConfig) -> list:
    """Rows a block's denoising passes fill, pass by pass
    (`low_confidence_static`): B // T each, the first B % T one more."""
    b, t = cfg.diffusion_block, cfg.denoise_steps
    return [b // t + (i < b % t) for i in range(t)]


def denoise_select(logits: jax.Array, open_rows: jax.Array, n_fill,
                   temps: jax.Array, rng: jax.Array):
    """What one denoising pass fills.  logits (S, B, vocab) of a block's
    rows (row i predicts the token at its own position), `open_rows`
    (S, B) bool the rows that still hold the mask token, `n_fill` how
    many of them a lane fills in this pass, `temps` (S,) as
    `sample_per_slot` takes them.  Each row's candidate is its argmax (a
    sample at its lane's temperature above 0) and its confidence the
    candidate's probability under the row's soft-max (of the logits over
    the temperature, where there is one); a lane fills its `n_fill` open
    rows of highest confidence, of equal ones the lower position first,
    all that are open where fewer are, never a row that is not open.
    Returns (candidates (S, B) int32, the rows filled (S, B) bool)."""
    s, b, v = logits.shape
    x0 = sample_per_slot(logits.reshape(s * b, v), rng,
                         jnp.repeat(temps, b)).reshape(s, b)
    scaled = logits.astype(jnp.float32) \
        / jnp.where(temps > 0.0, temps, 1.0)[:, None, None]
    conf = jnp.exp(
        jnp.take_along_axis(scaled, x0[..., None], axis=-1)[..., 0]
        - jax.scipy.special.logsumexp(scaled, axis=-1))
    conf = jnp.where(open_rows, conf, -1.0)
    row = jnp.arange(b)
    # before[s, i, j]: row j is filled before row i.
    before = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (row[None, None, :] < row[None, :, None]))
    return x0, open_rows & (before.sum(axis=-1) < n_fill)


def _block_pass(params, cache, tokens, block_tables, lengths, active, cfg,
                routing=False):
    """One forward pass over the block each lane is filling: tokens (S, B)
    at positions lengths .. lengths + B - 1 (`lengths` (S,): the lanes'
    committed positions, whole blocks), over the lanes' earlier blocks and
    the block's own rows.  It writes the block's K / V over whatever an
    earlier pass left there.  Returns (cache, hidden (S, B, d), experts
    visited, the routing if asked)."""
    b = cfg.diffusion_block
    positions = lengths[:, None] + jnp.arange(b, dtype=jnp.int32)
    cache, x, visited, taken, _ = _paged_forward(
        params, cache, tokens, block_tables, positions,
        jnp.where(active, lengths + b, 0), cfg, None, routing)
    return cache, x, visited, taken


def paged_block_pass(params, cache: PagedKVCache, tokens, block_tables,
                     lengths, active, cfg: TransformerConfig,
                     routing: bool = False):
    """The pass `paged_denoise_burst` scans, with its logits handed out (a
    scoring entry's: the burst returns tokens, never logits): tokens
    (S, B), rows that stand open holding `cfg.mask_token_id`, or a
    finished block's own tokens (the commit).  Returns (cache, logits
    (S, B, vocab)) and with `routing` the experts each row took,
    (L, S, B, top_k)."""
    cache, x, _, taken = _block_pass(params, cache, tokens, block_tables,
                                     lengths, active, cfg, routing)
    out = (cache, _final_logits(params, x, cfg))
    return (*out, taken) if routing else out


def paged_denoise_burst(params, cache: PagedKVCache, tokens, open_rows,
                        block_tables, lengths, active, temps, rng,
                        cfg: TransformerConfig, n_blocks: int):
    """`n_blocks` blocks of `cfg.diffusion_block` = B positions for every
    lane in one device call: what a decode burst is for a model that
    generates by diffusion over blocks.  `lengths` (S,) are the lanes'
    committed positions (whole blocks), `tokens` / `open_rows` (S, B) the
    first block as it stands: a prompt's last len % B tokens as given rows
    (not open) and `cfg.mask_token_id` in the open ones; every later block
    starts all open.  The tables must cover lengths + n_blocks x B.

    A block takes T = `cfg.denoise_steps` denoising passes and one commit
    pass, every lane in lock step: a denoising pass runs the block's B
    rows against the kept K / V of every earlier block and the block's own
    rows (`_block_pass`), takes the head's logits and fills the most
    confident open rows (`denoise_select`: `_fills` says how many); after
    T passes no row is open, and the commit pass runs the finished block
    and leaves its K / V in the lanes' pages (what the passes that saw
    mask tokens wrote there is overwritten: write-then-read in place, as a
    verify step's rejected tail is).  A block with fewer open rows is
    finished earlier and its remaining passes repeat the commit, same
    values.  The pool is the carry of both scans.  Returns (cache, tokens
    (n_blocks, S, B), rng, experts visited: int32, summed over the passes
    and the layers)."""
    b = cfg.diffusion_block
    fills = jnp.asarray(_fills(cfg), jnp.int32)

    def block(carry, first):
        cache, rng, lengths, visited = carry

        def denoise(carry, n_fill):
            cache, toks, still, rng, visited = carry
            with jax.named_scope("denoise_pass"):
                cache, x, n, _ = _block_pass(params, cache, toks,
                                             block_tables, lengths, active,
                                             cfg)
            with jax.named_scope("denoise_select"):
                rng, sub = jax.random.split(rng)
                x0, fill = denoise_select(_final_logits(params, x, cfg),
                                          still, n_fill, temps, sub)
            return (cache, jnp.where(fill, x0, toks), still & ~fill, rng,
                    visited + n), None

        toks = jnp.where(first, tokens, cfg.mask_token_id)
        (cache, toks, _, rng, visited), _ = jax.lax.scan(
            denoise, (cache, toks, open_rows | ~first, rng, visited), fills)
        with jax.named_scope("block_commit"):
            cache, _, n, _ = _block_pass(params, cache, toks, block_tables,
                                         lengths, active, cfg)
        return (cache, rng, jnp.where(active, lengths + b, lengths),
                visited + n), toks

    (cache, rng, _, visited), toks = jax.lax.scan(
        block, (cache, rng, lengths, jnp.int32(0)),
        jnp.arange(n_blocks) == 0)
    return cache, toks, rng, visited


def paged_prefill_chunk(params, cache: PagedKVCache, tokens: jax.Array,
                        block_tables: jax.Array, start: jax.Array,
                        n_valid: jax.Array, cfg: TransformerConfig,
                        slot: Optional[jax.Array] = None,
                        routing: bool = False):
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
    chunk's logits — and with `routing` (a scoring entry's) the experts
    each position took in every layer, (L, C, top_k).
    """
    positions = start + jnp.arange(tokens.shape[0], dtype=jnp.int32)
    cache, x, _, taken, routed, defect, _ = _served_forward(
        params, cache, tokens[None], block_tables[None], positions[None],
        (start + n_valid)[None], cfg,
        None if slot is None else jnp.asarray(slot, jnp.int32)[None],
        routing)
    if getattr(cfg, "final_logits", None) is None:
        out = (cache, _final_logits(params, x, cfg)[0, n_valid - 1])
    else:   # A model with a head of its own: over the one position asked for.
        last = jax.lax.dynamic_slice_in_dim(
            x, jnp.maximum(n_valid - 1, 0), 1, axis=1)
        out = (cache, cfg.final_logits(params, last)[0, 0])
    if routing:
        out += (jax.tree.map(lambda a: a[:, 0], taken),)
    # From a model that counts them, last: the chunk's top-k choices
    # that fell on experts held here, then the defect of its stream
    # mixes (`_served_forward`).
    return (*out, *([routed] if counts_routed(cfg) else []),
            *([defect] if counts_defect(cfg) else []))


def paged_verify_step(params, cache: PagedKVCache, cand_tokens: jax.Array,
                      block_tables: jax.Array, lengths: jax.Array,
                      active: jax.Array, temps: jax.Array, rng: jax.Array,
                      cfg: TransformerConfig):
    """Speculative verification through the block pool: K candidate
    tokens PER SLOT in one call (prompt-lookup decoding: the draft comes
    from n-gram matches in the slot's own context, no draft model), the
    K-wide case of the served step.  Decode is HBM-bandwidth-bound, and
    widening the query from 1 to K reuses the same weight and KV streams:
    a verify call costs about one decode step and advances up to K tokens.

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
    cache, x, *_ = _served_forward(
        params, cache, cand_tokens, block_tables, positions,
        jnp.where(active, lengths + k_w, 0), cfg, None)
    logits = _served_logits(params, x, cfg)              # (S, K, vocab)
    # Proposal i is correct iff the model's greedy token at the previous
    # position equals it; acceptance is the run of correct proposals.
    # Sampling slots (temps > 0) accept nothing and degrade to an exact
    # normal decode step via the properly-sampled column 0.
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


def pooled_leaves(cache) -> tuple:
    """The names of `cache`'s leaves that are pool blocks, (L, N_blocks,
    block_size, ...): what a block table indexes, and so what copy-on-
    write, prefix reuse and a shipped frame must carry.  A state says so
    itself (`pooled`); one that does not has the `k` / `v` pair."""
    return tuple(getattr(cache, "pooled", ("k", "v")))


def _with_pooled(cache, update):
    return dataclasses.replace(cache, **{
        name: update(getattr(cache, name), i)
        for i, name in enumerate(pooled_leaves(cache))})


def copy_block(cache, dst: jax.Array, src: jax.Array):
    """Copy one pool block across all layers, in every pooled leaf of the
    model's state (the device half of copy-on-write: a shared partial
    block is duplicated before its new owner appends into it)."""
    return _with_pooled(cache, lambda a, _: a.at[:, dst].set(a[:, src]))


def _pooled(cache) -> list:
    return [getattr(cache, name) for name in pooled_leaves(cache)]


def _alike(leaves) -> bool:
    """Whether the pooled leaves stack: one shape (the `k` / `v` pair, a
    single leaf).  Leaves of unlike rows (a latent pool and its indexer's
    keys) share layers, blocks and block size and go side by side."""
    return len({a.shape for a in leaves}) == 1


def gather_blocks(cache, block_ids) -> "jnp.ndarray":
    """Extract pool blocks as one host-transferable KV frame: the pooled
    leaves stacked, (n_leaves, L, n, block_size, *row): (2, L, n,
    block_size, Hkv, D) with k over v for the `k` / `v` pair ((2, L, n,
    block_size x Hkv, D) of pages kept as rows, which an engine whose
    pool is split over a mesh, and so kept by position, refuses:
    `frame_fits`), (1, L, n, block_size, W) for a latent pool; leaves of unlike rows side by side
    in one leaf's place, their rows flattened and joined, (1, L, n,
    block_size, sum of the rows): latent rows and index keys.  The frame is
    the disaggregated-serving wire unit — a prefill actor gathers its
    finished blocks, `jax.device_get` turns them into a plain ndarray,
    and the bytes ride the zero-copy transfer plane like any sealed shm
    object (serve/disagg.py ships them; import is `scatter_blocks`).
    Exact roundtrip: no dtype change, so a migrated stream's decode is
    bit-identical to never having moved."""
    import numpy as np

    ids = jnp.asarray(np.asarray(block_ids, np.int32))
    leaves = [a[:, ids] for a in _pooled(cache)]
    if _alike(leaves):
        return jnp.stack(leaves)
    return jnp.concatenate([a.reshape(*a.shape[:3], -1) for a in leaves],
                           axis=-1)[None]


def frame_fits(cache, frame_shape) -> bool:
    """Whether a `gather_blocks` frame of `frame_shape` is of `cache`'s
    geometry: its leaves, layers, block size and row."""
    leaves = _pooled(cache)
    if _alike(leaves):
        n, row = len(leaves), tuple(leaves[0].shape[3:])
    else:
        n, row = 1, (sum(math.prod(a.shape[3:]) for a in leaves),)
    want = leaves[0].shape
    return (len(frame_shape) == 4 + len(row)
            and frame_shape[0] == n and frame_shape[1] == want[0]
            and frame_shape[3] == want[2]
            and tuple(frame_shape[4:]) == row)


def scatter_blocks(cache, block_ids, frame):
    """Write a `gather_blocks` frame into freshly-allocated pool blocks
    of ANOTHER engine's cache (the decode-side adopt path).  The frame's
    geometry must match the receiving cache — the caller
    (PagedLLMEngine.import_prefix) holds it to `frame_fits` before
    touching the device."""
    import numpy as np

    ids = jnp.asarray(np.asarray(block_ids, np.int32))
    leaves = _pooled(cache)
    frame = jnp.asarray(frame, leaves[0].dtype)
    if _alike(leaves):
        return _with_pooled(cache, lambda a, i: a.at[:, ids].set(frame[i]))
    ends = np.cumsum([math.prod(a.shape[3:]) for a in leaves])
    return _with_pooled(cache, lambda a, i: a.at[:, ids].set(
        frame[0][..., ends[i] - math.prod(a.shape[3:]):ends[i]].reshape(
            *frame.shape[1:4], *a.shape[3:])))


def make_paged_engine_fns(cfg: TransformerConfig, donate: bool = True):
    """Jitted (prefill_chunk, decode_burst, copy_block) with cache
    donation.  Chunk width C and table depth B_max ride in the argument
    shapes (one compile per distinct pair, same discipline as prefill
    buckets); the burst takes a static n_steps.  The burst is of the
    model's kind: `paged_denoise_burst`, with a static n_blocks, for one
    that generates by diffusion over blocks."""
    chunk_jit = jax.jit(_bind_cfg(paged_prefill_chunk, cfg),
                        donate_argnums=(1,) if donate else ())
    if getattr(cfg, "diffusion_block", 0):
        burst_jit = jax.jit(_bind_cfg(paged_denoise_burst, cfg),
                            static_argnames=("n_blocks",),
                            donate_argnums=(1,) if donate else ())
    else:
        burst_jit = jax.jit(_bind_cfg(paged_decode_burst, cfg),
                            static_argnames=("n_steps",),
                            donate_argnums=(1,) if donate else ())
    copy_jit = jax.jit(copy_block, donate_argnums=(0,) if donate else ())
    return chunk_jit, burst_jit, copy_jit


def _routed_zero(n_rows: int, cfg):
    """Where the sum of what `counts_routed` counts starts, for a launch
    of `n_rows` rows a layer (`ops.moe.routed_zero`)."""
    from ray_tpu.ops.moe import routed_zero

    return routed_zero(n_rows, cfg.moe)
