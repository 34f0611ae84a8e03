"""The Mistral family: everything the harness knows of Mistral-7B and
Mixtral-8x7B (one decoder block, experts optional), and the one place
where the keys of their `config.json` are named.  A configuration file
says `"family": "mistral"` and `bench.harness.spec.family` loads this
file by that name; the harness asks it, as plain functions of the
configuration file's dict `config`, for

  program_config(config)               the program's configuration object
  init_params(key, cfg)                the program's initialiser for it
  forward / row_loss                   the plain float32 reference
  score(engine, config, seqs, n_prompt)   the engine's own logits, for
                                       `correct`
  decode_step_bytes, expert_bytes_per_step, expert_operand,
  prefill_flops, train_flops_per_token    what a step needs, for the
                                       rooflines and `train_mfu`
  serve_programs(config, place)        the served programs lowered, for
                                       bench/tools/memory_fit.py

and for nothing else.  It gives no tolerances of its own
(`TOLERANCES`): the constants of bench/harness/reference.py were
measured on these two models and are what they are held to.

The reference is written from the published descriptions (Mistral 7B,
arXiv:2310.06825; Mixtral of Experts, arXiv:2401.04088) and independent
of `ray_tpu/models/`: no kernels, no cache, no batching, no scan.  It
shares only the parameter tree's layout, which is data:

    embed (V,d)  lm_head (d,V)  final_norm (d,)
    blocks.{attn_norm,mlp_norm} (L,d)  blocks.wq (L,d,H*hd)
    blocks.{wk,wv} (L,d,Hkv*hd)  blocks.wo (L,H*hd,d)
    dense:  blocks.{w_gate,w_up} (L,d,f)  blocks.w_down (L,f,d)
    MoE:    blocks.router (L,d,E)  blocks.{w_gate,w_up} (L,E,d,f)
            blocks.w_down (L,E,f,d)

Callers run it under `jax.default_matmul_precision("highest")`: on a TPU
a float32 matmul is otherwise computed in bfloat16 passes.

Departures from the published models: none in the mathematics.  Rotary
embedding pairs dimension i with i + hd/2 (the "half-rotated" layout of
the public Mistral code) -- what `ray_tpu.ops.rotary` also does; with
random weights the two layouts are the same model up to a permutation of
wq / wk columns.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp

from bench.harness.spec import SpecError

F32 = jnp.float32


# ---------------------------------------------------------------------------
# configuration file -> the program
# ---------------------------------------------------------------------------
def program_config(config: dict):
    """The configuration file's keys are the source's (`config.json` of
    the model); this is where they meet the program's names.  The two
    refusals are statements about this family in this program."""
    from ray_tpu.models.transformer import TransformerConfig

    heads = config["num_attention_heads"]
    if config.get("head_dim", config["hidden_size"] // heads) * heads \
            != config["hidden_size"]:
        raise SpecError("head_dim * num_attention_heads != hidden_size: "
                        "the program derives the head size")
    if config.get("sliding_window"):
        raise SpecError("the program has no sliding-window attention")
    return TransformerConfig(
        name=config["name"],
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        param_dtype=jnp.dtype(config["param_dtype"]),
        compute_dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(config.get("remat", False)),
        remat_policy=config.get("remat_policy", "full"),
        n_experts=int(config.get("num_local_experts", 0)),
        expert_top_k=int(config.get("num_experts_per_tok", 2)))


def init_params(key, cfg):
    """The program's own initialiser (bench/harness/device.py calls it
    inside one jitted call, on the chip's `rbg` key)."""
    from ray_tpu.models.transformer import init_params as init

    return init(key, cfg)


# ---------------------------------------------------------------------------
# the plain float32 reference
# ---------------------------------------------------------------------------
def _rms_norm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain


def _rope(x, theta):
    """x (T, heads, hd): rotate pairs (i, i + hd/2) by pos * theta^(-2i/hd)."""
    t, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]       # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


_QUERY_BLOCK = 512


def _attention(x, bp, c):
    """Causal grouped-query attention over one sequence x (T, d).  The
    queries are taken _QUERY_BLOCK at a time against the whole context,
    so that the (heads, T, T) scores of a 4096-token row never exist at
    once; the mathematics is the plain softmax(QK^T / sqrt(hd)) V."""
    t = x.shape[0]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // h
    q = _rope((x @ bp["wq"]).reshape(t, h, hd), c["rope_theta"])
    k = _rope((x @ bp["wk"]).reshape(t, hkv, hd), c["rope_theta"])
    v = (x @ bp["wv"]).reshape(t, hkv, hd)
    k = jnp.repeat(k, h // hkv, axis=1)       # query head j reads kv head
    v = jnp.repeat(v, h // hkv, axis=1)       # j // (h / hkv)
    out = []
    for lo in range(0, t, _QUERY_BLOCK):
        hi = min(lo + _QUERY_BLOCK, t)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k) / jnp.sqrt(F32(hd))
        seen = jnp.arange(t)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v))
    return jnp.concatenate(out, 0).reshape(t, h * hd) @ bp["wo"]


def _dense_ffn(x, gate, up, down):
    """SwiGLU.  Weights are cast to float32 here, at their use: one
    expert's at a time is what fits beside a model at published widths."""
    return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) \
        @ down.astype(F32)


def _moe_ffn(x, bp, c):
    """Mixtral's sparse block: softmax over the top-k router logits of
    each token (equal to the renormalised top-k of the full softmax),
    and the weighted sum of those experts' SwiGLU outputs.  Every expert
    is evaluated on every token and masked: plain, and exact.  Also
    returns each token's routing margin: the distance between the last
    router logit taken and the first left out, as a share of the root
    mean square of the token's router logits."""
    k = c["num_experts_per_tok"]
    logits = x @ bp["router"].astype(F32)                      # (T, E)
    top, idx = jax.lax.top_k(logits, k + 1)
    margin = (top[:, k - 1] - top[:, k]) \
        / jnp.sqrt(jnp.mean(jnp.square(logits), axis=-1))
    gates = jax.nn.softmax(top[:, :k], axis=-1)               # (T, k)
    weight = jnp.sum(jax.nn.one_hot(idx[:, :k], logits.shape[-1], dtype=F32)
                     * gates[..., None], axis=1)               # (T, E)
    out = jnp.zeros_like(x)
    for e in range(logits.shape[-1]):
        out = out + weight[:, e:e + 1] * _dense_ffn(
            x, bp["w_gate"][e], bp["w_up"][e], bp["w_down"][e])
    return out, margin


_FFN = ("w_gate", "w_up", "w_down", "router")


def block(x, bp, c):
    """One decoder block on one sequence x (T, d); `bp` its parameters.
    Returns the block's output and each token's routing margin (infinite
    where the block has no router)."""
    attn = {n: a.astype(F32) for n, a in bp.items() if n not in _FFN}
    x = x + _attention(_rms_norm(x, attn["attn_norm"], c["rms_norm_eps"]),
                       attn, c)
    h = _rms_norm(x, attn["mlp_norm"], c["rms_norm_eps"])
    if c.get("num_local_experts"):
        out, margin = _moe_ffn(h, bp, c)
        return x + out, margin
    return (x + _dense_ffn(h, bp["w_gate"], bp["w_up"], bp["w_down"]),
            jnp.full(x.shape[:1], jnp.inf, F32))


def head(x, final_norm, out_matrix, c):
    return _rms_norm(x, final_norm.astype(F32), c["rms_norm_eps"]) \
        @ out_matrix.astype(F32)


def forward(params, tokens, c, jit=lambda f: f):
    """tokens (T,) int32 -> (logits (T, V) float32, margin (T,)), one
    sequence; `margin` is each token's smallest routing margin over the
    layers.  Parameters are cast to float32 a block at a time, at their
    use.  `jit=jax.jit` compiles the block once and runs it per layer:
    the same arithmetic with one layer's temporaries on the device at a
    time, which is what fits beside a model at published widths."""
    block_fn = jit(functools.partial(block, c=c))
    x = params["embed"][tokens].astype(F32)
    margin = jnp.full(x.shape[:1], jnp.inf, F32)
    for i in range(c["num_hidden_layers"]):
        x, m = block_fn(x, {n: a[i] for n, a in params["blocks"].items()})
        margin = jnp.minimum(margin, m)
    out = params["embed"].T if c.get("tie_word_embeddings") \
        else params["lm_head"]
    return jit(functools.partial(head, c=c))(x, params["final_norm"],
                                              out), margin


def row_loss(params, row, c, jit=lambda f: f):
    """Mean next-token cross entropy of one row (T+1,), float32.  A
    batch's loss is the mean over its rows (equal lengths).  (The dense
    configuration has no auxiliary loss; a MoE training reference would
    add the router's.)"""
    logits, _ = forward(params, row[:-1], c, jit=jit)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, row[1:, None], axis=-1)[:, 0]
    return jnp.mean(logz - tgt)


# ---------------------------------------------------------------------------
# the engine's own logits
# ---------------------------------------------------------------------------
def score(e, config: dict, seqs, n_prompt: int):
    """Each row of `seqs` (lanes, n_prompt + steps) through the paged
    engine `e`: prefill of its first `n_prompt` tokens through the
    engine's own jitted chunk program, then the rest teacher-forced
    through the paged cache with the program's `paged_decode_step` (the
    function `paged_decode_burst` scans: the burst itself returns
    sampled tokens, never logits), all lanes in one call a step.  Returns
    per lane the logits at positions n_prompt - 1 .. the last but one:
    1 + steps rows of (V,).

    Surfaces of the program called here: `e._prefill_chunk_fn(params,
    cache, tokens (<= chunk,), table_row (b_max,), start, n)` ->
    (cache, last logits); `paged_decode_step(params, cache, tokens (w,),
    tables (w, b_max), lengths (w,), active (w,), cfg=)` -> (cache,
    logits (w, V)); `e.cache`, `e.params`, `e.cfg`, `e._b_max`,
    `e.block_size`, `e.prefill_chunk`, `e._tick_lock`."""
    import numpy as np

    from ray_tpu.models.decoding import paged_decode_step

    lanes, total = seqs.shape
    width = lanes
    bs, chunk = e.block_size, e.prefill_chunk
    per_lane = -(-total // bs)
    tables = np.zeros((width, e._b_max), np.int32)
    for lane in range(lanes):       # blocks 1.. : 0 is the null block
        tables[lane, :per_lane] = 1 + lane * per_lane + np.arange(per_lane)
    step = jax.jit(functools.partial(paged_decode_step, cfg=e.cfg),
                   donate_argnums=(1,))
    got = {lane: [] for lane in range(lanes)}
    with e._tick_lock:
        for lane in range(lanes):
            for start in range(0, n_prompt, chunk):
                toks = seqs[lane, start:min(start + chunk, n_prompt)] \
                    .astype(np.int32)
                e.cache, last = e._prefill_chunk_fn(
                    e.params, e.cache, jnp.asarray(toks),
                    jnp.asarray(tables[lane]), jnp.int32(start),
                    jnp.int32(len(toks)))
            got[lane].append(last)                 # position n_prompt - 1
        active = np.arange(width) < lanes
        for i in range(n_prompt, total):
            tok = np.zeros((width,), np.int32)
            tok[:lanes] = seqs[:, i]
            e.cache, logits = step(
                e.params, e.cache, jnp.asarray(tok), jnp.asarray(tables),
                jnp.asarray(np.where(active, i, 0).astype(np.int32)),
                jnp.asarray(active))
            for lane in range(lanes):
                got[lane].append(logits[lane])     # position i
    return [got[lane] for lane in range(lanes)]


# ---------------------------------------------------------------------------
# Operations and bytes a step needs, from shapes alone.  These count what
# the algorithm requires, not what the program happens to execute: no
# recomputation, no dense-over-experts waste, no f32 copies.
# ---------------------------------------------------------------------------
def _dims(c: dict):
    d = c["hidden_size"]
    heads = c["num_attention_heads"]
    hd = c.get("head_dim", d // heads)
    kv = c["num_key_value_heads"] * hd
    return d, heads, hd, kv, c["intermediate_size"], c["vocab_size"]


def layer_params(c: dict, active_only: bool = False) -> int:
    """Matrix parameters of one block.  `active_only`: the experts one
    token is routed to (num_experts_per_tok), not all of them."""
    d, heads, hd, kv, f, _ = _dims(c)
    attn = 2 * d * heads * hd + 2 * d * kv
    e = c.get("num_local_experts", 0)
    if e:
        k = c["num_experts_per_tok"] if active_only else e
        return attn + k * 3 * d * f + d * e
    return attn + 3 * d * f


def expert_params_per_layer(c: dict) -> int:
    d, _, _, _, f, _ = _dims(c)
    return c.get("num_local_experts", 0) * 3 * d * f


def total_params(c: dict) -> int:
    d, *_, v = _dims(c)
    emb = v * d * (1 if c.get("tie_word_embeddings") else 2)
    return c["num_hidden_layers"] * layer_params(c) + emb


def forward_flops_per_token(c: dict, context: float) -> float:
    """Forward FLOPs for one token that attends over `context` positions
    (2 per multiply-add): matrices of the blocks (routed experts only),
    attention scores and values, and the output head.  The embedding
    lookup is a gather."""
    d, heads, hd, _, _, v = _dims(c)
    per_layer = 2 * layer_params(c, active_only=True) \
        + 4 * heads * hd * context
    return c["num_hidden_layers"] * per_layer + 2 * d * v


def prefill_flops(c: dict, tokens: float, context: float) -> float:
    """FLOPs that `tokens` prompt tokens need which together attend over
    `context` positions (a token at position p attends p + 1): block
    matrices with the routed experts only, attention scores and values.
    The output head, once a prompt, is left out: under 0.01%."""
    heads = c["num_attention_heads"]
    hd = c["hidden_size"] // heads
    return c["num_hidden_layers"] * (
        2 * layer_params(c, active_only=True) * tokens
        + 4 * heads * hd * context)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward + backward = 3 x forward, causal attention at its mean
    context (seq_len + 1) / 2, head on every token.  Recomputation under
    remat does not count."""
    return 3 * forward_flops_per_token(c, (seq_len + 1) / 2)


def _itemsize(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]


def expected_routed_experts(c: dict, lanes: int) -> float:
    """Distinct experts that `lanes` tokens need in one layer, each
    routed to num_experts_per_tok of num_local_experts: the expectation
    under uniform routing, E x (1 - (1 - k/E)^lanes).  (2 for one lane,
    3.5 for two, 5.5 for four of Mixtral's 8.)  The program exposes no
    routing counts; with random routers and random inputs the routing is
    uniform but for chance."""
    e, k = c["num_local_experts"], c["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** lanes)


def expert_bytes_per_step(c: dict, lanes: int) -> float:
    """Bytes of expert weights one decode step of `lanes` tokens needs:
    the routed experts of every layer, each once."""
    d, _, _, _, f, _ = _dims(c)
    return c["num_hidden_layers"] * expected_routed_experts(c, lanes) \
        * 3 * d * f * _itemsize(c["param_dtype"])


def expert_operand(c: dict):
    """What an op that reads a layer's expert weights shows in its HLO
    text: an operand shaped [E,d,f] or [E,f,d], as a compiled pattern;
    None where the configuration has no experts."""
    e = c.get("num_local_experts")
    if not e:
        return None
    d, f = c["hidden_size"], c["intermediate_size"]
    return re.compile(rf"\[(?:\d+,)?{e},(?:{d},{f}|{f},{d})\]")


def decode_step_bytes(c: dict, live_kv_tokens: float, lanes: int) -> float:
    """Bytes one decode step of `lanes` tokens must move: every dense
    weight once, of the experts only those the lanes are routed to, the
    output head, and the live KV of the lanes."""
    d, _, hd, kv, _, v = _dims(c)
    w = _itemsize(c["param_dtype"])
    dense = c["num_hidden_layers"] * (
        layer_params(c) - expert_params_per_layer(c)) + d * v
    experts = expert_bytes_per_step(c, lanes) \
        if c.get("num_local_experts") else 0.0
    cache = 2 * c["num_hidden_layers"] * kv * live_kv_tokens \
        * _itemsize(c.get("cache_dtype", c["compute_dtype"]))
    return dense * w + experts + cache


# ---------------------------------------------------------------------------
# for bench/tools/memory_fit.py
# ---------------------------------------------------------------------------
def serve_programs(config: dict, place):
    """What a replica of `config` keeps resident, as shapes, and its
    largest programs lowered at the engine's sizes: the widest decode
    burst and one prefill chunk.  `place(tree_or_shape)` puts the
    described device on shapes.  Returns (resident, programs): a dict of
    shape trees by name and a list of (name, lowered)."""
    from ray_tpu.models.decoding import (
        init_paged_cache, make_paged_engine_fns)

    cfg = program_config(config)
    eng = config["engine"]
    n_blocks = eng["num_slots"] * eng["max_len"] // eng["block_size"] + 1
    b_max = -(-eng["max_len"] // eng["block_size"])
    params = place(jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)))
    cache = place(jax.eval_shape(
        lambda: init_paged_cache(cfg, n_blocks, eng["block_size"])))
    rng = place(jax.eval_shape(lambda: jax.random.key(0)))
    chunk_fn, burst_fn, _ = make_paged_engine_fns(cfg)

    def arr(shape, dtype):
        return place(jax.ShapeDtypeStruct(shape, dtype))

    w, c = eng["num_slots"], eng["prefill_chunk"]
    return {"params": params, "pool": cache}, [
        (f"paged_decode_burst w={w}", burst_fn.lower(
            params, cache, arr((w,), jnp.int32), arr((w, b_max), jnp.int32),
            arr((w,), jnp.int32), arr((w,), jnp.bool_),
            arr((w,), jnp.float32), rng, n_steps=eng["max_burst"])),
        (f"paged_prefill_chunk c={c}", chunk_fn.lower(
            params, cache, arr((c,), jnp.int32), arr((b_max,), jnp.int32),
            arr((), jnp.int32), arr((), jnp.int32)))]
