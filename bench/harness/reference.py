"""Plain float32 references, written from the published descriptions
(Mistral 7B, arXiv:2310.06825; Mixtral of Experts, arXiv:2401.04088) and
independent of `ray_tpu/models/`: no kernels, no cache, no batching, no
scan.  They share only the parameter tree's layout, which is data:

    embed (V,d)  lm_head (d,V)  final_norm (d,)
    blocks.{attn_norm,mlp_norm} (L,d)  blocks.wq (L,d,H*hd)
    blocks.{wk,wv} (L,d,Hkv*hd)  blocks.wo (L,H*hd,d)
    dense:  blocks.{w_gate,w_up} (L,d,f)  blocks.w_down (L,f,d)
    MoE:    blocks.router (L,d,E)  blocks.{w_gate,w_up} (L,E,d,f)
            blocks.w_down (L,E,f,d)

`config` is a configuration file's dict.  Callers run these under
`jax.default_matmul_precision("highest")`: on a TPU a float32 matmul is
otherwise computed in bfloat16 passes.

Departures from the published models: none in the mathematics.  Rotary
embedding pairs dimension i with i + hd/2 (the "half-rotated" layout of
the public Mistral code) -- what `ray_tpu.ops.rotary` also does; with
random weights the two layouts are the same model up to a permutation of
wq / wk columns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


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
# The comparison that decides `correct`.
#
# The served model computes in bfloat16 (8 significant bits: one rounding
# moves a value by up to 2^-8 = 0.4% of itself) and keeps its KV cache in
# bfloat16; the reference computes the same weights in float32.  With
# random weights of variance 1/fan_in the logits have a standard deviation
# near 1.  The measure, per compared position, is the error's root mean
# square over the vocabulary as a share of the reference logits' own.
#
# Dense (measured on the chip, PR 23, Mistral widths, 8 layers, 18
# positions a run: 2 prompts x (the last prefill position + 8 decode
# steps)): 0.0094-0.0100 at every position, every run and every seed.
# Every position must meet LOGITS_REL, 3x that.
#
# With experts a position can differ for a reason that is no error: the
# program computes its router logits in bfloat16, so where the float32
# logits of the last expert taken and the first left out lie closer than
# the program's own rounding, it routes the token to the other expert, and
# that token's logits are then another function's (model-configs guide,
# section 3.3, warns of this for sampled tokens; with random routers it
# reaches the logits).  The reference knows where that can happen without
# asking the program: its own routing margin there (see _moe_ffn) is small
# in some layer.  A position whose margin is at least ROUTER_MARGIN in
# every layer is *decided* and must meet LOGITS_REL_EXPERTS; at least
# MIN_DECIDED positions must be decided; an *undecided* position is held
# to nothing but being finite.
#
# Measured on the chip (PR 23 review round, Mixtral widths, 3 layers, 6
# seeds x 18 positions): six positions were routed otherwise (errors
# 0.16-0.69), at margins 0.001, 0.005, 0.015, 0.021, 0.025 and 0.076;
# every other position read 0.009-0.033, in one run 0.018 in one lane and
# 0.030 in the other.  That is why LOGITS_REL_EXPERTS is wider than
# LOGITS_REL: a position attends over ~260 earlier ones of which some were
# routed otherwise, and their keys and values differ.  It is also why the
# margin is 0.2 and not the 0.02 that rounding alone would give: the same
# 2-3% reaches the router's input, the difference of two logits then
# carries about 0.03-0.045 of their rms, and 0.076 is a two-sigma event of
# that; 0.2 is over four.  About a fifth of the positions are decided.
#
# bench/tests/test_check.py holds this verdict to what it claims, through
# the engine's own programs at a tiny size: it passes the program as it
# is, and fails it with one expert's output dropped, with one layer's
# output dropped, and with the KV cache kept in 8-bit floats.  What it
# cannot catch: a layer computed in bfloat16 where the configuration says
# bfloat16 (the stated dtype is the program's), and a fault that touches
# only undecided positions.  The train check is tighter.
# ---------------------------------------------------------------------------
LOGITS_REL = 0.03
LOGITS_REL_EXPERTS = 0.06
ROUTER_MARGIN = 0.2
MIN_DECIDED = 6
# bf16 compute against f32 reference on the same f32 master weights: the
# loss is a mean over 8 x 4096 positions, so rounding averages out
# (measured on four chips, PR 23: 6e-6).  1e-3 relative is a hundredth of
# what skipping one of eight layers moves it.
LOSS_REL = 1e-3


def position_errors(got, want):
    """Per position (row): rms(got - want) / rms(want), float32."""
    got, want = jnp.asarray(got, F32), jnp.asarray(want, F32)
    return jnp.sqrt(jnp.mean(jnp.square(got - want), axis=-1)
                    / jnp.mean(jnp.square(want), axis=-1))


def logits_verdict(errors, margins, c) -> dict:
    """`errors`, `margins`: every compared position's error and routing
    margin (flat lists of equal length).  `each` lists them, so that a
    verdict can be explained from the output alone."""
    each = sorted((float(m), float(e)) for m, e in zip(margins, errors))
    errs = sorted(e for _, e in each)
    decided = [e for m, e in each if m >= ROUTER_MARGIN]
    bound = LOGITS_REL_EXPERTS if c.get("num_local_experts") else LOGITS_REL
    finite = all(e == e and e != float("inf") for e in errs)
    return {"positions": len(errs), "decided": len(decided),
            "median": errs[len(errs) // 2], "worst": errs[-1],
            "worst_decided": max(decided, default=None), "bound": bound,
            "finite": finite,
            "ok": bool(finite and len(decided) >= MIN_DECIDED
                       and max(decided) <= bound),
            "each": [[m if m != float("inf") else None, e]
                     for m, e in each]}
