"""The laguna family: everything the harness knows of Laguna-XS.2
(`model_type: laguna`, poolside): a decoder whose layers differ by kind in
more than their mask.  `layer_types` repeats (full, sliding, sliding,
sliding); a full layer has `num_attention_heads_per_layer` = 48 query heads
and a sliding layer 64, over the same 8 KV heads; each kind has its own
rope (`rope_parameters`); every layer gates its attention's output by head
(`gating`); `mlp_layer_types` makes layer 0's FFN a dense SwiGLU and the
rest routed experts beside a shared expert.  A configuration file says
`"family": "laguna"`; what the harness asks of a family is listed at the
top of families/mistral.py.  This one also gives `ring_operand` and
`ring_bytes_per_step` (for `window_attn_roofline`),
`routed_choices_per_row` (for `moe_routed_here_share.decode`) and
`TOLERANCES`, with its measurements beside it.

The model, for layer `l` of `num_hidden_layers` (the first that many
entries of `layer_types`, `mlp_layer_types` and
`num_attention_heads_per_layer`), eps `rms_norm_eps`, no biases, untied
embedding and head:

    x = E[token]
    x += Attn_l(RMSNorm(x))
    x += FFN_l(RMSNorm'(x))
    logits = RMSNorm_f(x) W_head

  Attn    u the normed input, H = num_attention_heads_per_layer[l], hd =
          head_dim, Hkv = num_key_value_heads:  q = u Wq (H x hd), k = u
          Wk, v = u Wv (Hkv x hd);  the layer's kind's rope on q and k: the
          first r = partial_rotary_factor x hd dimensions of a head are
          rotated, pair i with i + r / 2, at f_i = theta^(-2i / r), and
          the rest passed through.  `rope_type: yarn` (the full layers):
          c(n) = r ln(original / (2 pi n)) / (2 ln theta), low =
          floor(c(beta_fast)), high = ceil(c(beta_slow)) (within 0 ..
          r - 1), ramp_i = clip((i - low) / (high - low), 0, 1),
          inv_freq_i = f_i (1 - ramp_i) + f_i / factor ramp_i; cos and sin
          multiplied by attention_factor.  `default` (the sliding
          layers): f_i as it is.
          softmax(q k^T / sqrt(hd) + mask) v, query head j reading KV head
          j // (H / Hkv); `sliding_attention`: position t sees t -
          sliding_window < p <= t; `full_attention`: causal.
          g = sigmoid(u Wg) (H values), head j's output multiplied by g_j
          (assumed per head: below);  out Wo.
  dense   `mlp_layer_types[l] == "dense"`: (silu(u Wg) * (u Wu)) Wd at
          width intermediate_size.
  sparse  s = sigmoid(u W_r) in float32 over all published experts; the
          num_experts_per_tok largest are taken; gates g =
          moe_routed_scaling_factor x s[taken] / sum(s[taken]); each
          expert a SwiGLU at width moe_intermediate_size.  **This chip
          holds `num_experts` of them, from `first_local_expert`**: the
          sum runs over the held experts a token took and the rest of its
          experts is left out, in the program and here alike
          (model-configs guide, section 4).  Beside them a SwiGLU at width
          shared_expert_intermediate_size that every token takes, added
          once.

The reference below is those equations in plain `jax.numpy` float32,
independent of `ray_tpu/`: no kernels, no cache, no batching, no scan over
layers; attention with explicit masks in blocks of queries, every held
expert evaluated on every token and weighted (zero where not taken).  It
shares only the parameter tree's layout, which is data:

    embed (V,d)  lm_head (d,V)  final_norm (d,)
    lead[l], one leading dense layer, unstacked: attn_norm, mlp_norm (d,)
        wq (d,H*hd)  wk, wv (d,Hkv*hd)  wo (H*hd,d)  head_gate (d,H)
        w_gate, w_up (d,f_dense)  w_down (f_dense,d)
    blocks.*, stacked over the layers behind the leading ones:
        attn_norm, mlp_norm (.,d)  wk, wv (.,d,Hkv*hd)  router (.,d,E
        published)  w_gate, w_up (.,E held,d,f)  w_down (.,E held,f,d)
        shared_gate, shared_up (.,d,fs)  shared_down (.,fs,d)
    kinds.{full,window}.*, stacked over the layers of that kind behind the
        leading ones: wq (.,d,H*hd)  wo (.,H*hd,d)  head_gate (.,d,H)

Callers run it under `jax.default_matmul_precision("highest")`.

**Routing is handed over**, as in families/mellum.py and
families/glm4moelite.py and for their reason: with 8 of 256 taken, the
reference's own gap between the last expert taken and the first left out
is under the program's rounding at nearly every position.  `score` asks
the engine's scoring entry for the experts the program took at every
position and expert layer and keeps them under the lane's token ids;
`forward` looks its tokens up there, takes the program's experts, computes
their gates itself from its own float32 scores, and holds the program's
choice to ROUTER_SLACK on its own scores (a position whose set strays
further, or is not `num_experts_per_tok` distinct experts, gets NaN
logits, which `logits_verdict` refuses).

Assumed, because `config.json` leaves them to the family's convention (the
configuration file lists each under `assumed` with its ground): the gate
is one value a head, sigmoid of the layer's normed input; the router
scores by sigmoid, renormalises over the taken and has no selection bias;
no QK-norm and no gate on the shared expert; the window counts the current
position; the rope pairs dimension i with i + r / 2 and turns the first r.
"""
from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.spec import SpecError

F32 = jnp.float32

# The comparison that decides `correct` (bench/harness/reference.py), for
# this family.  Every compared position is decided by handed-over routing
# (above), so LOGITS_REL_EXPERTS holds all 34 of a run (`check`: 2 lanes x
# (the last of 6,144 prompt positions, prefilled in twelve launches of 512
# rows through pool and rings, + 16 decode steps): the timed lengths, past
# the window, past a turn of the 1,024-row ring and past YaRN's original
# 4,096).  Measured on the chip at published widths, 9 layers, 128 of 256
# experts (my chip runs, PR 45; PERF.md section 7 has the table).
#
# LOGITS_REL_EXPERTS: rms error of a position's logits as a share of the
# reference's own.  ROUTER_SLACK: how far the program's set of experts may
# stray from the reference's, as a share of the spread (standard deviation
# over the 256 experts) of the token's scores.
#   The program as it is, 26 seeds x 34 positions x 8 expert layers (calls
#   B, C, D1 and D2: twenty-two runs of the cell, thirteen of them from the
#   final tree's archive, and four bare checks): a position's error has
#   medians 0.0264-0.0287 and a largest a seed of 0.0315-0.0388; it strays
#   by at most 0.026-0.051 a seed.
#   **The pool and the rings kept in 8-bit floats** (float8_e4m3fn, the
#   nearest precision below the stated `cache_dtype`, rounded by eager ops
#   after every prefill launch and every decode step: inside one jit the
#   TPU compiler drops the pair of converts; three seeds, a process each):
#   error medians 0.148-0.151, the least of a seed's 34 positions
#   0.118-0.126, largest 0.179-0.193, every position over the limit on
#   every seed; strays to 0.25-0.29 (medians 0.11-0.13).  0.06 lies between
#   0.0388 and 0.118 with a factor of 1.5 below and of two above (2.5 to
#   the control's medians: the control reads 5.3 times the sound runs'
#   medians); 0.1 lies between 0.051 and 0.25, a factor of two below and of
#   2.5 above.
#   At these widths the check also refuses, each read on the chip on one
#   seed (error median, largest; stray median, largest): the gate left out
#   (1.08, 1.18; 2.66, 3.67), a full layer's rope over the whole head (1.41,
#   1.44; 3.72, 3.99), a window layer's over half (0.21, 0.25; 0.17, 0.46),
#   one theta for both kinds (0.19, 0.24; 0.18, 0.52), YaRN left off (1.35,
#   1.40; 3.50, 4.11), the shared expert dropped (1.18, 1.22; 3.00, 3.89),
#   the factor 2.5 dropped (0.62, 0.72; 1.03, 1.98), soft-max in place of
#   the sigmoid (0.39, 0.83; 0.45, 1.54), top-7 routing (no position has 8
#   experts), the dense first layer's output dropped (1.42, 1.45; 3.68,
#   4.05), **one held expert's output dropped** (the median position's error
#   0.033 is under the limit: 3 in 128 rows take that expert in a layer; the
#   largest 0.39 and strays to 0.93 refuse the run by both limits).
#   **What it cannot see:** a layer computed in bfloat16 where the
#   configuration says bfloat16 (the stated dtype is the program's); a
#   router wrong by less than ROUTER_SLACK everywhere, which is what
#   rounding does and a fault rarely.  tests/test_gated_moe_serving.py
#   holds eight of these faults in float32 at a tiny size, and in bfloat16
#   the cache's and the weights' precision, a held expert and the dense
#   layer.
TOLERANCES = {"LOGITS_REL_EXPERTS": 0.06, "ROUTER_SLACK": 0.1}

_KINDS = {"sliding_attention": "window", "full_attention": "full"}
# What `score` handed over: {a lane's token ids (int32 bytes): (T, L_e, k)}.
_HANDED: dict = {}


# ---------------------------------------------------------------------------
# configuration file -> the program
# ---------------------------------------------------------------------------
def layers(config: dict) -> list:
    """The layers that are run, each (kind of attention, query heads, kind
    of FFN): the first `num_hidden_layers` entries of the three published
    lists."""
    n = config["num_hidden_layers"]
    out = list(zip(config["layer_types"][:n],
                   config["num_attention_heads_per_layer"][:n],
                   config["mlp_layer_types"][:n]))
    if len(out) < n or any(k not in _KINDS or f not in ("dense", "sparse")
                           for k, _, f in out):
        raise SpecError(f"layer_types, num_attention_heads_per_layer and "
                        f"mlp_layer_types must name {n} layers, of "
                        f"{sorted(_KINDS)} and of dense / sparse")
    return out


def n_lead(config: dict) -> int:
    """The leading layers whose FFN is dense."""
    ffns = [f for _, _, f in layers(config)]
    lead = ffns.index("sparse") if "sparse" in ffns else len(ffns)
    if "dense" in ffns[lead:] or lead == len(ffns):
        raise SpecError("the program's dense layers lead and expert layers "
                        "follow: mlp_layer_types is dense.. then sparse..")
    return lead


def published_experts(config: dict) -> int:
    """The router's width: the published count of routed experts, of
    which `num_experts` are held here."""
    return int(config.get("published", {}).get(
        "num_experts", config["num_experts"]))


def held_range(config: dict):
    """(first, count) of the published experts that this chip holds."""
    return int(config.get("first_local_expert", 0)), \
        int(config["num_experts"])


def _period(kinds: list) -> list:
    """The shortest period that `kinds` repeats, its last one cut short
    where they are not whole periods."""
    for p in range(1, len(kinds) + 1):
        if kinds == (kinds[:p] * len(kinds))[:len(kinds)]:
            return kinds[:p]


def _withdraw_app() -> None:
    """Ends the run of a program that lacks this family's model, soon and
    non-zero (families/phi4flash.py says why this is needed: a replica
    whose constructor raises is restarted for `serve_startup_grace_s`)."""
    try:
        import ray_tpu
        from bench.harness.serve_cell import APP
        from ray_tpu.serve.controller import CONTROLLER_NAME

        ray_tpu.get(ray_tpu.get_actor(CONTROLLER_NAME).delete_app.remote(APP),
                    timeout=10)
    except Exception:  # noqa: BLE001 the constructor's own error stands
        pass


def _heads_of(config: dict, kind: str) -> int:
    heads = {h for k, h, _ in layers(config) if k == kind}
    if len(heads) > 1:
        raise SpecError(f"{kind} layers of {sorted(heads)} query heads: the "
                        f"program's heads differ by kind, not by layer")
    return heads.pop() if heads else 0


def program_config(config: dict):
    import dataclasses

    from ray_tpu.models.transformer import TransformerConfig

    needs = {"n_heads_window", "rope_theta_window", "rotary_dim",
             "rotary_dim_window", "attn_gate", "lead_pattern", "d_shared",
             "experts_held", "expert_scoring", "route_scale"}
    lacks = needs - {f.name for f in dataclasses.fields(TransformerConfig)}
    if lacks:
        _withdraw_app()
        raise SpecError(
            f"this program's TransformerConfig has no {sorted(lacks)}: it "
            f"cannot run a configuration of the laguna family")
    from ray_tpu.ops.rotary import YarnScaling

    for key, want in (("attention_bias", False), ("gating", True),
                      ("moe_apply_router_weight_on_input", False),
                      ("tie_word_embeddings", False)):
        if config[key] != want:
            raise SpecError(f"{key} = {config[key]!r}: the program's layers "
                            f"are {key} = {want!r}")
    rope = config["rope_parameters"]
    full, slide = rope["full_attention"], rope["sliding_attention"]
    if full["rope_type"] != "yarn" or slide["rope_type"] != "default":
        raise SpecError("the program ropes full layers under YaRN and "
                        "window layers unscaled")
    kinds = [_KINDS[k] for k, _, _ in layers(config)]
    lead = n_lead(config)
    hd = config["head_dim"]
    first, count = held_range(config)
    e = published_experts(config)
    h_full, h_window = (_heads_of(config, "full_attention"),
                        _heads_of(config, "sliding_attention"))
    return TransformerConfig(
        name=config["name"],
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=h_full or h_window,
        n_heads_window=h_window,
        n_kv_heads=config["num_key_value_heads"],
        d_head=hd,
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["shared_expert_intermediate_size"],
        n_experts=e,
        expert_top_k=config["num_experts_per_tok"],
        experts_held=None if count == e else (first, count),
        expert_scoring=config["assumed"]["router_scoring"],
        route_scale=float(config["moe_routed_scaling_factor"]),
        attn_gate=True,
        lead_pattern=tuple(kinds[:lead]),
        layer_pattern=tuple(_period(kinds[lead:])),
        window=config["sliding_window"],
        rope_theta=float(full["rope_theta"]),
        rope_theta_window=float(slide["rope_theta"]),
        rotary_dim=int(full["partial_rotary_factor"] * hd),
        rotary_dim_window=int(slide["partial_rotary_factor"] * hd),
        yarn=YarnScaling(
            factor=float(full["factor"]),
            original_max_len=int(full["original_max_position_embeddings"]),
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        tie_embeddings=False,
        param_dtype=jnp.dtype(config["param_dtype"]),
        compute_dtype=jnp.dtype(config["compute_dtype"]),
        remat=False)


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
    return x * jax.lax.rsqrt(var + eps) * gain.astype(F32)


def inv_frequencies(c: dict, kind: str):
    """(r / 2,) float32 inverse frequencies of a layer of `kind` over the
    r = partial_rotary_factor x head_dim dimensions its rope turns, r, and
    the factor on cos and sin."""
    p = c["rope_parameters"][kind]
    r = int(p["partial_rotary_factor"] * c["head_dim"])
    theta = float(p["rope_theta"])
    f = theta ** (-jnp.arange(r // 2, dtype=F32) * 2.0 / r)
    if p["rope_type"] != "yarn":
        return f, r, 1.0

    def turns_at(n):
        return r * math.log(p["original_max_position_embeddings"]
                            / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(turns_at(p["beta_fast"])), 0)
    high = min(math.ceil(turns_at(p["beta_slow"])), r - 1)
    ramp = jnp.clip((jnp.arange(r // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return (f * (1.0 - ramp) + f / p["factor"] * ramp, r,
            float(p["attention_factor"]))


def _rope(x, c, kind):
    """x (T, heads, hd): rotate pairs (i, i + r/2) of the first r
    dimensions, the rest as they are."""
    t = x.shape[0]
    inv, r, factor = inv_frequencies(c, kind)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., r:]], -1)


_QUERY_BLOCK = 512


def attention(u, p, c, kind):
    """Gated grouped-query attention of the normed input u (T, d) in a
    layer of `kind`, queries _QUERY_BLOCK at a time against the whole
    context.  The heads are what the layer's `wq` holds."""
    t = u.shape[0]
    hkv, hd = c["num_key_value_heads"], c["head_dim"]
    h = p["wq"].shape[-1] // hd
    q = (u @ p["wq"].astype(F32)).reshape(t, h, hd)
    k = (u @ p["wk"].astype(F32)).reshape(t, hkv, hd)
    v = (u @ p["wv"].astype(F32)).reshape(t, hkv, hd)
    q, k = _rope(q, c, kind), _rope(k, c, kind)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    out = []
    for lo in range(0, t, _QUERY_BLOCK):
        hi = min(lo + _QUERY_BLOCK, t)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k) / jnp.sqrt(F32(hd))
        qp, kp = jnp.arange(lo, hi)[:, None], jnp.arange(t)[None, :]
        seen = kp <= qp
        if kind == "sliding_attention":
            seen = seen & (kp > qp - c["sliding_window"])
        prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", prob, v))
    gate = jax.nn.sigmoid(u @ p["head_gate"].astype(F32))        # (T, H)
    out = jnp.concatenate(out, 0) * gate[:, :, None]
    return out.reshape(t, h * hd) @ p["wo"].astype(F32)


def swiglu(u, p, prefix="w_"):
    return (jax.nn.silu(u @ p[prefix + "gate"].astype(F32))
            * (u @ p[prefix + "up"].astype(F32))) \
        @ p[prefix + "down"].astype(F32)


def scores(u, p, c):
    """The router's score of every published expert, (T, E): the
    configuration's `assumed.router_scoring` of the router's logits."""
    logits = u @ p["router"].astype(F32)
    return jax.nn.sigmoid(logits) \
        if c["assumed"]["router_scoring"] == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)


def experts(u, p, taken, c):
    """The routed experts held here over u (T, d).  `taken` (T, k) int32:
    the experts the program took (None: the reference's own top-k).
    Returns (this chip's part of the routed sum, margin (T,), bad (T,)
    bool).  `margin`: with the reference's own routing, the gap between
    the last expert taken and the first left out over the spread of the
    token's scores; with handed-over routing 1 - how far the program's set
    strays from the reference's in that unit.  `bad`: the program's set is
    not k distinct experts, or strays by more than ROUTER_SLACK."""
    k, e = c["num_experts_per_tok"], published_experts(c)
    first, count = held_range(c)
    s = scores(u, p, c)                                          # (T, E)
    top, idx = jax.lax.top_k(s, k + 1)
    spread = jnp.std(s, axis=-1)
    if taken is None:
        taken = idx[:, :k]
        margin = (top[:, k - 1] - top[:, k]) / spread
        bad = jnp.zeros(margin.shape, bool)
    else:
        mine = jnp.sum(jax.nn.one_hot(taken, e, dtype=F32), axis=1) > 0
        kth = top[:, k - 1]
        lowest_in = jnp.min(jnp.where(mine, s, jnp.inf), axis=-1)
        highest_out = jnp.max(jnp.where(mine, -jnp.inf, s), axis=-1)
        stray = jnp.maximum(jnp.maximum(kth - lowest_in, highest_out - kth),
                            0.0) / spread
        margin = 1.0 - stray
        bad = (stray > TOLERANCES["ROUTER_SLACK"]) \
            | (jnp.sum(mine, axis=-1) != k)
    gates = jnp.take_along_axis(s, taken, axis=-1)               # (T, k)
    gates = F32(c["moe_routed_scaling_factor"]) * gates \
        / jnp.sum(gates, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(taken, e, dtype=F32) * gates[..., None],
                     axis=1)[:, first:first + count]             # (T, held)

    def one(acc, ex):
        gate, up, down, w = ex
        hidden = jax.nn.silu(u @ gate.astype(F32)) * (u @ up.astype(F32))
        return acc + w[:, None] * (hidden @ down.astype(F32)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        p["w_gate"], p["w_up"], p["w_down"], weight.T))
    return out, margin, bad


def dense_block(x, p, c, kind):
    """A leading layer on one sequence x (T, d)."""
    eps = c["rms_norm_eps"]
    x = x + attention(_rms_norm(x, p["attn_norm"], eps), p, c, kind)
    return x + swiglu(_rms_norm(x, p["mlp_norm"], eps), p)


def expert_block(x, p, taken, c, kind):
    """An expert layer on one sequence x (T, d)."""
    eps = c["rms_norm_eps"]
    x = x + attention(_rms_norm(x, p["attn_norm"], eps), p, c, kind)
    u = _rms_norm(x, p["mlp_norm"], eps)
    out, margin, bad = experts(u, p, taken, c)
    return x + out + swiglu(u, p, "shared_"), margin, bad


def layer_weights(params, c):
    """Each run layer's weights out of the program's tree, in order: a
    leading layer's own block, else the layer's slice of `blocks` with
    its kind's slice of `kinds`."""
    lead = n_lead(c)
    seen = {}
    for i, (kind, _, _) in enumerate(layers(c)):
        if i < lead:
            yield params["lead"][i]
            continue
        rank = seen.get(kind, 0)
        seen[kind] = rank + 1
        yield {**{n: a[i - lead] for n, a in params["blocks"].items()},
               **{n: a[rank] for n, a in
                  params.get("kinds", {}).get(_KINDS[kind], {}).items()}}


_HEAD_BLOCKS = 8


def _head_block(x, part, bad):
    return jnp.where(bad[:, None], jnp.nan, x) @ part.astype(F32)


def _head(x, w, bad, jit):
    """x (T, d) W_head -> (T, V) float32 **on the host**, a block of the
    head's columns at a time (families/glm4moelite.py says why: two lanes'
    logits on the device stood beside the engine's pool and parameters).
    A position marked `bad` gets NaN throughout."""
    vocab = w.shape[1]
    n = _HEAD_BLOCKS if vocab % _HEAD_BLOCKS == 0 else 1
    cols = vocab // n
    block = jit(_head_block)
    out = np.empty((x.shape[0], vocab), np.float32)
    for i in range(n):
        out[:, i * cols:(i + 1) * cols] = block(
            x, w[:, i * cols:(i + 1) * cols], bad)
    return out


def _key(tokens) -> bytes:
    return np.asarray(tokens).astype(np.int32).tobytes()


def forward(params, tokens, c, jit=lambda f: f, routing="handed"):
    """tokens (T,) int32 -> (logits (T, V) float32 on the host, margin
    (T,)), one sequence; `margin` is each position's smallest over the
    expert layers.  `routing`: "handed" takes what `score` left for these
    tokens (its own top-k where nothing was left), None the reference's
    own, an array (T, expert layers, k) that.  Parameters are cast to
    float32 a layer at a time, at their use, and the output head an
    eighth of the vocabulary at a time (`_head`).  `jit=jax.jit` compiles
    each kind of layer once and runs it per layer."""
    if isinstance(routing, str):
        routing = _HANDED.get(_key(tokens))
    run, lead = layers(c), n_lead(c)
    dense_fn = {k: jit(functools.partial(dense_block, c=c, kind=k))
                for k in {k for k, _, _ in run[:lead]}}
    expert_fn = {k: jit(functools.partial(expert_block, c=c, kind=k))
                 for k in {k for k, _, _ in run[lead:]}}
    x = params["embed"][tokens].astype(F32)
    margin = jnp.full(x.shape[:1], jnp.inf, F32)
    bad = jnp.zeros(x.shape[:1], bool)
    if routing is not None and routing.shape != (
            x.shape[0], len(run) - lead, c["num_experts_per_tok"]):
        routing, bad = None, ~bad         # not a routing of this model
    for i, (p, (kind, _, _)) in enumerate(zip(layer_weights(params, c), run)):
        if i < lead:
            x = dense_fn[kind](x, p)
            continue
        x, m, b = expert_fn[kind](
            x, p, None if routing is None
            else jnp.asarray(routing[:, i - lead]))
        margin, bad = jnp.minimum(margin, m), bad | b
    x = jit(functools.partial(_rms_norm, eps=c["rms_norm_eps"]))(
        x, params["final_norm"])
    return _head(x, params["lm_head"], bad, jit), margin


def row_loss(params, row, c, jit=lambda f: f):
    """Mean next-token cross entropy of one row (T+1,), float32."""
    logits, _ = forward(params, row[:-1], c, jit=jit, routing=None)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, row[1:, None], axis=-1)[:, 0]
    return jnp.mean(logz - tgt)


# ---------------------------------------------------------------------------
# the engine's own logits, and its routing
# ---------------------------------------------------------------------------
def score(e, config: dict, seqs, n_prompt: int):
    """The engine's scoring entry: prefill through its own chunk program
    (the launches an idle engine's tick would use, each writing and
    reading the lane's blocks and its slot's rings) and teacher-forced
    steps through the function its burst scans, both compiled to hand out
    the experts they took, which are kept for `forward` under each lane's
    token ids."""
    got, taken = e.score(seqs, n_prompt, routing=True)
    _HANDED.clear()
    for lane, route in enumerate(taken):
        _HANDED[_key(seqs[lane])] = np.asarray(route)
    return got


# ---------------------------------------------------------------------------
# Operations and bytes a step needs, from shapes alone: what the
# algorithm requires, not what the program happens to execute.
# ---------------------------------------------------------------------------
def _dims(c: dict) -> dict:
    run, hd = layers(c), c["head_dim"]
    lead = n_lead(c)
    return {"d": c["hidden_size"], "v": c["vocab_size"],
            "kv": c["num_key_value_heads"] * hd,
            # query widths of the run layers, by kind
            "q_full": [h * hd for k, h, _ in run if k == "full_attention"],
            "q_slide": [h * hd for k, h, _ in run
                        if k == "sliding_attention"],
            "heads": sum(h for _, h, _ in run),
            "fd": c["intermediate_size"], "f": c["moe_intermediate_size"],
            "fs": c["shared_expert_intermediate_size"],
            "e": published_experts(c), "held": held_range(c)[1],
            "k": c["num_experts_per_tok"], "window": c["sliding_window"],
            "n": len(run), "nd": lead, "ne": len(run) - lead}


def _itemsize(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]


def matrix_params(c: dict) -> dict:
    """Matrix parameters of the run layers' parts, and of what is held
    here."""
    s = _dims(c)
    d = s["d"]
    parts = {"attn": sum(2 * d * q for q in s["q_full"] + s["q_slide"])
             + s["n"] * 2 * d * s["kv"] + d * s["heads"],
             "dense_ffn": 3 * d * s["fd"], "shared": 3 * d * s["fs"],
             "router": d * s["e"], "expert": 3 * d * s["f"]}
    # every weight outside the routed experts that a step reads once: the
    # head, not the embedding (a gather of the step's rows)
    parts["dense"] = parts["attn"] + s["nd"] * parts["dense_ffn"] \
        + s["ne"] * (parts["shared"] + parts["router"]) + s["v"] * d
    parts["total"] = parts["dense"] + s["v"] * d \
        + s["ne"] * s["held"] * parts["expert"]
    return parts


def expected_held_experts(c: dict, rows: float) -> float:
    """Distinct held experts that `rows` tokens take in one layer under
    uniform routing: held x (1 - (1 - k/E)^rows).  (4 of 128 for one row,
    28.7 for eight, all 128 from some 200 rows on.)"""
    s = _dims(c)
    return s["held"] * (1.0 - (1.0 - s["k"] / s["e"]) ** rows)


def expert_bytes_per_step(c: dict, lanes: int) -> float:
    """Bytes of expert weights one decode step of `lanes` tokens needs:
    the held experts taken in every expert layer, each once."""
    s = _dims(c)
    return s["ne"] * expected_held_experts(c, lanes) \
        * matrix_params(c)["expert"] * _itemsize(c["param_dtype"])


def routed_choices_per_row(c: dict) -> int:
    """Top-k choices one row makes through the stack: k in every expert
    layer (of which held / E are expected to fall here)."""
    s = _dims(c)
    return s["ne"] * s["k"]


def expert_operand(c: dict):
    """What an op that reads a layer's held expert weights shows in its
    HLO text: an operand shaped [held,d,f] or [held,f,d] (after the
    layers' axis, where the stacks are whole), as a compiled pattern."""
    s = _dims(c)
    return re.compile(rf"\[(?:\d+,)?{s['held']},(?:{s['d']},{s['f']}|"
                      rf"{s['f']},{s['d']})\]")


def _kv_row_bytes(c: dict) -> int:
    return 2 * _dims(c)["kv"] * _itemsize(
        c.get("cache_dtype", c["compute_dtype"]))


def ring_bytes_per_step(c: dict, live_kv_tokens: float, lanes: int) -> float:
    """Ring rows one decode step must read: every window layer, every
    lane, the rows its query sees (at most the window; the lanes taken
    at their mean length, an upper estimate while some are shorter than
    the window), K and V."""
    s = _dims(c)
    mean_len = live_kv_tokens / lanes if lanes else 0.0
    return len(s["q_slide"]) * lanes * min(s["window"], mean_len) \
        * _kv_row_bytes(c)


def ring_operand(c: dict):
    """What an op that reads or writes the window layers' rings shows in
    its HLO text: an array whose trailing dimensions are a ring's
    (sliding_window + prefill_chunk rows of [Hkv, head_dim]), as a
    compiled pattern."""
    rows = c["sliding_window"] + c["engine"]["prefill_chunk"]
    return re.compile(rf"\[(?:\d+,)*{rows},{c['num_key_value_heads']},"
                      rf"{c['head_dim']}\]")


def decode_step_bytes(c: dict, live_kv_tokens: float, lanes: int) -> float:
    """Bytes one decode step of `lanes` tokens must move: every weight
    outside the routed experts once (the head once; the embedding is a
    gather), of the held experts those the lanes are expected to take,
    the full layers' KV of the live positions, and the window layers'
    rows seen."""
    s = _dims(c)
    return matrix_params(c)["dense"] * _itemsize(c["param_dtype"]) \
        + expert_bytes_per_step(c, lanes) \
        + len(s["q_full"]) * _kv_row_bytes(c) * live_kv_tokens \
        + ring_bytes_per_step(c, live_kv_tokens, lanes)


def prefill_flops(c: dict, tokens: float, context: float) -> float:
    """FLOPs that `tokens` prompt tokens need which together attend over
    `context` positions (a token at position p attends p + 1): the
    layers' matrices with the held experts a token takes (k x held / E
    expected, not the whole share a chunk's visit multiplies), attention
    scores and values at each layer's own query heads, over the context
    in a full layer and over at most the window in a window layer.  The
    output head, once a prompt, is left out."""
    s, m = _dims(c), matrix_params(c)
    dense = m["dense"] - s["v"] * s["d"]
    routed = s["ne"] * s["k"] * s["held"] / s["e"] * m["expert"]
    mean_ctx = context / tokens if tokens else 0.0
    return 2.0 * (dense + routed) * tokens \
        + 4.0 * sum(s["q_full"]) * context \
        + 4.0 * sum(s["q_slide"]) * tokens * min(s["window"], mean_ctx)


# ---------------------------------------------------------------------------
# for bench/tools/memory_fit.py
# ---------------------------------------------------------------------------
def serve_programs(config: dict, place):
    """What a replica of `config` keeps resident, as shapes, and its
    largest programs lowered at the engine's sizes: the widest decode
    burst and one prefill chunk."""
    from ray_tpu.models.decoding import (
        init_sequence_state, make_paged_engine_fns)

    cfg = program_config(config)
    eng = config["engine"]
    n_blocks = eng["num_slots"] * eng["max_len"] // eng["block_size"] + 1
    b_max = -(-eng["max_len"] // eng["block_size"])
    params = place(jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)))
    state = place(jax.eval_shape(lambda: init_sequence_state(
        cfg, n_blocks, eng["block_size"], num_slots=eng["num_slots"],
        prefill_chunk=eng["prefill_chunk"])))
    rng = place(jax.eval_shape(lambda: jax.random.key(0)))
    chunk_fn, burst_fn, _ = make_paged_engine_fns(cfg)

    def arr(shape, dtype):
        return place(jax.ShapeDtypeStruct(shape, dtype))

    w, ch = eng["num_slots"], eng["prefill_chunk"]
    return {"params": params, "sequence_state": state}, [
        (f"paged_decode_burst w={w}", burst_fn.lower(
            params, state, arr((w,), jnp.int32), arr((w, b_max), jnp.int32),
            arr((w,), jnp.int32), arr((w,), jnp.bool_),
            arr((w,), jnp.float32), rng, n_steps=eng["max_burst"],
            slots=arr((w,), jnp.int32))),
        (f"paged_prefill_chunk c={ch}", chunk_fn.lower(
            params, state, arr((ch,), jnp.int32), arr((b_max,), jnp.int32),
            arr((), jnp.int32), arr((), jnp.int32),
            slot=arr((), jnp.int32)))]
