"""The mellum family: everything the harness knows of
Mellum2-12B-A2.5B-Instruct (`model_type: mellum`, JetBrains): a decoder
whose layers repeat with period 4, three sliding-window layers to one
full-attention layer with its own YaRN rope, a head size the width does
not give, QK-norm, and 64 small experts of which a token takes 8.  A
configuration file says `"family": "mellum"`; what the harness asks of a
family is listed at the top of families/mistral.py.  This one also gives
`ring_operand` and `ring_bytes_per_step`, for `window_attn_roofline`,
and `TOLERANCES`, with its measurements beside it.

The model, for layer `l` of `num_hidden_layers` (the first that many
entries of `layer_types`; `mlp_layer_types` is `sparse` throughout, so
`intermediate_size` is used by no layer): `h = x + Attn_l(RMSNorm(x))`,
`y = h + MoE_l(RMSNorm(h))`, eps `rms_norm_eps`, no biases, untied
embedding and head.

  Attn   q = u Wq (H heads of `head_dim`), k = u Wk, v = u Wv (Hkv heads),
         q and k each under an RMSNorm over `head_dim` with a learned gain
         (assumed: below), then the rope, half-rotation layout;
         softmax(q k^T / sqrt(head_dim) + mask) v, query head j reading KV
         head j // (H / Hkv); out Wo.
         `sliding_attention`: position t sees t - sliding_window < p <= t;
         rope theta unscaled.  `full_attention`: causal; rope under YaRN:
         pair i of head_dim / 2 has f_i = theta^(-2i / head_dim), c(n) =
         head_dim ln(original / (2 pi n)) / (2 ln theta), low =
         floor(c(beta_fast)), high = ceil(c(beta_slow)) (within 0 ..
         head_dim - 1), r_i = clip((i - low) / (high - low), 0, 1),
         inv_freq_i = f_i (1 - r_i) + f_i / factor r_i; cos and sin are
         multiplied by attention_factor.
  MoE    router logits u Wr (E), float32 soft-max over all E, the
         num_experts_per_tok largest, their weights renormalised to sum 1
         (`norm_topk_prob`): equal to the soft-max over the taken
         experts' logits alone.  sum_e g_e W_down,e (silu(W_gate,e u) *
         W_up,e u), experts `moe_intermediate_size` wide.  No shared
         expert.

The reference below is those equations in plain `jax.numpy` float32,
independent of `ray_tpu/`: no kernels, no cache, no batching; attention
with explicit masks in blocks of queries, every expert evaluated on
every token and weighted (zero where not taken).  It shares only the
parameter tree's layout, which is data:

    embed (V,d)  lm_head (d,V)  final_norm (d,)
    blocks.{attn_norm,mlp_norm} (L,d)  blocks.{q_norm,k_norm} (L,hd)
    blocks.wq (L,d,H*hd)  blocks.{wk,wv} (L,d,Hkv*hd)  blocks.wo (L,H*hd,d)
    blocks.router (L,d,E)  blocks.{w_gate,w_up} (L,E,d,f)
    blocks.w_down (L,E,f,d)

Callers run it under `jax.default_matmul_precision("highest")`.

**Routing is handed over.**  With 8 of 64 taken the reference's own gap
between the last expert taken and the first left out is under
ROUTER_MARGIN in nearly every layer of every position, so the harness's
margin (bench/harness/reference.py) would decide no position at all.
`score` therefore asks the engine's scoring entry for the experts the
program took at every position and layer, and keeps them in `_HANDED`
under the lane's token ids; `forward`, which `deployment.logits_check`
calls next in the same process on the same tokens (this module is loaded
once a process: `spec.load_file`), looks its tokens up there.  With a
routing in hand it takes the program's experts, computes their gates
itself from its own float32 router logits, and holds the program's
choice to ROUTER_SLACK: an expert taken whose reference logit lies more
than the slack under the reference's own k-th, one left out that lies
more than the slack above it, a set that is not `num_experts_per_tok`
distinct experts, each make the position's logits NaN, which
`logits_verdict` refuses.  Every other position is decided, with the
margin 1 - (how far the program's set strays from the reference's, in
the units of ROUTER_MARGIN: 0 where the sets are equal).  With nothing
handed over `forward` falls back to its own top-k and its true margin.

Departures from the published model: the MTP head (`described_as`; the
config has no key for it) is left out.  Assumed, because `config.json`
leaves them to the family's convention (the configuration file lists
them under `assumed`): QK-norm (the config family whose keys these are
has it unconditionally and keyless), and that the window counts the
current position.
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
# (the docstring above), so LOGITS_REL_EXPERTS holds all 68 of a run
# (`check`: 4 lanes x (the last of 1536 prompt positions + 16 steps)).
# Measured on the chip at published widths, depth 8: see the readings
# beside each number, written by the PR that measured them.
#
# ROUTER_SLACK, in the units of ROUTER_MARGIN (a share of the rms of the
# token's router logits).  LOGITS_REL_EXPERTS: rms error of a position's
# logits as a share of the reference's own.
#   PR 34, 26 seeds x 68 positions x 8 layers (my chip runs: 8 seeds in
#   call 1, 18 more in the cell's own runs): the program as it is strays
#   from the reference's set by at most 0.017-0.038 a seed (largest
#   0.0375; PR 23 saw flips at margins up to 0.076 on Mixtral's 8 experts)
#   and a position's error has medians 0.0072-0.0075 and a largest a seed
#   of 0.0078-0.0091 (largest 0.00905).
#   With the pool and the rings rounded to 8-bit floats (float8_e4m3fn,
#   the nearest precision below the cache dtype the configuration states)
#   after every prefill chunk and every decode step, two seeds (call 2;
#   rounded by eager ops: inside one jit the TPU compiler drops the pair
#   of converts and call 1's reading was the unrounded one to the digit):
#   medians 0.0277 / 0.0273, largest 0.0332 / 0.0318, strays up to
#   0.120 / 0.169.  0.016 lies between 0.0091 and 0.0318 with a factor of
#   nearly two on both sides, and even the rounded cache's median fails
#   it; 0.08 lies between 0.038 and 0.120.  At these widths the check also
#   fails (one seed, call 1): YaRN left off the full layers (median error 0.102,
#   strays to 0.43), QK-norm left out (strays to 1.07), top-7 routing (no
#   position has 8 experts), the window mask dropped (strays to 1.24) and
#   one expert's output dropped (error up to 0.111, strays to 1.19).
TOLERANCES = {"LOGITS_REL_EXPERTS": 0.016, "ROUTER_SLACK": 0.08}

_KINDS = {"sliding_attention": "window", "full_attention": "full"}
# What `score` handed over: {a lane's token ids (int32 bytes): (T, L, k)}.
_HANDED: dict = {}


# ---------------------------------------------------------------------------
# configuration file -> the program
# ---------------------------------------------------------------------------
def layer_kinds(config: dict) -> list:
    """The kinds of the layers that are run: the first
    `num_hidden_layers` entries of `layer_types`."""
    n = config["num_hidden_layers"]
    kinds = config["layer_types"][:n]
    if len(kinds) < n or set(kinds) - set(_KINDS):
        raise SpecError(f"layer_types must name {n} layers, each one of "
                        f"{sorted(_KINDS)}")
    return kinds


def _period(kinds: list) -> list:
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
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


def program_config(config: dict):
    import dataclasses

    from ray_tpu.models.transformer import TransformerConfig

    needs = {"d_head", "d_expert", "qk_norm", "layer_pattern", "window",
             "yarn"}
    lacks = needs - {f.name for f in dataclasses.fields(TransformerConfig)}
    if lacks:
        _withdraw_app()
        raise SpecError(
            f"this program's TransformerConfig has no {sorted(lacks)}: it "
            f"cannot run a configuration of the mellum family")
    from ray_tpu.ops.rotary import YarnScaling

    for key, want in (("mlp_layer_types", ["sparse"]),
                      ("use_sliding_window", [True]),
                      ("norm_topk_prob", [True]),
                      ("attention_bias", [False])):
        got = config[key]
        if any(g not in want for g in (got if isinstance(got, list)
                                       else [got])):
            raise SpecError(f"{key} = {got!r}: the program's layers are "
                            f"{key} = {want[0]!r} throughout")
    rope = config["rope_parameters"]
    full, slide = rope["full_attention"], rope["sliding_attention"]
    if full["rope_type"] != "yarn" or slide["rope_type"] != "default" \
            or full["rope_theta"] != slide["rope_theta"]:
        raise SpecError("the program ropes full layers under YaRN and "
                        "window layers unscaled, with one theta")
    return TransformerConfig(
        name=config["name"],
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_head=config["head_dim"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_experts=config["num_experts"],
        expert_top_k=config["num_experts_per_tok"],
        qk_norm=bool(config.get("assumed", {}).get("qk_norm", True)),
        layer_pattern=tuple(_KINDS[k] for k in _period(layer_kinds(config))),
        window=config["sliding_window"],
        rope_theta=float(full["rope_theta"]),
        yarn=YarnScaling(
            factor=float(full["factor"]),
            original_max_len=int(full["original_max_position_embeddings"]),
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
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
    return x * jax.lax.rsqrt(var + eps) * gain


def inv_frequencies(c: dict, kind: str):
    """(head_dim / 2,) float32 inverse frequencies of a layer of `kind`,
    and the factor on cos and sin."""
    hd = c["head_dim"]
    p = c["rope_parameters"][kind]
    theta = float(p["rope_theta"])
    f = theta ** (-jnp.arange(hd // 2, dtype=F32) * 2.0 / hd)
    if p["rope_type"] != "yarn":
        return f, 1.0

    def turns_at(n):
        return hd * math.log(p["original_max_position_embeddings"]
                             / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(turns_at(p["beta_fast"])), 0)
    high = min(math.ceil(turns_at(p["beta_slow"])), hd - 1)
    r = jnp.clip((jnp.arange(hd // 2, dtype=F32) - low)
                 / max(high - low, 0.001), 0.0, 1.0)
    return f * (1.0 - r) + f / p["factor"] * r, float(p["attention_factor"])


def _rope(x, c, kind):
    """x (T, heads, hd): rotate pairs (i, i + hd/2)."""
    t, _, hd = x.shape
    inv, factor = inv_frequencies(c, kind)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


_QUERY_BLOCK = 512


def attention(u, bp, c, kind):
    """Grouped-query attention of the normed input u (T, d), queries
    _QUERY_BLOCK at a time against the whole context."""
    t = u.shape[0]
    h, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    eps = c["rms_norm_eps"]
    q = (u @ bp["wq"]).reshape(t, h, hd)
    k = (u @ bp["wk"]).reshape(t, hkv, hd)
    v = (u @ bp["wv"]).reshape(t, hkv, hd)
    if c.get("assumed", {}).get("qk_norm", True):
        q = _rms_norm(q, bp["q_norm"], eps)
        k = _rms_norm(k, bp["k_norm"], eps)
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
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v))
    return jnp.concatenate(out, 0).reshape(t, h * hd) @ bp["wo"]


def moe(u, bp, taken, c):
    """The sparse block over u (T, d).  `taken` (T, k) int32: the experts
    the program took (None: the reference's own top-k).  Returns (out,
    margin (T,), bad (T,) bool): the docstring at the top says what each
    is."""
    k, e = c["num_experts_per_tok"], c["num_experts"]
    logits = u @ bp["router"].astype(F32)                      # (T, E)
    top, idx = jax.lax.top_k(logits, k + 1)
    rms = jnp.sqrt(jnp.mean(jnp.square(logits), axis=-1))
    if taken is None:
        taken = idx[:, :k]
        margin = (top[:, k - 1] - top[:, k]) / rms
        bad = jnp.zeros(margin.shape, bool)
    else:
        mine = jnp.sum(jax.nn.one_hot(taken, e, dtype=F32), axis=1) > 0
        kth = top[:, k - 1]
        lowest_in = jnp.min(jnp.where(mine, logits, jnp.inf), axis=-1)
        highest_out = jnp.max(jnp.where(mine, -jnp.inf, logits), axis=-1)
        stray = jnp.maximum(jnp.maximum(kth - lowest_in, highest_out - kth),
                            0.0) / rms
        margin = 1.0 - stray
        bad = (stray > TOLERANCES["ROUTER_SLACK"]) \
            | (jnp.sum(mine, axis=-1) != k)
    gates = jax.nn.softmax(jnp.take_along_axis(logits, taken, axis=-1),
                           axis=-1)                            # (T, k)
    weight = jnp.sum(jax.nn.one_hot(taken, e, dtype=F32) * gates[..., None],
                     axis=1)                                   # (T, E)

    def one(acc, ex):
        gate, up, down, w = ex
        hidden = jax.nn.silu(u @ gate.astype(F32)) * (u @ up.astype(F32))
        return acc + w[:, None] * (hidden @ down.astype(F32)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        bp["w_gate"], bp["w_up"], bp["w_down"], weight.T))
    return out, margin, bad


_EXPERTS = ("w_gate", "w_up", "w_down", "router")


def block(x, bp, taken, c, kind):
    """One decoder layer on one sequence x (T, d)."""
    a = {n: w.astype(F32) for n, w in bp.items() if n not in _EXPERTS}
    eps = c["rms_norm_eps"]
    x = x + attention(_rms_norm(x, a["attn_norm"], eps), a, c, kind)
    out, margin, bad = moe(_rms_norm(x, a["mlp_norm"], eps), bp, taken, c)
    return x + out, margin, bad


def _final_norm(x, gain, eps):
    return _rms_norm(x, gain.astype(F32), eps)


def _head_block(x, columns):
    return x @ columns.astype(F32)


def _key(tokens) -> bytes:
    return np.asarray(tokens).astype(np.int32).tobytes()


def forward(params, tokens, c, jit=lambda f: f, routing="handed"):
    """tokens (T,) int32 -> (logits (T, V) float32, margin (T,)), one
    sequence; `margin` is each position's smallest over the layers.
    `routing`: "handed" takes what `score` left for these tokens (its
    own top-k where nothing was left), None the reference's own, an
    array (T, L, k) that.  Parameters are cast to float32 a layer at a
    time, at their use, and the output head an eighth of the vocabulary
    at a time.  `jit=jax.jit` compiles each kind of layer once and runs
    it per layer."""
    if isinstance(routing, str):
        routing = _HANDED.get(_key(tokens))
    kinds = layer_kinds(c)
    fns = {kind: jit(functools.partial(block, c=c, kind=kind))
           for kind in set(kinds)}
    x = params["embed"][tokens].astype(F32)
    margin = jnp.full(x.shape[:1], jnp.inf, F32)
    bad = jnp.zeros(x.shape[:1], bool)
    if routing is not None and routing.shape != (
            x.shape[0], len(kinds), c["num_experts_per_tok"]):
        routing, bad = None, ~bad         # not a routing of this model
    for i, kind in enumerate(kinds):
        x, m, b = fns[kind](
            x, {n: a[i] for n, a in params["blocks"].items()},
            None if routing is None else jnp.asarray(routing[:, i]))
        margin, bad = jnp.minimum(margin, m), bad | b
    x = jit(functools.partial(_final_norm, eps=c["rms_norm_eps"]))(
        x, params["final_norm"])
    out = params["embed"].T if c.get("tie_word_embeddings") \
        else params["lm_head"]
    cols = -(-out.shape[1] // 8)
    head = jit(_head_block)
    logits = jnp.concatenate([head(x, out[:, i:i + cols])
                              for i in range(0, out.shape[1], cols)], axis=1)
    return jnp.where(bad[:, None], jnp.nan, logits), margin


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
    and teacher-forced steps through the function its burst scans, both
    compiled to hand out the experts they took, which are kept for
    `forward` under each lane's token ids."""
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
    kinds = layer_kinds(c)
    hd = c["head_dim"]
    return {"d": c["hidden_size"], "v": c["vocab_size"],
            "q": c["num_attention_heads"] * hd,
            "kv": c["num_key_value_heads"] * hd,
            "f": c["moe_intermediate_size"], "e": c["num_experts"],
            "k": c["num_experts_per_tok"], "window": c["sliding_window"],
            "n": len(kinds), "full": kinds.count("full_attention"),
            "slide": kinds.count("sliding_attention")}


def _itemsize(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]


def layer_params(c: dict, active_only: bool = False) -> int:
    """Matrix parameters of one layer; `active_only`: the experts one
    token takes, not all of them."""
    s = _dims(c)
    experts = (s["k"] if active_only else s["e"]) * 3 * s["d"] * s["f"]
    return 2 * s["d"] * s["q"] + 2 * s["d"] * s["kv"] + s["d"] * s["e"] \
        + experts


def total_params(c: dict, active_only: bool = False) -> int:
    s = _dims(c)
    emb = s["v"] * s["d"] * (1 if c.get("tie_word_embeddings") else 2)
    return s["n"] * layer_params(c, active_only) + emb


def expected_routed_experts(c: dict, lanes: float) -> float:
    """Distinct experts that `lanes` tokens take in one layer under
    uniform routing: E (1 - (1 - k/E)^lanes).  (8 for one lane, 35 for
    six, 51 for twelve of 64.)"""
    e, k = c["num_experts"], c["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** lanes)


def expert_bytes_per_step(c: dict, lanes: int) -> float:
    """Bytes of expert weights one decode step of `lanes` tokens needs:
    the experts taken in every layer, each once."""
    s = _dims(c)
    return s["n"] * expected_routed_experts(c, lanes) * 3 * s["d"] * s["f"] \
        * _itemsize(c["param_dtype"])


def expert_operand(c: dict):
    """What an op that reads a layer's expert weights shows in its HLO
    text: an operand shaped [E,d,f] or [E,f,d] (after the layers' axis,
    where the stacks are whole), as a compiled pattern."""
    s = _dims(c)
    return re.compile(rf"\[(?:\d+,)?{s['e']},(?:{s['d']},{s['f']}|"
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
    return s["slide"] * lanes * min(s["window"], mean_len) * _kv_row_bytes(c)


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
    outside the experts once (the head once; the embedding is a gather),
    of the experts those the lanes are expected to take, the full
    layers' KV of the live positions, and the window layers' rows seen."""
    s = _dims(c)
    dense = s["n"] * (layer_params(c) - s["e"] * 3 * s["d"] * s["f"]) \
        + s["d"] * s["v"]
    return dense * _itemsize(c["param_dtype"]) \
        + expert_bytes_per_step(c, lanes) \
        + s["full"] * _kv_row_bytes(c) * live_kv_tokens \
        + ring_bytes_per_step(c, live_kv_tokens, lanes)


def prefill_flops(c: dict, tokens: float, context: float) -> float:
    """FLOPs that `tokens` prompt tokens need which together attend over
    `context` positions (a token at position p attends p + 1): the
    layers' matrices with the 8 experts a token takes (not the 64 a
    chunk's visit multiplies), attention scores and values over the
    context in a full layer and over at most the window in a window
    layer.  The output head, once a prompt, is left out."""
    s = _dims(c)
    mean_ctx = context / tokens if tokens else 0.0
    seen = s["full"] * context \
        + s["slide"] * tokens * min(s["window"], mean_ctx)
    return 2.0 * s["n"] * layer_params(c, active_only=True) * tokens \
        + 4.0 * s["q"] * seen


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
