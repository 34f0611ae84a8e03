"""The lfm2moe family: everything the harness knows of LFM2-8B-A1B
(`model_type: lfm2_moe`, LiquidAI): a decoder in which three of every four
layers mix positions by nothing but a gated depth-wise convolution of three
rows, the others are soft-max layers with QK-norm over heads of 64, the
first two layers' FFN is a dense SwiGLU and every other layer's is 32 wide
experts of which a token takes 4, by sigmoid scores under a selection bias.
A configuration file says `"family": "lfm2moe"`; what the harness asks of a
family is listed at the top of families/mistral.py.  This one also gives
`mixer_operand` (for `shortconv_step_share.decode` and
`shortconv_roofline.decode`), `conv_bytes_per_step` (for the latter) and
`TOLERANCES`, with its measurements beside it.

The model, for layer `l` of `num_hidden_layers` (the first that many entries
of `layer_types`), N(u; w) = u / rms(u) * w in float32 with eps `norm_eps`
(a plain gain), no bias anywhere:

    x = E[token]
    x += Mixer_l(N(x; operator_norm_l))
    x += FFN_l(N(x; ffn_norm_l))
    logits = N(x; embedding_norm) E^T      the final norm is the published
                                           `embedding_norm`; the head is E

  conv    d = hidden_size, J = conv_L_cache:  [B | C | z] = u W_in (d -> 3d,
          three chunks of d in that order);  h = B * z element by element;
          c_t = sum_{j < J} w_j * h_{t - (J - 1) + j} a channel (depth-wise,
          causal, zeros before the sequence's start);  y = C * c;  y W_out
          (d -> d).  No activation, no state but the last J - 1 rows of h.
  full_attention
          H = num_attention_heads, Hkv = num_key_value_heads, hd =
          hidden_size / H:  q = u Wq (H x hd), k = u Wk, v = u Wv (Hkv x
          hd);  q = N(q; q_layernorm), k = N(k; k_layernorm) over a head;
          rope by half-rotation over all hd dimensions (pair i with i + hd /
          2 at theta^(-2i / hd)), no scaling;  causal softmax(q k^T /
          sqrt(hd)) v, query head j reading KV head j // (H / Hkv);  Wo.
  FFN     l < num_dense_layers: W2 (silu(v W1) * (v W3)) at width
          intermediate_size.  Else s = sigmoid(v W_r) over the num_experts
          experts in float32; the num_experts_per_tok largest of s +
          expert_bias are taken (`use_expert_bias`); their gates are s of
          the taken over (their sum + 1e-6) (`norm_topk_prob`), times
          routed_scaling_factor; each expert the same SwiGLU at width
          moe_intermediate_size.  No shared expert.  **Every expert is held
          here**: no share of the experts is cut, so the model-configs
          guide's test that the shares add up to the uncut layer has
          nothing to add up and does not apply.

The reference below is those equations in plain `jax.numpy` float32,
independent of `ray_tpu/`: no kernels, no cache, no chunks, no scan over
layers; the convolution as J shifted products over the whole sequence,
attention with an explicit mask in blocks of queries, every expert evaluated
on every token and weighted (zero where not taken).  It shares only the
parameter tree's layout, which is data:

    embed (V,d)  final_norm (d,)                    (no lm_head: tied)
    lead[l], a leading dense layer, unstacked: attn_norm, mlp_norm (d,)
        in_proj (d,3d)  conv_w (J,d)  out_proj (d,d)   (or an attention's)
        w_gate, w_up (d,f_dense)  w_down (f_dense,d)
    blocks.*, stacked over the layers behind the leading ones: attn_norm,
        mlp_norm (.,d)  router (.,d,E)  router_bias (.,E) float32
        w_gate, w_up (.,E,d,f)  w_down (.,E,f,d)
    kinds.conv.*, stacked over the conv layers behind the leading ones:
        in_proj (.,d,3d)  conv_w (.,J,d)  out_proj (.,d,d)
    kinds.full.*, over the full layers: wq (.,d,H*hd)  wk, wv (.,d,Hkv*hd)
        wo (.,H*hd,d)  q_norm, k_norm (.,hd)

Callers run it under `jax.default_matmul_precision("highest")`.

**Routing is handed over**, as in families/laguna.py and for its reason:
where the reference's own gap between the last expert taken and the first
left out is under the program's rounding (bfloat16 router inputs), the
program takes another expert and the position's logits are another
function's.  `score` asks the engine's scoring entry for the experts the
program took at every position and expert layer and keeps them under the
lane's token ids; `forward` takes them, computes their gates itself from its
own float32 scores, and holds the program's choice to ROUTER_SLACK on its
own selection scores (s + bias).

Assumed, because `config.json` leaves them to the model's code (the
configuration file lists each under `assumed` with its ground): the order of
W_in's three chunks (`B, C, x` in the `lfm2_moe` modelling code: a
permutation of columns under seeded weights); the head tied to the embedding
(the published 8.3 B closes only so); half-rotation rope over the whole
head; every norm a plain gain, before the mixer, before the FFN, over each
head of q and k, and before the head; `expert_bias` drawn small and away
from zero; the gates' denominator is the sum + 1e-6 here and max(sum, 1e-9)
in the program (a relative 1e-6 / sum, under 1e-5 with 4 sigmoid scores: no
float32 test sees it).
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.spec import SpecError

F32 = jnp.float32

# The comparison that decides `correct` (bench/harness/reference.py), for
# this family.  Every compared position is decided by handed-over routing
# (above), so LOGITS_REL_EXPERTS holds all 68 of a run (`check`: 4 lanes x
# (the last of 1,300 prompt positions, prefilled in launches of 512, 512
# and 276 rows: the conv rows handed over twice through the slot and a
# ragged tail, + 16 decode steps)).  Measured on the chip at published
# widths, 14 layers, all 32 experts (my chip runs, PR 67; PERF.md section 7
# has the table).
#
# LOGITS_REL_EXPERTS: rms error of a position's logits as a share of the
# reference's own.  ROUTER_SLACK: how far the program's set of experts may
# stray from the reference's, as a share of the spread (standard deviation
# over the 32 experts) of the token's selection scores.
#   The program as it is, 22 seeds x 68 positions x 12 expert layers (calls
#   A, C and D: four bare checks and the checks of eighteen runs of the
#   cell, thirteen of them from the final tree's archive; the decode steps
#   through the block loop in call A and through the decode kernel since):
#   a position's error has medians 0.0549-0.0590 and a largest a seed of
#   0.0620-0.0698; it strays by at most 0.10-0.28 a seed (medians
#   0.017-0.034).  Twice what Laguna's nine layers read (medians
#   0.026-0.029): fourteen layers, and a mixer that is a product of three
#   projections of one input (C * conv(B * z)), which hands a relative
#   error of its input on about threefold where attention hands it on
#   once; the same error, ~5% of the router's input by the last layers, is
#   what moves the selection scores by up to 0.28 of their spread.
#   **What a sequence keeps in `cache_dtype` kept in 8-bit floats**
#   (`control("cache_fp8")`: the full layers' K and V and the conv layers'
#   two rows through float8_e4m3fn, the nearest precision below the stated
#   bfloat16, by eager ops after every launch and step; four seeds, calls
#   A and C): error medians 0.184-0.198, largest 0.233-0.253; strays to
#   0.51-0.69 (medians 0.20-0.23), some position outside the slack on
#   every seed.  0.11 lies between 0.0698 and the control's medians 0.184
#   with a factor of 1.6 below and 1.7 above (2.1 to its largest); 0.4
#   lies between 0.28 and 0.51, a factor of 1.4 below and 1.3 above.  The
#   control is refused by both limits on every seed.
#   **What it cannot see:** a layer computed in bfloat16 where the
#   configuration says bfloat16 (the stated dtype is the program's); a
#   router wrong by less than ROUTER_SLACK everywhere, which is what
#   rounding does and a fault rarely; the conv rows' precision apart from
#   the pool's (the control rounds both).  tests/test_short_conv_serving.py
#   holds in float32 at a tiny size: the rows kept in bfloat16, the
#   convolution summed in bfloat16, the router's scores in bfloat16, the
#   rows not handed from launch to launch, either gate dropped, QK-norm
#   left out, the selection made without the bias, soft-max scores, the
#   head untied; and in bfloat16 the cache's and the weights' precision, an
#   expert, a conv layer and a leading layer's mixer dropped.
TOLERANCES = {"LOGITS_REL_EXPERTS": 0.11, "ROUTER_SLACK": 0.4}

_KINDS = {"conv": "conv", "full_attention": "full"}
# What `score` handed over: {a lane's token ids (int32 bytes): (T, L_e, k)}.
_HANDED: dict = {}


# ---------------------------------------------------------------------------
# configuration file -> the program
# ---------------------------------------------------------------------------
def layer_types(config: dict) -> list:
    """The published kind of every run layer: the first
    `num_hidden_layers` entries of `layer_types`."""
    n = config["num_hidden_layers"]
    out = list(config["layer_types"][:n])
    if len(out) < n or set(out) - set(_KINDS):
        raise SpecError(f"layer_types must name {n} layers of "
                        f"{sorted(_KINDS)}")
    return out


def n_lead(config: dict) -> int:
    """The leading layers whose FFN is dense."""
    lead = int(config["num_dense_layers"])
    if not 0 <= lead < config["num_hidden_layers"]:
        raise SpecError(f"num_dense_layers {lead} of "
                        f"{config['num_hidden_layers']} layers")
    return lead


def split_layers(kinds: list):
    """`kinds` (the layers behind the leading ones) as (period, tail):
    whole periods and then the layers that are left, so chosen that the
    fewest layers are traced one by one (a period's and the tail's); a tail
    that is the first layers of one more period is given as ()."""
    best = None
    for p in range(1, len(kinds) + 1):
        period, n = kinds[:p], 1
        while kinds[n * p:(n + 1) * p] == period:
            n += 1
        tail = kinds[n * p:]
        if set(tail) <= set(period) and (
                best is None or p + len(tail) < sum(map(len, best))):
            best = (period, tail)
    period, tail = best
    return period, ([] if tail == period[:len(tail)] else tail)


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

    needs = {"conv_kernel", "layer_tail", "router_bias"}
    lacks = needs - {f.name for f in dataclasses.fields(TransformerConfig)}
    if lacks:
        _withdraw_app()
        raise SpecError(
            f"this program's TransformerConfig has no {sorted(lacks)}: it "
            f"cannot run a configuration of the lfm2moe family")
    for key, want in (("conv_bias", False), ("norm_topk_prob", True),
                      ("use_expert_bias", True)):
        if config[key] != want:
            raise SpecError(f"{key} = {config[key]!r}: the program's layers "
                            f"are {key} = {want!r}")
    heads = config["num_attention_heads"]
    if config["hidden_size"] % heads:
        raise SpecError("hidden_size is not whole heads: the program "
                        "derives the head size")
    kinds = [_KINDS[k] for k in layer_types(config)]
    lead = n_lead(config)
    period, tail = split_layers(kinds[lead:])
    return TransformerConfig(
        name=config["name"],
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        d_head=config["hidden_size"] // heads,
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_experts=config["num_experts"],
        expert_top_k=config["num_experts_per_tok"],
        expert_scoring="sigmoid",
        router_bias=True,
        route_scale=float(config["routed_scaling_factor"]),
        qk_norm=True,
        lead_pattern=tuple(kinds[:lead]),
        layer_pattern=tuple(period),
        layer_tail=tuple(tail),
        conv_kernel=config["conv_L_cache"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        tie_embeddings=True,
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


def _rope(x, theta):
    """x (T, heads, hd): rotate pairs (i, i + hd / 2) of the whole head."""
    t, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


_QUERY_BLOCK = 512


def attention(u, p, c):
    """The soft-max layer's mixer over the normed input u (T, d), queries
    _QUERY_BLOCK at a time against the whole context."""
    t = u.shape[0]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd, eps = c["hidden_size"] // h, c["norm_eps"]
    q = (u @ p["wq"].astype(F32)).reshape(t, h, hd)
    k = (u @ p["wk"].astype(F32)).reshape(t, hkv, hd)
    v = (u @ p["wv"].astype(F32)).reshape(t, hkv, hd)
    q = _rope(_rms_norm(q, p["q_norm"], eps), float(c["rope_theta"]))
    k = _rope(_rms_norm(k, p["k_norm"], eps), float(c["rope_theta"]))
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    out = []
    for lo in range(0, t, _QUERY_BLOCK):
        hi = min(lo + _QUERY_BLOCK, t)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k) / jnp.sqrt(F32(hd))
        seen = jnp.arange(t)[None, :] <= jnp.arange(lo, hi)[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", prob, v))
    return jnp.concatenate(out, 0).reshape(t, h * hd) @ p["wo"].astype(F32)


def short_conv(u, p, c):
    """The gated short convolution over the normed input u (T, d): the
    convolution as J products of the gated rows, each shifted."""
    t, width = u.shape[0], c["conv_L_cache"]
    b, gate, z = jnp.split(u @ p["in_proj"].astype(F32), 3, axis=-1)
    h = jnp.concatenate([jnp.zeros((width - 1, b.shape[1]), F32), b * z], 0)
    w = p["conv_w"].astype(F32)
    conv = sum(w[j] * h[j:j + t] for j in range(width))
    return (gate * conv) @ p["out_proj"].astype(F32)


def mixer(u, p, c, kind):
    return short_conv(u, p, c) if kind == "conv" else attention(u, p, c)


def swiglu(u, p):
    return (jax.nn.silu(u @ p["w_gate"].astype(F32))
            * (u @ p["w_up"].astype(F32))) @ p["w_down"].astype(F32)


def scores(u, p, c):
    """(the router's score of every expert, the scores the selection is
    made on), (T, E) each: sigmoid, and that plus the expert's bias."""
    s = jax.nn.sigmoid(u @ p["router"].astype(F32))
    return s, s + p["router_bias"].astype(F32)


def experts(u, p, taken, c):
    """The routed experts over u (T, d).  `taken` (T, k) int32: the
    experts the program took (None: the reference's own top-k of s +
    bias).  Returns (the routed sum, margin (T,), bad (T,) bool), as
    families/laguna.py's `experts` and in its units, on the selection
    scores; the gates are the unbiased scores'."""
    k, e = c["num_experts_per_tok"], c["num_experts"]
    s, pick = scores(u, p, c)                                    # (T, E)
    top, idx = jax.lax.top_k(pick, k + 1)
    spread = jnp.std(pick, axis=-1)
    if taken is None:
        taken = idx[:, :k]
        margin = (top[:, k - 1] - top[:, k]) / spread
        bad = jnp.zeros(margin.shape, bool)
    else:
        mine = jnp.sum(jax.nn.one_hot(taken, e, dtype=F32), axis=1) > 0
        kth = top[:, k - 1]
        lowest_in = jnp.min(jnp.where(mine, pick, jnp.inf), axis=-1)
        highest_out = jnp.max(jnp.where(mine, -jnp.inf, pick), axis=-1)
        stray = jnp.maximum(jnp.maximum(kth - lowest_in, highest_out - kth),
                            0.0) / spread
        margin = 1.0 - stray
        bad = (stray > TOLERANCES["ROUTER_SLACK"]) \
            | (jnp.sum(mine, axis=-1) != k)
    gates = jnp.take_along_axis(s, taken, axis=-1)               # (T, k)
    gates = F32(c["routed_scaling_factor"]) * gates \
        / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    weight = jnp.sum(jax.nn.one_hot(taken, e, dtype=F32) * gates[..., None],
                     axis=1)                                     # (T, E)

    def one(acc, ex):
        gate, up, down, w = ex
        hidden = jax.nn.silu(u @ gate.astype(F32)) * (u @ up.astype(F32))
        return acc + w[:, None] * (hidden @ down.astype(F32)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        p["w_gate"], p["w_up"], p["w_down"], weight.T))
    return out, margin, bad


def dense_block(x, p, c, kind):
    """A leading layer on one sequence x (T, d)."""
    eps = c["norm_eps"]
    x = x + mixer(_rms_norm(x, p["attn_norm"], eps), p, c, kind)
    return x + swiglu(_rms_norm(x, p["mlp_norm"], eps), p)


def expert_block(x, p, taken, c, kind):
    """An expert layer on one sequence x (T, d)."""
    eps = c["norm_eps"]
    x = x + mixer(_rms_norm(x, p["attn_norm"], eps), p, c, kind)
    out, margin, bad = experts(_rms_norm(x, p["mlp_norm"], eps), p, taken, c)
    return x + out, margin, bad


def layer_weights(params, c):
    """Each run layer's weights out of the program's tree, in order: a
    leading layer's own block, else the layer's slice of `blocks` with its
    kind's slice of `kinds`."""
    lead = n_lead(c)
    seen = {}
    for i, kind in enumerate(_KINDS[k] for k in layer_types(c)):
        if i < lead:
            yield params["lead"][i]
            continue
        rank = seen.get(kind, 0)
        seen[kind] = rank + 1
        yield {**{n: a[i - lead] for n, a in params["blocks"].items()},
               **{n: a[rank] for n, a in params["kinds"][kind].items()}}


_HEAD_BLOCKS = 8


def _head_block(x, rows, bad):
    return jnp.where(bad[:, None], jnp.nan, x) @ rows.astype(F32).T


def _head(x, embed, bad, jit):
    """x (T, d) E^T -> (T, V) float32 **on the host**, a block of the
    embedding's rows at a time (families/glm4moelite.py says why: the
    lanes' logits on the device stood beside the engine's pool and
    parameters).  A position marked `bad` gets NaN throughout."""
    vocab = embed.shape[0]
    n = _HEAD_BLOCKS if vocab % _HEAD_BLOCKS == 0 else 1
    rows = vocab // n
    block = jit(_head_block)
    out = np.empty((x.shape[0], vocab), np.float32)
    for i in range(n):
        out[:, i * rows:(i + 1) * rows] = block(
            x, embed[i * rows:(i + 1) * rows], bad)
    return out


def _key(tokens) -> bytes:
    return np.asarray(tokens).astype(np.int32).tobytes()


def forward(params, tokens, c, jit=lambda f: f, routing="handed"):
    """tokens (T,) int32 -> (logits (T, V) float32 on the host, margin
    (T,)), one sequence; `margin` is each position's smallest over the
    expert layers.  `routing`: "handed" takes what `score` left for these
    tokens (its own top-k where nothing was left), None the reference's
    own, an array (T, expert layers, k) that.  Parameters are cast to
    float32 a layer at a time, at their use, and the head an eighth of
    the vocabulary at a time (`_head`).  `jit=jax.jit` compiles each kind
    of layer once and runs it per layer."""
    if isinstance(routing, str):
        routing = _HANDED.get(_key(tokens))
    run, lead = [_KINDS[k] for k in layer_types(c)], n_lead(c)
    dense_fn = {k: jit(functools.partial(dense_block, c=c, kind=k))
                for k in set(run[:lead])}
    expert_fn = {k: jit(functools.partial(expert_block, c=c, kind=k))
                 for k in set(run[lead:])}
    x = params["embed"][tokens].astype(F32)
    margin = jnp.full(x.shape[:1], jnp.inf, F32)
    bad = jnp.zeros(x.shape[:1], bool)
    if routing is not None and routing.shape != (
            x.shape[0], len(run) - lead, c["num_experts_per_tok"]):
        routing, bad = None, ~bad         # not a routing of this model
    for i, (p, kind) in enumerate(zip(layer_weights(params, c), run)):
        if i < lead:
            x = dense_fn[kind](x, p)
            continue
        x, m, b = expert_fn[kind](
            x, p, None if routing is None
            else jnp.asarray(routing[:, i - lead]))
        margin, bad = jnp.minimum(margin, m), bad | b
    x = jit(functools.partial(_rms_norm, eps=c["norm_eps"]))(
        x, params["final_norm"])
    return _head(x, params["embed"], bad, jit), margin


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
    (the launches an idle engine's tick would use, each continuing the
    slot's conv rows and leaving them in the slot) and teacher-forced steps
    through the function its burst scans, both compiled to hand out the
    experts they took, which are kept for `forward` under each lane's
    token ids."""
    got, taken = e.score(seqs, n_prompt, routing=True)
    _HANDED.clear()
    for lane, route in enumerate(taken):
        _HANDED[_key(seqs[lane])] = np.asarray(route)
    return got


# What bench/tools/controls.py prints beside a verdict: nothing this
# reference reads of its own.
LAST: dict = {}


def control(fault: str, cfg):
    """For bench/tools/controls.py: (the program configuration, a function
    that undoes the patch) of `sound`, and of `cache_fp8`: what a sequence
    keeps in the stated `cache_dtype` (the full layers' K and V, the conv
    layers' rows) rounded to float8_e4m3fn, the nearest precision below it,
    after every launch and step of the scoring entry, by eager ops (inside
    one jit the TPU compiler drops the pair of converts:
    families/laguna.py).  The readings are beside `TOLERANCES`."""
    import dataclasses

    if fault == "sound":
        return cfg, lambda: None
    if fault != "cache_fp8":
        raise SystemExit(f"no fault {fault!r}")
    from ray_tpu.serve.llm import PagedLLMEngine

    inner = PagedLLMEngine.score

    def rounded(cache):
        def fp8(a):
            return a.astype(jnp.float8_e4m3fn).astype(a.dtype)

        return dataclasses.replace(cache, k=fp8(cache.k), v=fp8(cache.v),
                                   lconv=fp8(cache.lconv))

    def score(e, seqs, n_prompt, **kw):
        if not getattr(e, "_rounds_cache", False):
            inner(e, np.ones((1, 9), np.int64), 8, **kw)    # builds them
            for name in ("_score_chunk", "_score_step"):
                program = getattr(e, name)

                def keeping(*a, _program=program, **k):
                    cache, *rest = _program(*a, **k)
                    return (rounded(cache), *rest)

                setattr(e, name, keeping)
            e._rounds_cache = True
        return inner(e, seqs, n_prompt, **kw)

    PagedLLMEngine.score = score

    def undo():
        PagedLLMEngine.score = inner

    return cfg, undo


# ---------------------------------------------------------------------------
# Operations and bytes a step needs, from shapes alone: what the
# algorithm requires, not what the program happens to execute.
# ---------------------------------------------------------------------------
def _dims(c: dict) -> dict:
    run, lead = [_KINDS[k] for k in layer_types(c)], n_lead(c)
    h = c["num_attention_heads"]
    hd = c["hidden_size"] // h
    return {"d": c["hidden_size"], "v": c["vocab_size"], "q": h * hd,
            "kv": c["num_key_value_heads"] * hd, "j": c["conv_L_cache"],
            "f": c["moe_intermediate_size"], "fd": c["intermediate_size"],
            "e": c["num_experts"], "k": c["num_experts_per_tok"],
            "n": len(run), "lead": lead, "full": run.count("full"),
            "conv": run.count("conv"),
            "conv_behind": run[lead:].count("conv")}


def _itemsize(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]


def matrix_params(c: dict) -> dict:
    """Matrix parameters of the run layers' parts."""
    s = _dims(c)
    d = s["d"]
    parts = {"full": 2 * d * s["q"] + 2 * d * s["kv"],
             "conv": 4 * d * d + s["j"] * d,
             "dense_ffn": 3 * d * s["fd"], "router": d * s["e"],
             "expert": 3 * d * s["f"]}
    # every weight outside the routed experts that a step reads once: the
    # tied head (the embedding read as the head; the lookup is a gather)
    parts["dense"] = s["full"] * parts["full"] + s["conv"] * parts["conv"] \
        + s["lead"] * parts["dense_ffn"] \
        + (s["n"] - s["lead"]) * parts["router"] + s["v"] * d
    parts["total"] = parts["dense"] \
        + (s["n"] - s["lead"]) * s["e"] * parts["expert"]
    return parts


def expected_held_experts(c: dict, rows: float) -> float:
    """Distinct experts that `rows` tokens take in one layer under uniform
    routing: E x (1 - (1 - k/E)^rows).  (4 of 32 for one row, 21.0 for
    eight, 31.6 for thirty-two.)"""
    s = _dims(c)
    return s["e"] * (1.0 - (1.0 - s["k"] / s["e"]) ** rows)


def expert_bytes_per_step(c: dict, lanes: int) -> float:
    """Bytes of expert weights one decode step of `lanes` tokens needs:
    the experts taken in every expert layer, each once."""
    s = _dims(c)
    return (s["n"] - s["lead"]) * expected_held_experts(c, lanes) \
        * matrix_params(c)["expert"] * _itemsize(c["param_dtype"])


def expert_operand(c: dict):
    """What an op that reads a layer's expert weights shows in its HLO
    text: an operand shaped [E,d,f] or [E,f,d] (after the layers' axis,
    where the stacks are whole), as a compiled pattern."""
    s = _dims(c)
    return re.compile(rf"\[(?:\d+,)?{s['e']},(?:{s['d']},{s['f']}|"
                      rf"{s['f']},{s['d']})\]")


def conv_bytes_per_step(c: dict, lanes: int) -> float:
    """Bytes the conv mixers of one decode step of `lanes` tokens must
    move, as far as `mixer_operand` sees their ops: every conv layer's
    W_in and kernel once, W_out of the conv layers behind the leading ones
    (a leading layer's own W_out is a bare [d, d] that an attention
    layer's projections are too, so its op is not read and its bytes are
    not counted), and the lanes' J - 1 kept rows read and written once
    (the cache dtype)."""
    s = _dims(c)
    rows = 2.0 * (s["j"] - 1) * s["d"] * lanes * _itemsize(
        c.get("cache_dtype", c["compute_dtype"]))
    unseen = (s["conv"] - s["conv_behind"]) * s["d"] * s["d"]
    return (s["conv"] * matrix_params(c)["conv"] - unseen) \
        * _itemsize(c["param_dtype"]) + s["conv"] * rows


def mixer_operand(c: dict):
    """What an op of a conv layer's mixer shows in its HLO text: the
    slots' kept rows ([conv layers, slots, J - 1, d]) or the lanes' ([..,
    J - 1 or J, d]), a lane's three chunks ([.., 3 d]), or one of the conv
    layers' projections, stacked behind the leading layers ([layers, d, 3
    d] in, [layers, d, d] out) or a leading layer's own ([d, 3 d])."""
    s = _dims(c)
    d, j, n = s["d"], s["j"], s["conv_behind"]
    return re.compile(
        rf"\[(?:\d+,)+(?:{j - 1}|{j}),{d}\]"
        rf"|\[(?:\d+,)*{3 * d}\]"
        rf"|\[{n},{d},{d}\]")


def _kv_row_bytes(c: dict) -> int:
    return 2 * _dims(c)["kv"] * _itemsize(
        c.get("cache_dtype", c["compute_dtype"]))


def decode_step_bytes(c: dict, live_kv_tokens: float, lanes: int) -> float:
    """Bytes one decode step of `lanes` tokens must move: every weight
    outside the routed experts once (the tied head once; the lookup is a
    gather), of the experts those the lanes are expected to take, the full
    layers' KV of the live positions, and the conv layers' kept rows read
    and written."""
    s = _dims(c)
    rows = 2.0 * s["conv"] * (s["j"] - 1) * s["d"] * lanes * _itemsize(
        c.get("cache_dtype", c["compute_dtype"]))
    return matrix_params(c)["dense"] * _itemsize(c["param_dtype"]) \
        + expert_bytes_per_step(c, lanes) \
        + s["full"] * _kv_row_bytes(c) * live_kv_tokens + rows


def prefill_flops(c: dict, tokens: float, context: float) -> float:
    """FLOPs that `tokens` prompt tokens need which together attend over
    `context` positions (a token at position p attends p + 1): the layers'
    matrices with the k experts a token takes, the convolution's J
    products a channel and its two gates, attention scores and values
    over the context in the full layers.  The head, once a prompt, is
    left out."""
    s, m = _dims(c), matrix_params(c)
    dense = m["dense"] - s["v"] * s["d"]
    routed = (s["n"] - s["lead"]) * s["k"] * m["expert"]
    return 2.0 * (dense + routed) * tokens \
        + 2.0 * s["conv"] * (s["j"] + 2) * s["d"] * tokens \
        + 4.0 * s["full"] * s["q"] * context


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
