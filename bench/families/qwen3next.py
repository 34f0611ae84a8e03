"""The qwen3next family: everything the harness knows of
Qwen3-Next-80B-A3B-Instruct (`model_type: qwen3_next`): a decoder in which
three of every four layers keep no KV but a matrix of state a head under a
gated delta rule (Gated DeltaNet, arXiv:2412.06464), the fourth is a
soft-max layer whose output is gated element by element, and every layer's
FFN is many small routed experts beside one shared expert under a gate of
its own.  A configuration file says `"family": "qwen3next"`; what the
harness asks of a family is listed at the top of families/mistral.py.  This
one also gives `state_operand` / `state_bytes_per_step` (for
`ssm_state_roofline`), `scan_operand` / `scan_flops_per_chunk` /
`scan_bytes_per_chunk` (for `gdn_chunk_roofline`), `mixer_operand` (for
`gdn_step_share.decode`), `routed_choices_per_row` (for
`moe_routed_here_share.decode`) and `TOLERANCES`, with its measurements
beside it.

The model, for layer `l` of `num_hidden_layers`, eps `rms_norm_eps`, no
biases, untied embedding and head:

    x = E[token]
    x += Mixer_l(N(x))
    x += MoE_l(N'(x))
    logits = N_f(x) W_head            N(u) = u / rms(u) * (1 + w), float32

Layer `l` is full where (l + 1) % full_attention_interval == 0, else linear.

  full    u the normed input, H = num_attention_heads, hd = head_dim, Hkv =
          num_key_value_heads:  q = u Wq, gate = u Wg (H x hd each: the
          published q_proj holds both), k = u Wk, v = u Wv (Hkv x hd);
          q = N_q(q), k = N_k(k) over a head (gain 1 + w);  rope on the
          first r = partial_rotary_factor x hd dimensions of a head, pair i
          with i + r / 2 at theta^(-2i / r), the rest passed through;
          causal softmax(q k^T / sqrt(hd)) v, query head j reading KV head
          j // (H / Hkv);  the output times sigmoid(gate), element by
          element;  Wo.
  linear  Hk = linear_num_key_heads, Hv = linear_num_value_heads, dk =
          linear_key_head_dim, dv = linear_value_head_dim:
          [q | k | v | z] = u W_qkvz (Hk dk, Hk dk, Hv dv, Hv dv),
          [b | a] = u W_ba (Hv, Hv).  [q | k | v] through a causal depth-wise
          convolution of linear_conv_kernel_dim rows (no bias), then SiLU.
          beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias), float32.
          q, k L2-normalised over a head (eps 1e-6), q scaled by 1 /
          sqrt(dk); key head i serves value heads i Hv / Hk .. (i + 1) Hv /
          Hk - 1.  A head and position t, S (dk x dv) float32, zero at the
          sequence's start:
              S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);
              S <- S + k_t d^T;  o_t = S^T q_t
          y = w_o * o / rms(o) * silu(z) over a head's dv (plain gain);
          W_out.
  MoE     p = softmax(u W_r) over all published experts in float32; the
          num_experts_per_tok largest are taken, their p renormalised to
          sum 1 (`norm_topk_prob`); each expert a SwiGLU at width
          moe_intermediate_size.  **This chip holds `num_experts` of them,
          from `first_local_expert`**: the sum runs over the held experts a
          token took and the rest of its experts is left out, in the
          program and here alike (model-configs guide, section 4).  Beside
          them sigmoid(u w_s) x a SwiGLU at width
          shared_expert_intermediate_size that every token takes, added
          once.

The reference below is those equations in plain `jax.numpy` float32,
independent of `ray_tpu/`: no kernels, no cache, no chunks: the recurrence
runs a position at a time (`lax.scan` over positions), attention with an
explicit mask in blocks of queries, every held expert evaluated on every
token and weighted (zero where not taken).  It shares only the parameter
tree's layout, which is data:

    embed (V,d)  lm_head (d,V)  final_norm (d,)
    blocks.*, stacked over the layers: attn_norm, mlp_norm (.,d)  router
        (.,d,E published)  w_gate, w_up (.,E held,d,f)  w_down (.,E held,f,d)
        shared_gate, shared_up (.,d,fs)  shared_down (.,fs,d)  shared_scale
        (.,d,1)
    kinds.full.*, stacked over the full layers: wq, head_gate (.,d,H*hd)
        wk, wv (.,d,Hkv*hd)  wo (.,H*hd,d)  q_norm, k_norm (.,hd)
    kinds.linear.*, stacked over the linear layers: in_qkvz (.,d,2 Hk dk +
        2 Hv dv)  in_ba (.,d,2 Hv)  conv_w (.,J,2 Hk dk + Hv dv)  A_log,
        dt_bias (.,Hv) float32  gate_norm (.,dv)  out_proj (.,Hv dv,d)

Callers run it under `jax.default_matmul_precision("highest")`.

**Routing is handed over**, as in families/laguna.py and for its reason
(10 of 512 taken: the reference's own gap between the last expert taken and
the first left out is under the program's rounding at nearly every
position).  `score` asks the engine's scoring entry for the experts the
program took and keeps them under the lane's token ids; `forward` takes
them, computes their gates itself from its own float32 scores, and holds
the program's choice to ROUTER_SLACK on its own scores.

Assumed (the configuration file lists each under `assumed`): W_qkvz's and
W_ba's columns are laid out [q | k | v | z] and [b | a] (the release
interleaves them by key head: a permutation under seeded weights); the rope
pairs dimension i with i + r / 2; `A_log` / `dt_bias` are drawn so that a
step's decay exp(g) lies mostly in (0.2, 1) with heads that remember over
hundreds of positions (the published initialisation decays a state to
nothing within a few positions under seeded projections, and a check on
such a model could not see a lost hand-off); the multi-token-prediction
module is not served.
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
# this family.  Every compared position is decided by handed-over routing,
# so LOGITS_REL_EXPERTS holds all 34 of a run (`check`: 2 lanes x (the last
# of 6,144 prompt positions, prefilled in twelve launches of 512 rows, each
# eight chunks of the rule with the state handed from launch to launch
# through the slot, + 16 decode steps of the rule's step form), at the timed
# lengths).  Measured on the chip at published widths, 8 layers, 128 of 512
# experts (my chip runs, PR 64; PERF.md section 7 has the table).
#
# LOGITS_REL_EXPERTS: rms error of a position's logits as a share of the
# reference's own.  ROUTER_SLACK: how far the program's set of experts may
# stray from the reference's, as a share of the spread (standard deviation
# over the 512 experts) of the token's scores.
#   The program as it is, 4 seeds x 34 positions x 8 layers (call A's run
#   of the cell and call B's three bare checks): a position's error has
#   medians 0.0296-0.0313 and a largest a seed of 0.0316-0.0389; it strays
#   by at most 0.310-0.354 a seed (medians 0.082-0.105: with 10 of 512
#   taken by soft-max scores the tenth and the eleventh probability lie
#   closer than bfloat16 rounds the router's input at most positions, and
#   the spread of 512 probabilities is small).
#   **What a sequence keeps in `cache_dtype` kept in 8-bit floats**
#   (`control("cache_fp8")`: the full layers' K and V and the linear
#   layers' conv rows through float8_e4m3fn, the nearest precision below
#   the stated bfloat16, by eager ops after every launch and step; two
#   seeds, call B): error medians 0.0855-0.0892, largest 0.108-0.112;
#   strays to 1.04-1.13 (medians 0.46-0.55, 4-6 of 34 positions beyond
#   0.6).  0.06 lies between 0.0389 and the control's medians 0.0855 (its
#   largest 0.108) with a factor of 1.5 below and 1.4 above (1.8 to the
#   largest); 0.6 lies between 0.354 and 1.04, a factor of 1.7 either way.
#   The control is refused by both limits.  Nine further runs of the cell
#   with the limits set (calls C and D, a seed each, seven of them from
#   the final tree's archive) read `correct` true.
#   **A slot's state kept in bfloat16** (`control("state_bf16")`, the
#   nearest precision below the stated float32 `state_dtype`; two seeds,
#   call B) reads as the program as it is: error medians 0.0313-0.0325,
#   largest 0.0377-0.0394, strays to 0.196-0.290.  At these widths this
#   check cannot see it: a state's rounding is 2^-9 of entries that the
#   decay halves within a few positions on most heads, under activations
#   that are bfloat16 already.  tests/test_gated_delta_serving.py refuses
#   it in float32 at a tiny size (errors of 6e-3 to 1.3e-2 of the logits'
#   rms against 7e-5 as it is), with the other faults of the mechanism:
#   beta dropped, the decay dropped, the gate norm without silu(z), a
#   state or the conv rows not handed from one launch to the next.
#   **What it cannot see besides:** a layer computed in bfloat16 where the
#   configuration says bfloat16; a router wrong by less than ROUTER_SLACK
#   everywhere, which is what rounding does and a fault rarely.
TOLERANCES = {"LOGITS_REL_EXPERTS": 0.06, "ROUTER_SLACK": 0.6}

# What `score` handed over: {a lane's token ids (int32 bytes): (T, L, k)}.
_HANDED: dict = {}


# ---------------------------------------------------------------------------
# configuration file -> the program
# ---------------------------------------------------------------------------
def kinds(config: dict) -> list:
    """The kind of every run layer: "full" where (l + 1) is a multiple of
    `full_attention_interval`, else "linear"."""
    every = config["full_attention_interval"]
    return ["full" if (l + 1) % every == 0 else "linear"
            for l in range(config["num_hidden_layers"])]


def published_experts(config: dict) -> int:
    """The router's width: the published count of routed experts, of
    which `num_experts` are held here."""
    return int(config.get("published", {}).get(
        "num_experts", config["num_experts"]))


def held_range(config: dict):
    """(first, count) of the published experts that this chip holds."""
    return int(config.get("first_local_expert", 0)), \
        int(config["num_experts"])


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

    needs = {"linear_k_heads", "linear_v_heads", "linear_d_k", "linear_d_v",
             "linear_conv", "linear_chunk", "norm_plus_one", "shared_gate"}
    lacks = needs - {f.name for f in dataclasses.fields(TransformerConfig)}
    if lacks:
        _withdraw_app()
        raise SpecError(
            f"this program's TransformerConfig has no {sorted(lacks)}: it "
            f"cannot run a configuration of the qwen3next family")
    for key, want in (("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("norm_topk_prob", True), ("decoder_sparse_step", 1),
                      ("mlp_only_layers", []), ("rope_scaling", None),
                      ("use_sliding_window", False)):
        if config[key] != want:
            raise SpecError(f"{key} = {config[key]!r}: the program's layers "
                            f"are {key} = {want!r}")
    every = config["full_attention_interval"]
    if config["num_hidden_layers"] % every:
        raise SpecError(f"num_hidden_layers {config['num_hidden_layers']} is "
                        f"not whole periods of {every} layers")
    hd = config["head_dim"]
    first, count = held_range(config)
    e = published_experts(config)
    return TransformerConfig(
        name=config["name"],
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_head=hd,
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["shared_expert_intermediate_size"],
        n_experts=e,
        expert_top_k=config["num_experts_per_tok"],
        experts_held=None if count == e else (first, count),
        qk_norm=True,
        attn_gate=hd,
        shared_gate=True,
        norm_plus_one=True,
        layer_pattern=tuple(kinds(config)[:every]),
        linear_k_heads=config["linear_num_key_heads"],
        linear_v_heads=config["linear_num_value_heads"],
        linear_d_k=config["linear_key_head_dim"],
        linear_d_v=config["linear_value_head_dim"],
        linear_conv=config["linear_conv_kernel_dim"],
        linear_chunk=int(config["assumed"]["linear_chunk"]),
        linear_state_dtype=jnp.dtype(config.get("state_dtype", "float32")),
        rope_theta=float(config["rope_theta"]),
        rotary_dim=int(config["partial_rotary_factor"] * hd),
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
def _rms_norm(x, gain, eps, plus=1.0):
    """x / rms(x) * (plus + gain): the stack's norms add 1 to a gain stored
    about 0; the gated norm of a linear layer (`plus` 0) does not."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (plus + gain.astype(F32))


def _rope(x, c):
    """x (T, heads, hd): rotate pairs (i, i + r/2) of the first r
    dimensions, the rest as they are."""
    t = x.shape[0]
    r = int(c["partial_rotary_factor"] * c["head_dim"])
    inv = float(c["rope_theta"]) ** (-jnp.arange(r // 2, dtype=F32) * 2.0 / r)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., r:]], -1)


_QUERY_BLOCK = 512


def attention(u, p, c):
    """The gated soft-max layer's mixer over the normed input u (T, d),
    queries _QUERY_BLOCK at a time against the whole context."""
    t = u.shape[0]
    h, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    eps = c["rms_norm_eps"]
    q = (u @ p["wq"].astype(F32)).reshape(t, h, hd)
    k = (u @ p["wk"].astype(F32)).reshape(t, hkv, hd)
    v = (u @ p["wv"].astype(F32)).reshape(t, hkv, hd)
    q = _rope(_rms_norm(q, p["q_norm"], eps), c)
    k = _rope(_rms_norm(k, p["k_norm"], eps), c)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    out = []
    for lo in range(0, t, _QUERY_BLOCK):
        hi = min(lo + _QUERY_BLOCK, t)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k) / jnp.sqrt(F32(hd))
        seen = jnp.arange(t)[None, :] <= jnp.arange(lo, hi)[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", prob, v))
    gate = jax.nn.sigmoid(u @ p["head_gate"].astype(F32))     # (T, H hd)
    out = jnp.concatenate(out, 0).reshape(t, h * hd) * gate
    return out @ p["wo"].astype(F32)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule a position at a time, from a zero state.  q, k
    (T, H, dk), v (T, H, dv), g, beta (T, H).  Returns o (T, H, dv)."""
    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[:, None, None] * state
        delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * delta[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(step, zero, (q, k, v, g, beta))[1]


def linear_mixer(u, p, c):
    """The Gated DeltaNet mixer over the normed input u (T, d)."""
    t = u.shape[0]
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    width = c["linear_conv_kernel_dim"]
    n_conv = 2 * hk * dk + hv * dv
    qkvz = u @ p["in_qkvz"].astype(F32)
    ba = u @ p["in_ba"].astype(F32)
    qkv, z = qkvz[:, :n_conv], qkvz[:, n_conv:].reshape(t, hv, dv)
    padded = jnp.concatenate([jnp.zeros((width - 1, n_conv), F32), qkv], 0)
    w = p["conv_w"].astype(F32)
    qkv = jax.nn.silu(sum(w[j] * padded[j:j + t] for j in range(width)))

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = unit(qkv[:, :hk * dk].reshape(t, hk, dk)) / jnp.sqrt(F32(dk))
    k = unit(qkv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk))
    v = qkv[:, 2 * hk * dk:].reshape(t, hv, dv)
    q, k = jnp.repeat(q, hv // hk, axis=1), jnp.repeat(k, hv // hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"].astype(F32)) * jax.nn.softplus(
        ba[:, hv:] + p["dt_bias"].astype(F32))
    o = delta_rule(q, k, v, g, beta)
    y = _rms_norm(o, p["gate_norm"], c["rms_norm_eps"], plus=0.0) \
        * jax.nn.silu(z)
    return y.reshape(t, hv * dv) @ p["out_proj"].astype(F32)


def swiglu(u, p, prefix="w_"):
    return (jax.nn.silu(u @ p[prefix + "gate"].astype(F32))
            * (u @ p[prefix + "up"].astype(F32))) \
        @ p[prefix + "down"].astype(F32)


def shared(u, p):
    """The shared expert under its gate, one value a token."""
    return jax.nn.sigmoid(u @ p["shared_scale"].astype(F32)) \
        * swiglu(u, p, "shared_")


def scores(u, p, c):
    """The router's score of every published expert, (T, E)."""
    return jax.nn.softmax(u @ p["router"].astype(F32), axis=-1)


def experts(u, p, taken, c):
    """The routed experts held here over u (T, d).  `taken` (T, k) int32:
    the experts the program took (None: the reference's own top-k).
    Returns (this chip's part of the routed sum, margin (T,), bad (T,)
    bool), as families/laguna.py's `experts` and in its units."""
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
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(taken, e, dtype=F32) * gates[..., None],
                     axis=1)[:, first:first + count]             # (T, held)

    def one(acc, ex):
        gate, up, down, w = ex
        hidden = jax.nn.silu(u @ gate.astype(F32)) * (u @ up.astype(F32))
        return acc + w[:, None] * (hidden @ down.astype(F32)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        p["w_gate"], p["w_up"], p["w_down"], weight.T))
    return out, margin, bad


def block(x, p, taken, c, kind):
    """A layer of `kind` on one sequence x (T, d)."""
    eps = c["rms_norm_eps"]
    u = _rms_norm(x, p["attn_norm"], eps)
    x = x + (attention(u, p, c) if kind == "full" else linear_mixer(u, p, c))
    u = _rms_norm(x, p["mlp_norm"], eps)
    out, margin, bad = experts(u, p, taken, c)
    return x + out + shared(u, p), margin, bad


def layer_weights(params, c):
    """Each run layer's weights out of the program's tree, in order: the
    layer's slice of `blocks` with its kind's slice of `kinds`."""
    seen = {}
    for i, kind in enumerate(kinds(c)):
        rank = seen.get(kind, 0)
        seen[kind] = rank + 1
        yield {**{n: a[i] for n, a in params["blocks"].items()},
               **{n: a[rank] for n, a in params["kinds"][kind].items()}}


_HEAD_BLOCKS = 8


def _head_block(x, part, bad):
    return jnp.where(bad[:, None], jnp.nan, x) @ part.astype(F32)


def _head(x, w, bad, jit):
    """x (T, d) W_head -> (T, V) float32 **on the host**, a block of the
    head's columns at a time (families/glm4moelite.py says why).  A
    position marked `bad` gets NaN throughout."""
    vocab = w.shape[1]
    n = _HEAD_BLOCKS if vocab % _HEAD_BLOCKS == 0 else 1
    cols = vocab // n
    head_block = jit(_head_block)
    out = np.empty((x.shape[0], vocab), np.float32)
    for i in range(n):
        out[:, i * cols:(i + 1) * cols] = head_block(
            x, w[:, i * cols:(i + 1) * cols], bad)
    return out


def _key(tokens) -> bytes:
    return np.asarray(tokens).astype(np.int32).tobytes()


def forward(params, tokens, c, jit=lambda f: f, routing="handed"):
    """tokens (T,) int32 -> (logits (T, V) float32 on the host, margin
    (T,)), one sequence; `margin` is each position's smallest over the
    layers.  `routing`: "handed" takes what `score` left for these tokens
    (its own top-k where nothing was left), None the reference's own, an
    array (T, layers, k) that.  Parameters are cast to float32 a layer at a
    time, at their use, and the output head an eighth of the vocabulary at
    a time (`_head`).  `jit=jax.jit` compiles each kind of layer once and
    runs it per layer."""
    if isinstance(routing, str):
        routing = _HANDED.get(_key(tokens))
    run = kinds(c)
    fns = {k: jit(functools.partial(block, c=c, kind=k)) for k in set(run)}
    x = params["embed"][tokens].astype(F32)
    margin = jnp.full(x.shape[:1], jnp.inf, F32)
    bad = jnp.zeros(x.shape[:1], bool)
    if routing is not None and routing.shape != (
            x.shape[0], len(run), c["num_experts_per_tok"]):
        routing, bad = None, ~bad         # not a routing of this model
    for i, (p, kind) in enumerate(zip(layer_weights(params, c), run)):
        x, m, b = fns[kind](x, p, None if routing is None
                            else jnp.asarray(routing[:, i]))
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
    (the launches an idle engine's tick would use, each carrying the
    slot's state through its chunks of the rule and leaving it in the
    slot) and teacher-forced steps through the function its burst scans,
    both compiled to hand out the experts they took, which are kept for
    `forward` under each lane's token ids."""
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
    that undoes the patch) of `sound`; `state_bf16`, a slot's state kept in
    bfloat16, the nearest precision below the stated `state_dtype`; and
    `cache_fp8`, what a sequence keeps in the stated `cache_dtype` (the full
    layers' K and V, the linear layers' conv rows) rounded to float8_e4m3fn
    after every launch and step of the scoring entry, by eager ops (inside
    one jit the TPU compiler drops the pair of converts:
    families/laguna.py).  The readings are beside `TOLERANCES`."""
    import dataclasses

    if fault == "sound":
        return cfg, lambda: None
    if fault == "state_bf16":
        return dataclasses.replace(
            cfg, linear_state_dtype=jnp.dtype("bfloat16")), lambda: None
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
    run = kinds(c)
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    return {"d": c["hidden_size"], "v": c["vocab_size"],
            "q": c["num_attention_heads"] * c["head_dim"],
            "kv": c["num_key_value_heads"] * c["head_dim"],
            "hk": hk, "hv": hv, "dk": dk, "dv": dv,
            "conv": 2 * hk * dk + hv * dv, "inner": hv * dv,
            "width": c["linear_conv_kernel_dim"],
            "chunk": int(c["assumed"]["linear_chunk"]),
            "f": c["moe_intermediate_size"],
            "fs": c["shared_expert_intermediate_size"],
            "e": published_experts(c), "held": held_range(c)[1],
            "k": c["num_experts_per_tok"], "n": len(run),
            "full": run.count("full"), "linear": run.count("linear")}


def _itemsize(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]


def matrix_params(c: dict) -> dict:
    """Matrix parameters of the run layers' parts, and of what is held
    here."""
    s = _dims(c)
    d = s["d"]
    parts = {"full": 3 * d * s["q"] + 2 * d * s["kv"],
             "linear": d * (s["conv"] + s["inner"] + 2 * s["hv"])
             + s["inner"] * d + s["width"] * s["conv"],
             "shared": 3 * d * s["fs"] + d, "router": d * s["e"],
             "expert": 3 * d * s["f"]}
    # every weight outside the routed experts that a step reads once: the
    # head, not the embedding (a gather of the step's rows)
    parts["dense"] = s["full"] * parts["full"] + s["linear"] * parts["linear"] \
        + s["n"] * (parts["shared"] + parts["router"]) + s["v"] * d
    parts["total"] = parts["dense"] + s["v"] * d \
        + s["n"] * s["held"] * parts["expert"]
    return parts


def expected_held_experts(c: dict, rows: float) -> float:
    """Distinct held experts that `rows` tokens take in one layer under
    uniform routing: held x (1 - (1 - k/E)^rows).  (2.5 of 128 for one
    row, 18.7 for eight, all 128 from some 300 rows on.)"""
    s = _dims(c)
    return s["held"] * (1.0 - (1.0 - s["k"] / s["e"]) ** rows)


def expert_bytes_per_step(c: dict, lanes: int) -> float:
    """Bytes of expert weights one decode step of `lanes` tokens needs:
    the held experts taken in every layer, each once."""
    s = _dims(c)
    return s["n"] * expected_held_experts(c, lanes) \
        * matrix_params(c)["expert"] * _itemsize(c["param_dtype"])


def routed_choices_per_row(c: dict) -> int:
    """Top-k choices one row makes through the stack: k in every layer
    (of which held / E are expected to fall here)."""
    s = _dims(c)
    return s["n"] * s["k"]


def expert_operand(c: dict):
    """What an op that reads a layer's held expert weights shows in its
    HLO text: an operand shaped [held,d,f] or [held,f,d] (after the
    layers' axis, where the stacks are whole), as a compiled pattern."""
    s = _dims(c)
    return re.compile(rf"\[(?:\d+,)?{s['held']},(?:{s['d']},{s['f']}|"
                      rf"{s['f']},{s['d']})\]")


def _state_bytes_per_lane(c: dict) -> float:
    """One linear layer's state of one lane: S a value head (the state
    dtype) and the conv rows kept (the cache dtype)."""
    s = _dims(c)
    return s["hv"] * s["dk"] * s["dv"] * _itemsize(
        c.get("state_dtype", "float32")) \
        + (s["width"] - 1) * s["conv"] * _itemsize(
            c.get("cache_dtype", c["compute_dtype"]))


def state_bytes_per_step(c: dict, lanes: int) -> float:
    """Recurrent state one decode step of `lanes` tokens must read and
    write: every linear layer's state and conv rows, once each way."""
    return 2.0 * _dims(c)["linear"] * _state_bytes_per_lane(c) * lanes


def state_rows_per_step(c: dict, lanes: int) -> int:
    """State rows (a lane's state in one linear layer) a decode step of
    `lanes` tokens reads, and writes as many."""
    return _dims(c)["linear"] * lanes


def state_operand(c: dict):
    """What an op of a step that reads or writes the lanes' recurrent
    state shows in its HLO text: a float32 array [lanes, Hv, dk, dv]."""
    s = _dims(c)
    return re.compile(rf"f32\[\d+,{s['hv']},{s['dk']},{s['dv']}\]")


def mixer_operand(c: dict):
    """What an op of a linear layer's mixer shows in its HLO text: the
    lanes' state (`state_operand`), the slots' state and conv rows, the
    rows of the convolution ([.., J - 1 or J or more, 2 Hk dk + Hv dv]) or
    one of the linear layers' stacked projections ([linear layers, d, ..]
    in, [linear layers, Hv dv, d] out)."""
    s = _dims(c)
    n, d = s["linear"], s["d"]
    return re.compile(
        rf"\[(?:\d+,)*{s['hv']},{s['dk']},{s['dv']}\]"
        rf"|\[(?:\d+,)*\d+,{s['conv']}\]"
        rf"|\[{n},{d},(?:{s['conv'] + s['inner']}|{2 * s['hv']})\]"
        rf"|\[{n},{s['inner']},{d}\]")


def scan_flops_per_chunk(c: dict, tokens: float) -> float:
    """FLOPs of the chunked rule over one launch of `tokens` positions,
    every linear layer and value head, in chunks of C = `linear_chunk`:
    K K^T and Q K^T (C x C x dk each), the triangular system applied to
    [K | V] (C x C x (dk + dv), as a product with its inverse), W S_0, Q
    S_0 and the state's update (C x dk x dv each), and the corrected
    values' product (C x C x dv); the causal half is not discounted."""
    s = _dims(c)
    q, dk, dv = s["chunk"], s["dk"], s["dv"]
    per_chunk = 2.0 * (2 * q * q * dk + q * q * (dk + dv) + 3 * q * dk * dv
                       + q * q * dv)
    return s["linear"] * s["hv"] * (tokens / q) * per_chunk


def scan_bytes_per_chunk(c: dict, tokens: float) -> float:
    """Bytes the chunked rule over one launch must move, every linear
    layer: the slot's state in and out, and q, k, v (the compute dtype),
    g and beta (float32) of the launch's positions in, o (float32) out."""
    s = _dims(c)
    act = _itemsize(c["compute_dtype"])
    per_pos = s["conv"] * act + 2 * 4 * s["hv"] + 4 * s["inner"]
    return s["linear"] * (2.0 * s["hv"] * s["dk"] * s["dv"] * _itemsize(
        c.get("state_dtype", "float32")) + tokens * per_pos)


def scan_operand(c: dict):
    """What an op of a launch's chunked rule shows in its HLO text: a
    state, trailing dimensions [Hv, dk, dv] in float32 or rounded for a
    product, or a chunk's C x C matrix a head (the decay, the system, the
    masked Q K^T), [.., Hv, C, C]."""
    s = _dims(c)
    return re.compile(
        rf"(?:f32|bf16)\[(?:\d+,)*{s['hv']},{s['dk']},{s['dv']}\]"
        rf"|\[(?:\d+,)*{s['hv']},{s['chunk']},{s['chunk']}\]")


def _kv_row_bytes(c: dict) -> int:
    return 2 * _dims(c)["kv"] * _itemsize(
        c.get("cache_dtype", c["compute_dtype"]))


def decode_step_bytes(c: dict, live_kv_tokens: float, lanes: int) -> float:
    """Bytes one decode step of `lanes` tokens must move: every weight
    outside the routed experts once (the head once; the embedding is a
    gather), of the held experts those the lanes are expected to take,
    the full layers' KV of the live positions, and the linear layers'
    state read and written."""
    s = _dims(c)
    return matrix_params(c)["dense"] * _itemsize(c["param_dtype"]) \
        + expert_bytes_per_step(c, lanes) \
        + s["full"] * _kv_row_bytes(c) * live_kv_tokens \
        + state_bytes_per_step(c, lanes)


def prefill_flops(c: dict, tokens: float, context: float) -> float:
    """FLOPs that `tokens` prompt tokens need which together attend over
    `context` positions (a token at position p attends p + 1): the layers'
    matrices with the held experts a token takes (k x held / E expected),
    the chunked rule in the linear layers, attention scores and values
    over the context in the full layers.  The output head, once a prompt,
    is left out."""
    s, m = _dims(c), matrix_params(c)
    dense = m["dense"] - s["v"] * s["d"]
    routed = s["n"] * s["k"] * s["held"] / s["e"] * m["expert"]
    return 2.0 * (dense + routed) * tokens \
        + scan_flops_per_chunk(c, tokens) \
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
