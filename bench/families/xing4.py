"""The xing4 family: everything the harness knows of Xing4.0-29B-A4B
(`model_type: xing4_0`, XingChen-AGI): multi-head latent attention (MLA,
arXiv:2405.04434) under YaRN in every layer, a dense SwiGLU in the leading
`first_k_dense_replace` layers and, in the rest, routed experts chosen by
biased sigmoid scores (`topk_method: noaux_tc`) beside a shared expert,
and **a residual of `hc_mult` streams a position, mixed around every
sub-block by manifold-constrained hyper-connections** (mHC,
arXiv:2512.24880, on Hyper-Connections, arXiv:2409.19606).  A
configuration file says `"family": "xing4"`; what the harness asks of a
family is listed at the top of families/mistral.py.  This one also gives
`hc_operand`, `hc_bytes_per_row`, `hc_stream_bytes`, `hc_phi_bytes` and
`hc_flops_per_row` (for `hc_roofline` and `hc_chunk_share`),
`expert_bytes_per_chunk` / `expert_flops_per_chunk` (for
`moe_chunk_roofline`) and `TOLERANCES`, with its measurements; it gives no
`decode_step_bytes` (no cell of it is judged on a decode step).

The model.  A position carries `X` in R^{n x d}, n = `hc_mult`.  For each
sub-block `F` (latent attention with its own pre-norm; the dense FFN, or
experts + shared expert, with theirs), with parameters of its own `Phi`
(n d, n^2 + 2n), `alpha_pre`, `alpha_post`, `alpha_res` (scalars), `b`
(n^2 + 2n), all float32:

    r        = RMSNorm(vec(X))                  n d values, eps hc_eps, no weight
    [p|q|R]  = r Phi                            n | n | n^2
    h_pre    = sigmoid(alpha_pre p + b_pre)     (n)
    h_post   = 2 sigmoid(alpha_post q + b_post) (n)
    M_0      = exp(clip(alpha_res mat(R) + b_res, mhc_h_res_clamp_min,
                        mhc_h_res_clamp_max))   (n x n)
    M_t      = rows(cols(M_{t-1})), t = 1 .. hc_sinkhorn_iters
               cols: each column / (its sum + hc_eps)
               rows: each row / (its sum + hc_eps)
    H_res    = M_20
    u        = h_pre X                          (d)   the sub-block's input
    X'       = H_res X + h_post^T F(u)          (n x d)

`X_0` is the token's embedding in each of the n streams; the final norm
and the untied head read the sum of the streams.

  MLA     H = num_attention_heads, u' = RMSNorm(u) the normed input:
          c_q = RMSNorm(u' W_qa) (q_lora_rank);  q = c_q W_qb, per head
          (q_n qk_nope_head_dim | q_r qk_rope_head_dim);
          [c | k_r] = u' W_kva (kv_lora_rank | qk_rope_head_dim);
          c = RMSNorm(c);  rope on every head's q_r and on the one k_r,
          theta `rope_theta`, **under YaRN** (`rope_scaling`: the pairs
          that turn fewer than beta_slow times over
          original_max_position_embeddings slowed by `factor`, those that
          turn more than beta_fast times kept, the rest blended; cos and
          sin x mscale(factor, mscale) / mscale(factor, mscale_all_dim) =
          1, mscale(f, m) = 0.1 m ln f + 1);  per head k_n = c W_uk[h],
          v = c W_uv[h];  softmax((q_n . k_n + q_r . k_r) x
          (qk_nope_head_dim + qk_rope_head_dim)^-0.5 x mscale(factor,
          mscale_all_dim)^2 + causal mask) v;  out W_o.
  dense   l < first_k_dense_replace: (silu(h Wg) * (h Wu)) Wd at width
          intermediate_size, h = RMSNorm'(u).
  experts s = sigmoid(h W_r) in float32; the num_experts_per_tok largest
          of s + b (`e_score_correction_bias`; one group) are taken; gates
          g = routed_scaling_factor x s[taken] / sum(s[taken]); each
          expert a SwiGLU at width moe_intermediate_size.  Every expert is
          held here (the config's own `ep_size` is 1).
  shared  a SwiGLU at width n_shared_experts x moe_intermediate_size,
          every token, added to the routed sum.

The reference below is those equations in plain `jax.numpy` float32,
independent of `ray_tpu/models/` and `ray_tpu/ops/`: no kernels, no
cache, no batching, no scan over layers, attention in the plain
(expanded) form, the streams a dimension of their own, the Sinkhorn
rounds sums over axes.  It shares only the parameter tree's layout:

    embed (V,d)  lm_head (d,V)  final_norm (d,)
    attn.* over all layers, dense.* over the leading dense layers, ffn.*
        over the expert layers: as families/glm4moelite.py lists them
    hc_attn.*, hc_ffn.* over all layers, float32: phi (., n^2 + 2n, n d:
        Phi transposed, its rows p | q | R row-major)  alpha (., 3: pre,
        post, res)  b (., n^2 + 2n)

Callers run it under `jax.default_matmul_precision("highest")`.
**Routing is handed over**, as in families/glm4moelite.py and for its
reason (`score`, `forward(routing="handed")`, ROUTER_SLACK).

Departures from the published model: none in the mathematics of what is
held.  Left out: the multi-token-prediction module
(`num_nextn_predict_layers`), a drafter that the next-token logits do not
pass through.  Assumed (the configuration file lists each under `assumed`
with its ground): the flattened streams' norm carries no weight; columns
before rows, `hc_eps` in both divisions and in that norm; expansion by
copying and contraction by summing; the seeding of the mixing's
parameters; the rope's pairing (i with i + 32), `kv_b_proj` by head, the
bias's seeding, DeepSeek's YaRN convention.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.spec import SpecError

F32 = jnp.float32
_EXPERT_STACKS = ("w_gate", "w_up", "w_down")

# The comparison that decides `correct` (bench/harness/reference.py), for
# this family: as families/glm4moelite.py's, every compared position decided
# by handed-over routing.  `check`: 2 lanes x (the last of 2,304 prompt
# positions, prefilled in launches of 512 rows over the latent pool, + 16
# decode steps through the kernels) = 34 positions, at the timed lengths.
# Measured on the chip at the published widths, 7 layers (my chip runs,
# PR 57: calls B and C; `control` below makes the faults for bench/tools/controls.py).
#
# LOGITS_REL_EXPERTS: rms error of a position's logits as a share of the
# reference's own.  ROUTER_SLACK: how far the program's set of experts may
# stray from the reference's, as a share of the spread of the token's
# selection scores.  HC_DEFECT_RATIO: the median over a sequence's positions
# of the program's own defect of H_res (a position's largest |row sum - 1|
# or |column sum - 1| over its 14 mixes, handed over beside the routing)
# over the same median of the reference's own 20 rounds.
#   The program as it is, 11 seeds x 34 positions (three bare checks, a
#   traced run and seven timed runs): a position's error has medians
#   0.0167-0.0228 and a largest of 0.0190-0.0270 on the three seeds read
#   bare; it strays by at most 0.023-0.037; its defects' median is
#   0.988-0.994 of the reference's (3.1e-5 to 1.2e-4 a seed; the largest
#   defect on the check's rows 0.0096-0.0233, `hc_res_defect` of a timed
#   window 0.028-0.033).
#   **The pool kept in 8-bit floats** (float8_e4m3fn, the nearest precision
#   below the stated `cache_dtype`; two seeds): error medians 0.0622 and
#   0.0727, largest 0.0721 and 0.0932, every position over the limit on
#   both; strays to 0.092-0.132.  0.04 lies between 0.0270 and 0.0622 with a
#   factor of one and a half on either side (the control's smallest median
#   reads 2.3 times the sound runs' largest error: families/glm4moelite.py
#   found the same narrow room and says why no value has more).  0.2 is
#   five times the sound runs' largest stray; the control does not reach it
#   and is refused by the error alone.
#   **The coefficients in bfloat16** (Phi, b, h_post and H_res rounded; one
#   seed here, two in call A): the logits move by 0.002-0.005 of their size
#   at float32 (CPU, tiny) and are not told from rounding at bfloat16, but
#   every row of H_res then sums to 1 only to 2^-9: the defects' median
#   reads 0.00247 against the reference's 5.3e-5, 47 times it, and every
#   position is refused.  **Five rounds of twenty**: 0.0372, 700 times.
#   3.0 lies between 0.994 and 47 with a factor of three below and fifteen
#   above; ten rounds read 120 times at the tiny size (CPU).  The largest
#   defect tells none of these apart (one slow row in a thousand sets it:
#   0.0068 for float32, 0.0079 for bfloat16 on 4,096 random rows), which
#   is why the median is what is held.
#   **What it cannot see:** a layer computed in bfloat16 where the
#   configuration says bfloat16; a router wrong by less than ROUTER_SLACK
#   everywhere; nineteen rounds of twenty (1.9e-5 at float32, tiny).
#   tests/test_mhc_mla_serving.py holds five faults of the mixing at a tiny
#   size in float32, where the logits tell each.
TOLERANCES = {"LOGITS_REL_EXPERTS": 0.04, "ROUTER_SLACK": 0.2,
              "HC_DEFECT_RATIO": 3.0}

# What `score` handed over: {a lane's token ids (int32 bytes): {"experts":
# (T, L_e, k), "hc_defect": (T, 1)}}.
_HANDED: dict = {}
# What the reference's last `forward` read of its own mixing: the largest
# |row sum - 1| and |column sum - 1| of H_res over its positions and mixes
# (what `hc_res_defect` of the program is held beside), the median over its
# positions of a position's largest, and the program's median where it was
# handed over.
LAST = {"hc_res_defect": 0.0, "hc_defect_median": 0.0,
        "hc_defect_median_program": None}


# ---------------------------------------------------------------------------
# configuration file -> the program
# ---------------------------------------------------------------------------
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


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def program_config(config: dict):
    try:
        from ray_tpu.models.mla_moe import MLAMoEConfig
        from ray_tpu.ops.rotary import YarnScaling

        if "hc_mult" not in {f.name for f in
                             dataclasses.fields(MLAMoEConfig)}:
            raise ImportError("one residual stream")
    except ImportError:
        _withdraw_app()
        raise SpecError(
            "this program's ray_tpu.models.mla_moe carries one residual "
            "stream a position (no hc_mult): it cannot run a configuration "
            "of the xing4 family") from None
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("topk_method", "noaux_tc"), ("norm_topk_prob", True),
                      ("scoring_func", "sigmoid"), ("n_group", 1),
                      ("topk_group", 1), ("moe_layer_freq", 1),
                      ("ep_size", 1), ("tie_word_embeddings", False)):
        if config[key] != want:
            raise SpecError(f"{key} = {config[key]!r}: the program's layers "
                            f"are {key} = {want!r}")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise SpecError("latent attention has one key and value a head: "
                        "num_key_value_heads = num_attention_heads")
    rs = config["rope_scaling"]
    if rs.get("type") != "yarn":
        raise SpecError(f"rope_scaling.type = {rs.get('type')!r}: the "
                        f"family's rope is under YaRN")
    return MLAMoEConfig(
        name=config["name"],
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_dense_layers=config["first_k_dense_replace"],
        n_heads=config["num_attention_heads"],
        q_rank=config["q_lora_rank"],
        kv_rank=config["kv_lora_rank"],
        d_nope=config["qk_nope_head_dim"],
        d_rope=config["qk_rope_head_dim"],
        d_v=config["v_head_dim"],
        d_ff=config["intermediate_size"],
        n_experts=config["n_routed_experts"],
        expert_top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["n_shared_experts"] * config["moe_intermediate_size"],
        route_scale=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        yarn=YarnScaling(
            factor=float(rs["factor"]),
            original_max_len=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            attention_factor=_mscale(rs["factor"], rs["mscale"])
            / _mscale(rs["factor"], rs["mscale_all_dim"])),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        hc_mult=config["hc_mult"],
        hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=float(config["hc_eps"]),
        hc_res_clamp=(float(config["mhc_h_res_clamp_min"]),
                      float(config["mhc_h_res_clamp_max"])),
        param_dtype=jnp.dtype(config["param_dtype"]),
        compute_dtype=jnp.dtype(config["compute_dtype"]))


def init_params(key, cfg):
    """The program's own initialiser (bench/harness/device.py calls it
    inside one jitted call, on the chip's `rbg` key)."""
    return cfg.init_params(key)


# ---------------------------------------------------------------------------
# the plain float32 reference
# ---------------------------------------------------------------------------
def _rms_norm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(F32)


def _inverse_frequencies(hd: int, c: dict):
    """(hd // 2,) of a roped head of `hd` dimensions under the
    configuration's YaRN (arXiv:2309.00071, as DeepSeek-V2's public code
    blends them: linearly by pair index between the pair that turns
    beta_fast times over the original context and the one that turns
    beta_slow times)."""
    rs, theta = c["rope_scaling"], float(c["rope_theta"])
    half = hd // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)

    def pair_that_turns(n):
        return hd * math.log(rs["original_max_position_embeddings"]
                             / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(rs["beta_slow"])), hd - 1)
    if low == high:
        high += 0.001
    slowed = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return jnp.asarray(inv * (1.0 - slowed) + inv / rs["factor"] * slowed,
                       F32)


def _rope(x, c):
    """x (T, heads, hd): rotate pairs (i, i + hd/2) by pos x the pair's
    frequency; cos and sin times YaRN's factor (1 where mscale =
    mscale_all_dim)."""
    t, _, hd = x.shape
    half = hd // 2
    rs = c["rope_scaling"]
    m = F32(_mscale(rs["factor"], rs["mscale"])
            / _mscale(rs["factor"], rs["mscale_all_dim"]))
    ang = jnp.arange(t, dtype=F32)[:, None] * _inverse_frequencies(hd, c)
    cos, sin = m * jnp.cos(ang)[:, None, :], m * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(c: dict) -> float:
    rs = c["rope_scaling"]
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 \
        * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


_QUERY_BLOCK = 512


def attention(u, p, c):
    """u (T, d) -> MLA(RMSNorm(u)) (T, d) in the plain form: every
    position's per-head keys and values expanded from its latent, causal
    soft-max, queries _QUERY_BLOCK at a time against the whole context."""
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    eps = c["rms_norm_eps"]
    u = _rms_norm(u, p["norm"], eps)
    t = u.shape[0]
    cq = _rms_norm(u @ p["wq_a"].astype(F32), p["q_norm"], eps)
    q = (cq @ p["wq_b"].astype(F32)).reshape(t, h, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], c)
    ckr = u @ p["wkv_a"].astype(F32)
    lat = _rms_norm(ckr[:, :r], p["kv_norm"], eps)
    k_r = _rope(ckr[:, None, r:], c)[:, 0]                       # (T, dr)
    k_n = jnp.einsum("tr,hnr->thn", lat, p["w_uk"].astype(F32))
    v = jnp.einsum("tr,hrv->thv", lat, p["w_uv"].astype(F32))
    scale = F32(softmax_scale(c))
    out = []
    for lo in range(0, t, _QUERY_BLOCK):
        hi = min(lo + _QUERY_BLOCK, t)
        s = (jnp.einsum("qhn,khn->hqk", q_n[lo:hi], k_n)
             + jnp.einsum("qhe,ke->hqk", q_r[lo:hi], k_r)) * scale
        seen = jnp.arange(t)[None, :] <= jnp.arange(lo, hi)[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khv->qhv", prob, v))
    return jnp.concatenate(out, 0).reshape(t, -1) @ p["wo"].astype(F32)


def dense_ffn(u, dp, c):
    h = _rms_norm(u, dp["norm"], c["rms_norm_eps"])
    return (jax.nn.silu(h @ dp["w_gate"].astype(F32))
            * (h @ dp["w_up"].astype(F32))) @ dp["w_down"].astype(F32)


def experts(u, fp, stacks, li, taken, c):
    """The routed experts of expert layer `li` over normed rows u (T, d);
    `stacks`: the expert layers' w_gate / w_up / w_down whole, read an
    expert at a time (a layer's 64 experts are 1.41 GB: sliced out a layer
    at a time, two layers' copies stood beside the engine's 11.67 GB and
    the run's peak read 15.02 GB; my chip run, PR 57, call A).  `taken`
    (T, k) int32: the experts the program took (None: the reference's own
    top-k of s + b).  Returns (the routed sum, margin (T,), bad (T,)
    bool), as families/glm4moelite.py's."""
    k, e = c["num_experts_per_tok"], c["n_routed_experts"]
    s = jax.nn.sigmoid(u @ fp["router"].astype(F32))             # (T, E)
    pick = s + fp["router_bias"].astype(F32)
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
        / jnp.sum(gates, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(taken, e, dtype=F32) * gates[..., None],
                     axis=1)                                      # (T, E)

    def one(acc, ex):
        at, w = ex
        gate, up, down = (stacks[name][li, at].astype(F32)
                          for name in _EXPERT_STACKS)
        hidden = jax.nn.silu(u @ gate) * (u @ up)
        return acc + w[:, None] * (hidden @ down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (jnp.arange(e), weight.T))
    return out, margin, bad


def expert_ffn(u, fp, stacks, li, taken, c):
    h = _rms_norm(u, fp["norm"], c["rms_norm_eps"])
    out, margin, bad = experts(h, fp, stacks, li, taken, c)
    gu = h @ fp["shared_gate_up"].astype(F32)
    f = gu.shape[-1] // 2
    shared = (jax.nn.silu(gu[:, :f]) * gu[:, f:]) \
        @ fp["shared_down"].astype(F32)
    return out + shared, margin, bad


def mixing(x, hp, c):
    """The coefficients of streams x (T, n, d): (h_pre (T, n), h_post
    (T, n), H_res (T, n, n)), the equations at the head of this file."""
    n, eps = c["hc_mult"], F32(c["hc_eps"])
    t = x.shape[0]
    flat = x.reshape(t, -1)
    r = flat * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + eps)
    proj = r @ hp["phi"].astype(F32).T
    alpha, b = hp["alpha"].astype(F32), hp["b"].astype(F32)
    h_pre = jax.nn.sigmoid(alpha[0] * proj[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(
        alpha[2] * proj[:, 2 * n:] + b[2 * n:],
        c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"])).reshape(t, n, n)
    for _ in range(c["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)   # each column
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)   # each row
    return h_pre, h_post, m


def mixed(x, hp, sub_block, c):
    """X' = H_res X + h_post^T F(h_pre X) over streams x (T, n, d);
    `sub_block` (T, d) -> ((T, d), ...).  Returns (X', each position's
    defect of H_res (T,), the rest of what the sub-block returned)."""
    h_pre, h_post, h_res = mixing(x, hp, c)
    out, *rest = sub_block(jnp.sum(h_pre[:, :, None] * x, axis=1))
    defect = jnp.max(jnp.maximum(
        jnp.abs(jnp.sum(h_res, axis=2) - 1.0),
        jnp.abs(jnp.sum(h_res, axis=1) - 1.0)), axis=-1)          # (T,)
    n = x.shape[1]
    new = sum(h_res[:, :, m, None] * x[:, None, m, :] for m in range(n)) \
        + h_post[:, :, None] * out[:, None, :]
    return new, defect, rest


def dense_block(x, ap, dp, ha, hf, c):
    """A leading layer on one sequence's streams x (T, n, d)."""
    x, d1, _ = mixed(x, ha, lambda u: (attention(u, ap, c),), c)
    x, d2, _ = mixed(x, hf, lambda u: (dense_ffn(u, dp, c),), c)
    return x, jnp.maximum(d1, d2)


def expert_block(x, ap, fp, stacks, li, ha, hf, taken, c):
    """Expert layer `li` on one sequence's streams x (T, n, d)."""
    x, d1, _ = mixed(x, ha, lambda u: (attention(u, ap, c),), c)
    x, d2, (margin, bad) = mixed(
        x, hf, lambda u: expert_ffn(u, fp, stacks, li, taken, c), c)
    return x, jnp.maximum(d1, d2), margin, bad


_HEAD_BLOCKS = 8


def _head_block(x, part, bad):
    return jnp.where(bad[:, None], jnp.nan, x) @ part.astype(F32)


def _head(x, w, bad, jit):
    """x (T, d) W_head -> (T, V) float32 on the host, an eighth of the
    head's columns at a time (families/glm4moelite.py `_head` says why)."""
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


def _layer(tree, i):
    return {name: a[i] for name, a in tree.items()}


def forward(params, tokens, c, jit=lambda f: f, routing="handed"):
    """tokens (T,) int32 -> (logits (T, V) float32 on the host, margin
    (T,)), one sequence; `margin` is each position's smallest over the
    expert layers.  `routing`: "handed" takes what `score` left for these
    tokens (its own top-k where nothing was left), None the reference's
    own, an array (T, expert layers, k) that, or a dict of it under
    "experts" with, under "hc_defect", the program's own defect of every
    position (T, 1): **the median of those over the sequence is held to
    HC_DEFECT_RATIO times the reference's own** (past it every position
    gets NaN logits: the projection was cut short or lost its float32).
    `jit=jax.jit` compiles each kind of layer once and runs it per layer.
    Leaves the defects of its own H_res in `LAST`."""
    if isinstance(routing, str):
        routing = _HANDED.get(_key(tokens))
    theirs = None
    if isinstance(routing, dict):
        routing, theirs = routing["experts"], routing.get("hc_defect")
    n, nd = c["num_hidden_layers"], c["first_k_dense_replace"]
    dense_fn = jit(functools.partial(dense_block, c=c))
    expert_fn = jit(functools.partial(expert_block, c=c))
    x = params["embed"][tokens].astype(F32)
    # expansion: the embedding in each of the streams
    x = jnp.broadcast_to(x[:, None, :], (x.shape[0], c["hc_mult"],
                                         x.shape[1]))
    margin = jnp.full(x.shape[:1], jnp.inf, F32)
    bad = jnp.zeros(x.shape[:1], bool)
    defect = jnp.zeros(x.shape[:1], F32)
    if routing is not None and routing.shape != (
            x.shape[0], n - nd, c["num_experts_per_tok"]):
        routing, bad = None, ~bad         # not a routing of this model
    stacks = {name: params["ffn"][name] for name in _EXPERT_STACKS}
    small = {name: a for name, a in params["ffn"].items()
             if name not in _EXPERT_STACKS}
    for i in range(n):
        ap = _layer(params["attn"], i)
        ha, hf = _layer(params["hc_attn"], i), _layer(params["hc_ffn"], i)
        if i < nd:
            x, d = dense_fn(x, ap, _layer(params["dense"], i), ha, hf)
        else:
            x, d, m, b = expert_fn(
                x, ap, _layer(small, i - nd), stacks, jnp.int32(i - nd), ha,
                hf,
                None if routing is None else jnp.asarray(routing[:, i - nd]))
            margin, bad = jnp.minimum(margin, m), bad | b
        defect = jnp.maximum(defect, d)
    own = float(jnp.median(defect))
    LAST.update(hc_res_defect=float(jnp.max(defect)), hc_defect_median=own,
                hc_defect_median_program=None)
    if theirs is not None:
        got = float(np.median(np.asarray(theirs)))
        LAST["hc_defect_median_program"] = got
        if not got <= TOLERANCES["HC_DEFECT_RATIO"] * own:
            bad = ~jnp.zeros_like(bad)
    # contraction: the sum of the streams
    x = jit(functools.partial(_rms_norm, eps=c["rms_norm_eps"]))(
        jnp.sum(x, axis=1), params["final_norm"])
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
    (each launch reading the lane's earlier blocks of the latent pool) and
    teacher-forced steps through the function its burst scans, both
    compiled to hand out the experts they took, which are kept for
    `forward` under each lane's token ids."""
    got, taken = e.score(seqs, n_prompt, routing=True)
    _HANDED.clear()
    for lane, route in enumerate(taken):
        _HANDED[_key(seqs[lane])] = jax.tree.map(np.asarray, route)
    return got


# ---------------------------------------------------------------------------
# the faults behind `TOLERANCES`
# ---------------------------------------------------------------------------
def control(fault: str, cfg):
    """For bench/tools/controls.py: (the program configuration, a function
    that undoes the patch) of `sound`, `bf16_coefficients`, `iters_<n>`,
    `no_clip`, `no_dynamic` or `pool_fp8` (the tool's head says what each
    is); the readings are beside `TOLERANCES`."""
    from ray_tpu.models import mla_moe
    from ray_tpu.ops import hyper_connections as hc

    if fault == "sound":
        return cfg, lambda: None
    if fault.startswith("iters_"):
        return dataclasses.replace(
            cfg, hc_sinkhorn_iters=int(fault[6:])), lambda: None
    if fault == "no_clip":
        return dataclasses.replace(cfg, hc_res_clamp=(-1.0, 1.0)), \
            lambda: None
    inner = hc.hc_coefficients
    if fault == "no_dynamic":
        def patched(x, phi, alpha, b, **kw):
            return inner(x, jnp.zeros_like(phi), alpha, b, **kw)
    elif fault == "bf16_coefficients":
        bf = jnp.bfloat16

        def rounded(v):
            return v.astype(bf).astype(jnp.float32)

        def patched(x, phi, alpha, b, **kw):
            u, h_post, h_res, _ = inner(x, rounded(phi), alpha, rounded(b),
                                        **kw)
            h_res = rounded(h_res)
            return u, rounded(h_post), h_res, hc.res_defect(h_res)
    elif fault == "pool_fp8":
        row = mla_moe._latent_row
        mla_moe._latent_row = lambda *a: row(*a).astype(
            jnp.float8_e4m3fn).astype(jnp.bfloat16)

        def undo_row():
            mla_moe._latent_row = row

        return cfg, undo_row
    else:
        raise SystemExit(f"no fault {fault!r}")
    mla_moe.hc_coefficients = patched

    def undo():
        mla_moe.hc_coefficients = inner

    return cfg, undo


# ---------------------------------------------------------------------------
# Operations and bytes a step needs, from shapes alone: what the
# algorithm requires, not what the program happens to execute.
# ---------------------------------------------------------------------------
def _dims(c: dict) -> dict:
    n, nd = c["num_hidden_layers"], c["first_k_dense_replace"]
    hc = c["hc_mult"]
    return {"d": c["hidden_size"], "v": c["vocab_size"],
            "h": c["num_attention_heads"], "qr": c["q_lora_rank"],
            "r": c["kv_lora_rank"], "dn": c["qk_nope_head_dim"],
            "dr": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
            "fd": c["intermediate_size"], "f": c["moe_intermediate_size"],
            "fs": c["n_shared_experts"] * c["moe_intermediate_size"],
            "e": c["n_routed_experts"], "k": c["num_experts_per_tok"],
            "n": n, "nd": nd, "ne": n - nd, "hc": hc,
            "c": hc * hc + 2 * hc}


def _itemsize(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]


def matrix_params(c: dict) -> dict:
    """Matrix parameters of one layer's parts, and of what is held here."""
    s = _dims(c)
    d, h = s["d"], s["h"]
    parts = {"attn": d * s["qr"] + s["qr"] * h * (s["dn"] + s["dr"])
             + d * (s["r"] + s["dr"]) + s["r"] * h * (s["dn"] + s["dv"])
             + h * s["dv"] * d,
             "dense_ffn": 3 * d * s["fd"], "shared": 3 * d * s["fs"],
             "router": d * s["e"], "expert": 3 * d * s["f"],
             # two mixes a layer, each a Phi of (n d, n^2 + 2n)
             "mixing": 2 * s["hc"] * d * s["c"]}
    # every weight outside the routed experts that a step reads once: the
    # head, not the embedding (a gather of the step's rows)
    parts["dense"] = s["n"] * (parts["attn"] + parts["mixing"]) \
        + s["nd"] * parts["dense_ffn"] \
        + s["ne"] * (parts["shared"] + parts["router"]) + s["v"] * d
    parts["total"] = parts["dense"] + s["v"] * d \
        + s["ne"] * s["e"] * parts["expert"]
    return parts


def expected_held_experts(c: dict, rows: float) -> float:
    """Distinct experts that `rows` tokens take in one layer under uniform
    routing: E x (1 - (1 - k/E)^rows).  (4 of 64 for one row, 25.8 for
    eight, all 64 from some 100 rows on.)"""
    s = _dims(c)
    return s["e"] * (1.0 - (1.0 - s["k"] / s["e"]) ** rows)


def expert_bytes_per_step(c: dict, lanes: int) -> float:
    """Bytes of expert weights one step of `lanes` tokens needs: the
    experts taken in every expert layer, each once."""
    s = _dims(c)
    return s["ne"] * expected_held_experts(c, lanes) \
        * matrix_params(c)["expert"] * _itemsize(c["param_dtype"])


def expert_bytes_per_chunk(c: dict, tokens: float) -> float:
    """Bytes of expert weights a prefill chunk of `tokens` needs."""
    return expert_bytes_per_step(c, tokens)


def expert_flops_per_chunk(c: dict, tokens: float) -> float:
    """FLOPs of the routed rows of a chunk: a token takes k experts."""
    s = _dims(c)
    return 2.0 * s["ne"] * tokens * s["k"] * matrix_params(c)["expert"]


def expert_operand(c: dict):
    """What an op that reads a layer's expert weights shows in its HLO
    text: an operand shaped [E,d,f] or [E,f,d] (after the layers' axis,
    where the stacks are whole), as a compiled pattern."""
    s = _dims(c)
    return re.compile(rf"\[(?:\d+,)?{s['e']},(?:{s['d']},{s['f']}|"
                      rf"{s['f']},{s['d']})\]")


def hc_bytes_per_row(c: dict) -> int:
    """Bytes of streams one row must move through the stack's mixes, each
    touched once a phase, whatever implements them: a mix reads the n
    streams for its coefficients and the mix-down and writes the
    sub-block's input (d), then reads the n streams and the sub-block's
    output (d) and writes the n new streams: (3 n + 2) d values, twice a
    layer.  100,352 B a mix and 1.40 MB a row at n 4, d 3584, 7 layers in
    bfloat16.  The coefficients themselves (n^2 + 2n float32 a row) are
    left out: under a hundredth.  **These are the bytes of a launch whose
    streams do not fit on the chip**: at 512 rows they are 14.7 MB, XLA
    keeps them in VMEM from one mix to the next, and the three kernels
    take 0.54 ms where these bytes at 819 GB/s would take 0.88 (my chip
    runs, PR 57): `hc_roofline` counts them only past the chip's on-chip
    memory (bench/metrics/hc_roofline.py)."""
    s = _dims(c)
    return 2 * s["n"] * (3 * s["hc"] + 2) * s["d"] \
        * _itemsize(c["compute_dtype"])


def hc_stream_bytes(c: dict, rows: float) -> float:
    """Bytes of the streams of a launch of `rows` rows: what has to fit
    on the chip for the mixes to pass them from one to the next without a
    trip through HBM (14.7 MB at 512 rows)."""
    s = _dims(c)
    return rows * s["hc"] * s["d"] * _itemsize(c["compute_dtype"])


def hc_phi_bytes(c: dict) -> int:
    """Bytes of Phi a launch must read whatever its rows: (n^2 + 2n) x
    n d float32, twice a layer (19.3 MB at 7 layers)."""
    s = _dims(c)
    return 2 * s["n"] * s["c"] * s["hc"] * s["d"] * 4


def hc_flops_per_row(c: dict) -> float:
    """FLOPs of the coefficients' product a row on a unit that multiplies
    bfloat16: vec(X) Phi exact in float32 is three bfloat16 passes (Phi's
    leading, middle and trailing bits), 2 x n d x 3 (n^2 + 2n), twice a
    layer.  The mixes themselves (n d and (n^2 + n) d multiply-adds in
    float32 a mix) run on the vector unit, which has no published peak,
    and are left out: the share of a roofline that counts these is a
    floor's, and says so."""
    s = _dims(c)
    return 2 * s["n"] * 2.0 * s["hc"] * s["d"] * 3 * s["c"]


def hc_operand(c: dict):
    """What an op that reads or writes a row's streams shows in its HLO
    text: a 16-bit array whose trailing dimension is the n streams laid
    flat (n d: `ops.hyper_connections` says why they are): the streams
    themselves and Phi's three bfloat16 parts, which the coefficients'
    product reads (n^2 + 2n rows of n d).  As a compiled pattern."""
    s = _dims(c)
    return re.compile(rf"bf16\[(?:\d+,)*{s['hc'] * s['d']}\]")


def prefill_flops(c: dict, tokens: float, context: float) -> float:
    """FLOPs that `tokens` prompt tokens need which together attend over
    `context` positions (a token at position p attends p + 1): the layers'
    matrices with the k experts a token takes, the mixing's products (a
    Phi of (n d, n^2 + 2n) a mix) and its four-stream mixes (n d for the
    mix-down, (n^2 + n) d for the mix-up, a multiply-add each), and
    attention in the plain form (a score qk_nope_head_dim +
    qk_rope_head_dim wide and a value v_head_dim wide a head and attended
    position).  The output head, once a prompt, is left out."""
    s, m = _dims(c), matrix_params(c)
    dense = m["dense"] - s["v"] * s["d"]
    routed = s["ne"] * s["k"] * m["expert"]
    mixes = 2 * s["n"] * (s["hc"] ** 2 + 2 * s["hc"]) * s["d"]
    return 2.0 * (dense + routed + mixes) * tokens \
        + 2.0 * s["n"] * s["h"] * (s["dn"] + s["dr"] + s["dv"]) * context


# ---------------------------------------------------------------------------
# for bench/tools/memory_fit.py
# ---------------------------------------------------------------------------
def serve_programs(config: dict, place):
    """What a replica of `config` keeps resident, as shapes, and its
    largest programs lowered at the engine's sizes: the widest decode
    burst, and a prefill chunk at the configuration's width and at the
    widest tier a pool-only model's tick launches (512 rows)."""
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

    def chunk(rows):
        return (f"paged_prefill_chunk c={rows}", chunk_fn.lower(
            params, state, arr((rows,), jnp.int32), arr((b_max,), jnp.int32),
            arr((), jnp.int32), arr((), jnp.int32)))

    w = eng["num_slots"]
    return {"params": params, "sequence_state": state}, [
        (f"paged_decode_burst w={w}", burst_fn.lower(
            params, state, arr((w,), jnp.int32), arr((w, b_max), jnp.int32),
            arr((w,), jnp.int32), arr((w,), jnp.bool_),
            arr((w,), jnp.float32), rng, n_steps=eng["max_burst"])),
        chunk(eng["prefill_chunk"]), chunk(512)]
