"""The deepseek_v32 family: everything the harness knows of
DeepSeek-V3.2-Exp (`model_type: deepseek_v32`, deepseek-ai): latent
attention (MLA, arXiv:2405.04434) under YaRN in every layer, **every layer
attending to the `index_topk` positions a learned indexer scores highest**
(the lightning indexer of the model's own report), a dense SwiGLU in the
leading `first_k_dense_replace` layers and, in the rest, routed experts
**chosen group by group** (`n_group` groups of consecutive experts,
`topk_group` kept) by biased sigmoid scores, beside a shared expert.  A
configuration file says `"family": "deepseek_v32"`; what the harness asks
of a family is listed at the top of families/mistral.py.  This one also
gives `index_operand` / `attn_operand` / `select_operand` with
`index_flops` / `index_bytes` / `attn_flops` / `attn_bytes` for a prefill
launch and `*_per_step` for a decode step (the `dsa_*` metrics and their
`.decode` siblings), `routed_choices_per_row`, `control` (for
bench/tools/controls.py) and `TOLERANCES`, with its measurements beside
it.

The model, for layer `l` of `num_hidden_layers`, eps `rms_norm_eps`, no
biases on any projection, untied embedding and head, `u` a position's
normed input:

    x = E[token]
    x += Attn_l(RMSNorm(x))
    x += FFN_l(RMSNorm'(x))
    logits = RMSNorm_f(x) W_head

  attn    c_q = RMSNorm(u W_qa)  (q_lora_rank);  q = c_q W_qb, per head
          (q_n qk_nope_head_dim | q_r qk_rope_head_dim);  [c | k_r] =
          u W_kva  (kv_lora_rank | qk_rope_head_dim);  c = RMSNorm(c);
          rope (theta `rope_theta`, rotate-half, all qk_rope_head_dim
          dims, **under YaRN**, `rope_scaling`: the pairs' frequencies
          blended as DeepSeek's public code does, cos and sin x
          mscale(factor, mscale) / mscale(factor, mscale_all_dim) = 1,
          mscale(f, m) = 0.1 m ln f + 1) on every head's q_r and on the one
          k_r;  k_n = c W_uk[h], v = c W_uv[h].
          indexer: q^I = c_q W^I_qb, index_n_heads x index_head_dim, the
          first qk_rope_head_dim dims of each roped (the same frequencies);
          k^I = LayerNorm(u W^I_k) (gain and bias, eps
          `assumed.index_norm_eps`), the first qk_rope_head_dim roped;
          w = u W^I_w x index_n_heads^-1/2 x index_head_dim^-1/2;
          I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s]) for s <= t;
          S_t = the index_topk positions s <= t of largest I[t, s] (all
          of them while t < index_topk).
          o_h = softmax_{s in S_t}((q_n,h . k_n,s,h + q_r,h . k_r,s) x
          (qk_nope_head_dim + qk_rope_head_dim)^-1/2 x mscale(factor,
          mscale_all_dim)^2) v_s,h;  out = concat_h(o_h) W_o.
  dense   l < first_k_dense_replace: (silu(h Wg) * (h Wu)) Wd at width
          intermediate_size.
  experts s = sigmoid(h W_r) in float32 over all published experts; z =
          s + b (`e_score_correction_bias`); the experts in `n_group`
          groups of consecutive ones, a group's score the sum of its two
          largest z, the `topk_group` groups of largest score kept; the
          num_experts_per_tok largest z inside them are taken; gates g =
          routed_scaling_factor x s[taken] / sum(s[taken]) (the bias
          selects and does not gate; the sum runs over all taken, held
          here or not); each expert a SwiGLU at width
          moe_intermediate_size.  **This chip holds `n_routed_experts` of
          them, from `first_local_expert`**: the sum runs over the held
          experts a token took and the rest of its experts is left out,
          in the program and here alike (model-configs guide, section 4).
  shared  a SwiGLU at width n_shared_experts x moe_intermediate_size,
          every token, added to the routed sum.

The reference below is those equations in plain `jax.numpy` float32,
independent of `ray_tpu/`: no kernels, no cache, no batching, no scan over
layers, **attention in the plain (expanded) form** (every position's k_n
and v made from its latent, an explicit mask over the whole context), a
block of heads and a block of query rows at a time so that it fits; the
indexer's scores in blocks of query rows; every held expert evaluated on
every token and weighted (zero where not taken), cast to float32 an expert
at a time.  It shares only the parameter tree's layout, which is data
(ray_tpu/models/mla_moe.py lists it; families/dots3note.py has the same
leaves less the head gate and the window layers').

Callers run it under `jax.default_matmul_precision("highest")`.

**Routing and selection are handed over**, as in families/dots3note.py and
for its reasons: `score` keeps, under a lane's token ids, the experts the
program took at every position and expert layer, **the groups it kept
there**, and the positions every layer attended at every row.  `forward`
computes its own float32 scores and holds each handed set to them: the
kept groups to the reference's own group scores and the taken experts to
its z inside those groups (the larger stray against ROUTER_SLACK, in units
of the spread of the token's z), the positions to its own index scores
(SELECT_SLACK a row, SELECT_SLACK_MEDIAN a lane); a set outside its slack,
or not of the right count, gives NaN logits at that row, which
`logits_verdict` refuses.  `margin` is 1 - the largest stray; with the
reference's own routing (`routing=None`) **the smaller of the expert edge
(8th against 9th z inside the kept groups) and the group edge (the 4th
against the 5th group's score)**, both over the spread of z.

Assumed (the configuration file lists each under `assumed` with its
ground): the seeded selection bias, the group score, what stands outside
the kept groups, the rope's pairing, the indexer's formula and its norm's
eps.  Left out: the multi-token-prediction module, which next-token logits
do not pass through.
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

# The comparison that decides `correct` (bench/harness/reference.py), for
# this family.  Every compared position is decided by handed-over routing
# and selection (above), so LOGITS_REL_EXPERTS holds all 34 of a run
# (`check`: 2 lanes x (the last of 8,192 prompt positions, prefilled in 16
# launches of 512 rows through the pool and the index keys, + 16 decode
# steps): the timed lengths, every compared row past index_topk in all five
# layers).  Measured on the chip at published widths, 5 layers, 8 of 256
# experts (my chip runs, PR 59, calls 2 and 3: bench/tools/controls.py's
# loop with the limits loosened so that every reading prints, one engine a
# (fault, seed); `control` below says what each fault is).
#
# LOGITS_REL_EXPERTS: rms error of a position's logits as a share of the
# reference's own.  ROUTER_SLACK: how far a row's kept groups (by the
# groups' scores) or its experts (by z inside the kept groups) may stray
# from the reference's, as a share of the spread (standard deviation over
# the published experts) of the token's z.  SELECT_SLACK: the same for the
# set of positions a layer attends at one row, as a share of the spread
# (over the positions the row sees) of the row's index scores.
# SELECT_SLACK_MEDIAN: the median, over all rows of a lane that see more
# than index_topk positions, of a row's largest stray over its five layers,
# past which the whole lane is refused.
#   The program as it is, 6 seeds: a position's error has medians
#   0.0154-0.0160 and a largest a seed of 0.0169-0.0180; over the compared
#   rows the largest stray (groups, experts or selection) has medians
#   0.070-0.077 and a largest of 0.099-0.127; over every row of a lane a
#   row's selection strays by at most 0.141-0.150, its groups and experts
#   by at most 0.046-0.060, and **the lane's median stray reads
#   0.0666-0.0686** (five layers' largest: families/dots3note.py reads
#   0.040 over two).
#   **The index keys in 8-bit floats** (`keys_fp8`, 3 seeds): the errors do
#   not move (medians 0.0155-0.0157, largest 0.0169-0.0173: which 2,048 of
#   8k positions a row attends hardly reaches seeded logits), the compared
#   rows' stray has medians 0.118-0.123 and a largest of 0.153-0.177, any
#   row's selection 0.227-0.249, and **the lane's median 0.1150-0.1161**:
#   refused by SELECT_SLACK_MEDIAN alone.  0.09 lies between 0.0686 and
#   0.1150 with a factor of 1.3 on either side, on two readings that each
#   hold to 3% over seeds.  (SELECT_SLACK stays for a row's gross faults,
#   at 0.3, twice the sound runs' largest over any row.)
#   **The stored latent rows in 8-bit floats** (`pool_fp8`, 3 readings on 2
#   seeds): error medians 0.0627-0.0633, largest 0.0678-0.0712, every
#   position over the limit; strays 0.34-0.35 (median), the lane's median
#   0.33-0.34, groups and experts to 0.22-0.30.  0.033 lies between 0.0180
#   and 0.0627 with a factor of 1.8 below and 1.9 above.
#   At these widths the check also refuses, each read on one seed (error
#   median, largest; compared rows' stray median, largest): **a group
#   scored by its maximum** (`group_max`: 0.0154, 0.0169: the logits do not
#   move; 0.244, 0.657, and 1.157 over any row: more than half the compared
#   rows' groups stray past ROUTER_SLACK, which lies a factor of 3.3 over
#   the sound runs' 0.060), **one group for eight** (`one_group`: a row's
#   experts stand in more than topk_group groups: NaN; strays 0.46, 1.04),
#   **the gates not scaled by 2.5** (`no_scale`: 0.0722, 0.244; strays
#   0.63, 1.43: another function from the first expert layer on), **1,024
#   positions for 2,048** (`top_half`: not 2,048 distinct positions: NaN at
#   every row).
#   **What it cannot see:** a group edge or an expert edge settled the
#   other way inside ROUTER_SLACK, and a selection's inside SELECT_SLACK
#   (they are handed over: that is what rounding does, and a fault
#   rarely); equal scores at a set's edge (the sort's order stands); a
#   layer computed in bfloat16 where the configuration says bfloat16.
#   tests/test_group_moe_serving.py holds the six faults at a tiny size in
#   float32, where nothing strays at all.
TOLERANCES = {"LOGITS_REL_EXPERTS": 0.033, "ROUTER_SLACK": 0.2,
              "SELECT_SLACK": 0.3, "SELECT_SLACK_MEDIAN": 0.09}

# What `score` handed over, by a lane's token ids (int32 bytes): the
# experts every position took (T, L_e, k), the groups it kept there (T,
# L_e, topk_group; None from a program that keeps none), the positions the
# rows attended (rows, layers, index_topk), and the first such row.
_HANDED: dict = {}
# What the last `forward` read of the handed sets (a tool's to print;
# nothing is decided by it).
LAST = {"select_stray": 0.0, "select_stray_median": 0.0, "route_stray": 0.0}


# ---------------------------------------------------------------------------
# configuration file -> the program
# ---------------------------------------------------------------------------
def published_experts(config: dict) -> int:
    """The router's width: the published count of routed experts, of
    which `n_routed_experts` are held here."""
    return int(config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"]))


def held_range(config: dict):
    """(first, count) of the published experts that this chip holds."""
    return int(config.get("first_local_expert", 0)), \
        int(config["n_routed_experts"])


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
    except ImportError:
        MLAMoEConfig = None
    needs = {"expert_groups", "expert_groups_kept", "index_top_k", "yarn"}
    lacks = needs - ({f.name for f in dataclasses.fields(MLAMoEConfig)}
                     if MLAMoEConfig else set())
    if lacks:
        _withdraw_app()
        raise SpecError(
            f"this program's MLAMoEConfig has no {sorted(lacks)}: it cannot "
            f"run a configuration of the deepseek_v32 family")
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("topk_method", "noaux_tc"), ("norm_topk_prob", True),
                      ("scoring_func", "sigmoid"), ("moe_layer_freq", 1),
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
    first, count = held_range(config)
    e = published_experts(config)
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
        n_experts=e,
        expert_top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["n_shared_experts"] * config["moe_intermediate_size"],
        route_scale=float(config["routed_scaling_factor"]),
        experts_held=None if count == e else (first, count),
        expert_groups=config["n_group"],
        expert_groups_kept=config["topk_group"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        index_heads=config["index_n_heads"],
        index_dim=config["index_head_dim"],
        index_top_k=config["index_topk"],
        index_norm_eps=float(config["assumed"]["index_norm_eps"]),
        yarn=YarnScaling(
            factor=float(rs["factor"]),
            original_max_len=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            attention_factor=_mscale(rs["factor"], rs["mscale"])
            / _mscale(rs["factor"], rs["mscale_all_dim"])),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
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


def _layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain.astype(F32) \
        + bias.astype(F32)


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
    """x (T, heads, hd): rotate pairs (i, i + dr/2) of the first dr =
    qk_rope_head_dim dimensions by pos x the pair's frequency, the rest as
    they are; cos and sin times YaRN's factor (1 where mscale =
    mscale_all_dim)."""
    t, width = x.shape[0], c["qk_rope_head_dim"]
    half = width // 2
    rs = c["rope_scaling"]
    m = F32(_mscale(rs["factor"], rs["mscale"])
            / _mscale(rs["factor"], rs["mscale_all_dim"]))
    ang = jnp.arange(t, dtype=F32)[:, None] * _inverse_frequencies(width, c)
    cos, sin = m * jnp.cos(ang)[:, None, :], m * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:width]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., width:]], -1)


def softmax_scale(c: dict) -> float:
    rs = c["rope_scaling"]
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 \
        * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


_QUERY_BLOCK = 512      # query rows of one block of attention scores
_INDEX_BLOCK = 128      # query rows of one block of index scores
_HEAD_BLOCK = 16        # heads whose keys and values are expanded at once


def _blocks(x, size):
    """x (T, ..) -> (ceil(T / size), size, ..), zero rows behind."""
    pad = -x.shape[0] % size
    x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x.reshape(-1, size, *x.shape[1:])


def index_scores(u, cq, p, c):
    """The indexer's score of every position for every query row, in
    blocks of _INDEX_BLOCK rows: (T, T) float32, -inf where s > t."""
    t = u.shape[0]
    hi, di = c["index_n_heads"], c["index_head_dim"]
    key = _layer_norm(u @ p["wk_idx"].astype(F32), p["k_idx_norm"],
                      p["k_idx_bias"], c["assumed"]["index_norm_eps"])
    key = _rope(key[:, None, :], c)[:, 0]                        # (T, Di)
    q = _rope((cq @ p["wq_idx"].astype(F32)).reshape(t, hi, di), c)
    w = (u @ p["w_idx"].astype(F32)) * F32(hi ** -0.5 * di ** -0.5)

    def block(xs):
        q, w, at = xs                       # (b, Hi, Di), (b, Hi), (b,)
        sc = jnp.einsum("qhd,kd->qhk", q, key)
        out = jnp.sum(jax.nn.relu(sc) * w[:, :, None], axis=1)   # (b, T)
        return jnp.where(jnp.arange(t)[None, :] <= at[:, None], out,
                         -jnp.inf)

    # a padded row (at = -1) sees nothing and is dropped
    out = jax.lax.map(block, (_blocks(q, _INDEX_BLOCK),
                              _blocks(w, _INDEX_BLOCK),
                              _blocks(jnp.arange(t) + 1, _INDEX_BLOCK) - 1))
    return out.reshape(-1, t)[:t]


def own_selection(scores, k: int):
    """(rows, T) bool: each row's `k` positions of largest score, the
    lower position first among equals (relu leaves exact zeros, so equals
    are common at small sizes); all it sees while those are fewer."""
    def block(sc):
        top, at = jax.lax.top_k(sc, min(k, sc.shape[-1]))
        rows = jnp.arange(sc.shape[0])[:, None]
        return jnp.zeros(sc.shape, bool).at[
            rows, jnp.where(top > -jnp.inf, at, sc.shape[-1])].set(
            True, mode="drop")

    mask = jax.lax.map(block, _blocks(scores, _INDEX_BLOCK))
    return mask.reshape(-1, scores.shape[1])[:scores.shape[0]]


def handed_selection(scores, handed, first, k: int):
    """The program's sets for the rows `first` .. of one layer, `handed`
    (n, k') int32, against the reference's own `scores` (n, T) of those
    rows, _INDEX_BLOCK rows at a time.  Returns (mask (n, T) bool of the
    handed positions a row sees, stray (n,): how far the set strays from
    the reference's top-k in units of the row's score spread, bad (n,):
    not min(t + 1, k) distinct positions <= t, or a stray beyond
    SELECT_SLACK)."""
    n, t = scores.shape

    def block(xs):
        scores, handed, at = xs             # (b, T), (b, k'), (b,)
        b = scores.shape[0]
        seen = jnp.arange(t)[None, :] <= at[:, None]
        inside = (handed <= at[:, None]) & (handed >= 0)
        mask = jnp.zeros((b, t), bool).at[
            jnp.arange(b)[:, None], jnp.where(inside, handed, t)].set(
            True, mode="drop")
        count = jnp.maximum(jnp.sum(seen, axis=-1), 1)
        mean = jnp.sum(jnp.where(seen, scores, 0.0), axis=-1) / count
        spread = jnp.sqrt(jnp.sum(jnp.where(
            seen, jnp.square(scores - mean[:, None]), 0.0), axis=-1) / count)
        kth = jax.lax.top_k(scores, min(k, t))[0][:, -1]
        lowest_in = jnp.min(jnp.where(mask, scores, jnp.inf), axis=-1)
        highest_out = jnp.max(jnp.where(seen & ~mask, scores, -jnp.inf),
                              axis=-1)
        stray = jnp.where(
            at + 1 > k,
            jnp.maximum(jnp.maximum(kth - lowest_in, highest_out - kth), 0.0)
            / spread, 0.0)
        bad = (jnp.sum(mask, axis=-1) != jnp.minimum(at + 1, k)) \
            | (stray > TOLERANCES["SELECT_SLACK"])
        # a padded row (at = -1) is dropped
        return mask, stray, bad & (at >= 0)

    mask, stray, bad = jax.lax.map(block, (
        _blocks(scores, _INDEX_BLOCK), _blocks(handed, _INDEX_BLOCK),
        _blocks(first + jnp.arange(n) + 1, _INDEX_BLOCK) - 1))
    return mask.reshape(-1, t)[:n], stray.reshape(-1)[:n], \
        bad.reshape(-1)[:n]


def attention(x, p, c, handed=None, first=None):
    """x (T, d) -> (Attn(RMSNorm(x)) (T, d), stray (T,), bad (T,)) in the
    plain form: every position's per-head keys and values expanded from
    its latent, _HEAD_BLOCK heads at a time, a masked soft-max over the
    whole context, _QUERY_BLOCK query rows at a time.  `handed` (n,
    index_topk) with `first` (a traced scalar): the positions the program
    attended for rows `first` ..; `stray` and `bad` are what
    `handed_selection` reads there, 0 and False elsewhere."""
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    eps = c["rms_norm_eps"]
    u = _rms_norm(x, p["norm"], eps)
    t = u.shape[0]
    cq = _rms_norm(u @ p["wq_a"].astype(F32), p["q_norm"], eps)
    ckr = u @ p["wkv_a"].astype(F32)
    lat = _rms_norm(ckr[:, :r], p["kv_norm"], eps)
    k_r = _rope(ckr[:, None, r:], c)[:, 0]                       # (T, dr)
    at = jnp.arange(t)
    stray, bad = jnp.zeros((t,), F32), jnp.zeros((t,), bool)
    if c["index_topk"]:
        scores = index_scores(u, cq, p, c)
        seen = own_selection(scores, c["index_topk"])
        if handed is not None:
            mask, far, wrong = handed_selection(
                jax.lax.dynamic_slice_in_dim(scores, first, handed.shape[0]),
                handed, first, c["index_topk"])
            seen = jax.lax.dynamic_update_slice_in_dim(seen, mask, first, 0)
            stray = jax.lax.dynamic_update_slice_in_dim(stray, far, first, 0)
            bad = jax.lax.dynamic_update_slice_in_dim(bad, wrong, first, 0)
    else:
        seen = at[None, :] <= at[:, None]
    scale = F32(softmax_scale(c))
    hb = _HEAD_BLOCK if h % _HEAD_BLOCK == 0 else h
    seen_blocks = _blocks(seen, _QUERY_BLOCK)

    def heads(acc, ws):
        wq_b, w_uk, w_uv, wo = ws      # (qr,hb,dn+dr) (hb,dn,r) (hb,r,dv)
        q = jnp.einsum("tr,rhe->the", cq, wq_b.astype(F32))
        q_n, q_r = q[..., :dn], _rope(q[..., dn:], c)
        k_n = jnp.einsum("tr,hnr->thn", lat, w_uk.astype(F32))
        v = jnp.einsum("tr,hrv->thv", lat, w_uv.astype(F32))

        def rows(xs):
            q_n, q_r, seen = xs
            sc = (jnp.einsum("qhn,khn->hqk", q_n, k_n)
                  + jnp.einsum("qhe,ke->hqk", q_r, k_r)) * scale
            prob = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf),
                                  axis=-1)
            # a padded row sees nothing: NaN, dropped below
            return jnp.einsum("hqk,khv->qhv", prob, v)

        out = jax.lax.map(rows, (_blocks(q_n, _QUERY_BLOCK),
                                 _blocks(q_r, _QUERY_BLOCK), seen_blocks))
        out = out.reshape(-1, hb, dv)[:t]
        return acc + out.reshape(t, hb * dv) @ wo.astype(F32).reshape(
            hb * dv, -1), None

    nb = h // hb
    out, _ = jax.lax.scan(heads, jnp.zeros_like(x), (
        jnp.moveaxis(p["wq_b"].reshape(-1, nb, hb, dn + dr), 1, 0),
        p["w_uk"].reshape(nb, hb, dn, r), p["w_uv"].reshape(nb, hb, r, dv),
        p["wo"].reshape(nb, hb, dv, -1)))
    return out, stray, bad


def dense_ffn(u, dp):
    return (jax.nn.silu(u @ dp["w_gate"].astype(F32))
            * (u @ dp["w_up"].astype(F32))) @ dp["w_down"].astype(F32)


def _stray(values, mine, k: int):
    """How far the set `mine` (.., N) bool strays from the `k` largest of
    `values` (.., N): the larger of (the k-th largest - the lowest inside)
    and (the highest outside - the k-th largest), at least 0."""
    kth = jax.lax.top_k(values, k)[0][..., -1]
    lowest_in = jnp.min(jnp.where(mine, values, jnp.inf), axis=-1)
    highest_out = jnp.max(jnp.where(mine, -jnp.inf, values), axis=-1)
    return jnp.maximum(jnp.maximum(kth - lowest_in, highest_out - kth), 0.0)


def group_scores(pick, c):
    """pick (T, E) selection scores -> (T, n_group): a group's score is
    the sum of its two largest (of its largest where it has one
    expert)."""
    g = c["n_group"]
    by_group = pick.reshape(pick.shape[0], g, -1)
    return jnp.sum(jax.lax.top_k(by_group, min(2, by_group.shape[-1]))[0],
                   axis=-1)


def experts(u, fp, taken, c, groups=None):
    """The routed experts held here over u (T, d).  `taken` (T, k) int32:
    the experts the program took, `groups` (T, topk_group) int32 the
    groups it kept (None: those `taken` touches); `taken` None: the
    reference's own, the topk_group groups of largest score (the lower
    index among equals) and the k largest z inside them.  Returns (this
    chip's part of the routed sum, margin (T,), bad (T,) bool).  `margin`:
    with the reference's own routing the smaller of the gap between the
    last expert taken and the first left out inside the kept groups and
    the gap between the last group kept and the first left out, over the
    spread of the token's z; with handed-over routing 1 - the larger of
    how far the kept groups stray from the reference's by their scores
    and how far the experts stray from its top-k inside the handed
    groups, in that unit.  `bad`: not topk_group distinct groups, not k
    distinct experts inside them, or a stray beyond ROUTER_SLACK."""
    k, e = c["num_experts_per_tok"], published_experts(c)
    ng, kept = c["n_group"], c["topk_group"]
    first, count = held_range(c)
    s = jax.nn.sigmoid(u @ fp["router"].astype(F32))             # (T, E)
    pick = s + fp["router_bias"].astype(F32)
    spread = jnp.std(pick, axis=-1)
    gs = group_scores(pick, c)                                   # (T, G)
    group_of = jnp.arange(e) // (e // ng)
    if taken is None:
        top_g, idx_g = jax.lax.top_k(gs, min(kept + 1, ng))
        mine_g = jnp.sum(jax.nn.one_hot(idx_g[:, :kept], ng, dtype=F32),
                         axis=1) > 0
        inside = jnp.where(mine_g[:, group_of], pick, -jnp.inf)
        top, idx = jax.lax.top_k(inside, k + 1)
        taken = idx[:, :k]
        margin = (top[:, k - 1] - top[:, k]) / spread
        if kept < ng:
            margin = jnp.minimum(margin,
                                 (top_g[:, kept - 1] - top_g[:, kept])
                                 / spread)
        bad = jnp.zeros(margin.shape, bool)
    else:
        mine = jnp.sum(jax.nn.one_hot(taken, e, dtype=F32), axis=1) > 0
        if groups is None:      # a program that hands none: those touched
            mine_g = jnp.sum(jax.nn.one_hot(group_of[taken], ng, dtype=F32),
                             axis=1) > 0
            kth = jax.lax.top_k(gs, kept)[0][:, -1]
            far_g = jnp.maximum(
                kth - jnp.min(jnp.where(mine_g, gs, jnp.inf), axis=-1), 0.0)
            bad = jnp.sum(mine_g, axis=-1) > kept
        else:
            mine_g = jnp.sum(jax.nn.one_hot(groups, ng, dtype=F32),
                             axis=1) > 0
            far_g = _stray(gs, mine_g, kept)
            bad = jnp.sum(mine_g, axis=-1) != kept
        inside = jnp.where(mine_g[:, group_of], pick, -jnp.inf)
        stray = jnp.maximum(_stray(inside, mine, k), far_g) / spread
        margin = 1.0 - stray
        bad |= ~(stray <= TOLERANCES["ROUTER_SLACK"]) \
            | (jnp.sum(mine, axis=-1) != k)
    # The bias selects and does not gate; the sum runs over all k taken.
    gates = jnp.take_along_axis(s, taken, axis=-1)               # (T, k)
    gates = F32(c["routed_scaling_factor"]) * gates \
        / jnp.sum(gates, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(taken, e, dtype=F32) * gates[..., None],
                     axis=1)[:, first:first + count]             # (T, held)

    def one(acc, ex):
        gate, up, down, w = ex
        hidden = jax.nn.silu(u @ gate.astype(F32)) * (u @ up.astype(F32))
        return acc + w[:, None] * (hidden @ down.astype(F32)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        fp["w_gate"], fp["w_up"], fp["w_down"], weight.T))
    return out, margin, bad


def shared_expert(u, fp):
    gu = u @ fp["shared_gate_up"].astype(F32)
    f = gu.shape[-1] // 2
    return (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ fp["shared_down"].astype(F32)


def dense_block(x, ap, dp, handed, first, c):
    """A leading layer on one sequence x (T, d)."""
    out, stray, bad = attention(x, ap, c, handed, first)
    x = x + out
    return x + dense_ffn(_rms_norm(x, dp["norm"], c["rms_norm_eps"]), dp), \
        stray, bad


def expert_block(x, ap, fp, taken, groups, handed, first, c):
    """An expert layer on one sequence x (T, d)."""
    out, stray, bad = attention(x, ap, c, handed, first)
    x = x + out
    u = _rms_norm(x, fp["norm"], c["rms_norm_eps"])
    out, margin, wrong = experts(u, fp, taken, c, groups)
    return x + out + shared_expert(u, fp), margin, stray, bad | wrong


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
    block = jit(_head_block)
    out = np.empty((x.shape[0], vocab), np.float32)
    for i in range(n):
        out[:, i * cols:(i + 1) * cols] = block(
            x, w[:, i * cols:(i + 1) * cols], bad)
    return out


def _key(tokens) -> bytes:
    return np.asarray(tokens).astype(np.int32).tobytes()


def forward(params, tokens, c, jit=lambda f: f, routing="handed",
            selection=None, groups=None):
    """tokens (T,) int32 -> (logits (T, V) float32 on the host, margin
    (T,)), one sequence; `margin` is each position's smallest over the
    layers (with handed-over sets: 1 - the largest of the groups', the
    experts' and the selection's stray).  `routing`: "handed" takes what
    `score` left for these tokens, experts, groups and selection (its own
    where nothing was left), None the reference's own, an array (T, expert
    layers, k) those experts, with `groups` (T, expert layers, topk_group)
    the groups kept.  `selection`: (first row, (rows, layers, index_topk))
    the positions the layers attended from that row on.  Parameters are
    cast to float32 at their use, a block of heads and an expert at a
    time, and the output head an eighth of the vocabulary at a time
    (`_head`).  `jit=jax.jit` compiles each kind of layer once and runs it
    per layer."""
    if isinstance(routing, str):
        left = _HANDED.get(_key(tokens))
        routing = None if left is None else left["experts"]
        if left is not None:
            groups = left["groups"]
            if selection is None:
                selection = (left["first"], left["selected"])
    n, nd = c["num_hidden_layers"], c["first_k_dense_replace"]
    dense_fn = jit(functools.partial(dense_block, c=c))
    expert_fn = jit(functools.partial(expert_block, c=c))
    x = params["embed"][tokens].astype(F32)
    t = x.shape[0]
    margin = jnp.full((t,), jnp.inf, F32)
    stray = jnp.zeros((t,), F32)
    bad = jnp.zeros((t,), bool)
    if routing is not None and routing.shape != (
            t, n - nd, c["num_experts_per_tok"]):
        routing, bad = None, ~bad         # not a routing of this model
    if routing is None:
        groups = None
    elif groups is not None and groups.shape != (
            t, n - nd, c["topk_group"]):
        groups, bad = None, ~bad          # not the groups of this model
    if selection is not None:
        first, sel = selection
        sel = np.asarray(sel)
        if not c["index_topk"] or sel.ndim != 3 or sel.shape[1] != n \
                or first < 0 or first + sel.shape[0] > t:
            selection, bad = None, ~bad   # not a selection of this model
    for i in range(n):
        ap = {name: a[i] for name, a in params["attn"].items()}
        handed = first_row = None
        if selection is not None:
            handed = jnp.asarray(sel[:, i], jnp.int32)
            first_row = jnp.int32(first)
        if i < nd:
            x, far, wrong = dense_fn(
                x, ap, {name: a[i] for name, a in params["dense"].items()},
                handed, first_row)
        else:
            x, m, far, wrong = expert_fn(
                x, ap, {name: a[i - nd] for name, a in params["ffn"].items()},
                None if routing is None else jnp.asarray(routing[:, i - nd]),
                None if groups is None else jnp.asarray(groups[:, i - nd]),
                handed, first_row)
            margin = jnp.minimum(margin, m)
        stray, bad = jnp.maximum(stray, far), bad | wrong
    # A lane's selection as a whole: the median stray of the rows that see
    # more than index_topk positions (the others' sets are all they see).
    choosing = jnp.arange(t) + 1 > c["index_topk"]
    typical = float(jnp.nanmedian(jnp.where(choosing, stray, jnp.nan))) \
        if selection is not None and bool(choosing.any()) else 0.0
    if typical > TOLERANCES["SELECT_SLACK_MEDIAN"]:
        bad = ~jnp.zeros_like(bad)
    LAST.update(select_stray=float(jnp.max(stray)),
                select_stray_median=typical,
                route_stray=float(1.0 - jnp.min(margin))
                if routing is not None else 0.0)
    x = jit(functools.partial(_rms_norm, eps=c["rms_norm_eps"]))(
        x, params["final_norm"])
    return _head(x, params["lm_head"], bad, jit), \
        jnp.minimum(margin, 1.0 - stray)


def row_loss(params, row, c, jit=lambda f: f):
    """Mean next-token cross entropy of one row (T+1,), float32."""
    logits, _ = forward(params, row[:-1], c, jit=jit, routing=None)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, row[1:, None], axis=-1)[:, 0]
    return jnp.mean(logz - tgt)


# ---------------------------------------------------------------------------
# the engine's own logits, its routing and its selection
# ---------------------------------------------------------------------------
def score(e, config: dict, seqs, n_prompt: int):
    """The engine's scoring entry: prefill through its own chunk program
    (the launches an idle engine's tick would use, each writing and
    reading the lane's blocks of latent rows and of index keys) and
    teacher-forced steps through the function its burst scans, both
    compiled to hand out the experts they took, the groups they kept and
    the positions their layers attended, at every position, which are kept
    for `forward` under each lane's token ids."""
    got, taken = e.score(seqs, n_prompt, routing=True)
    _HANDED.clear()
    for lane, took in enumerate(taken):
        _HANDED[_key(seqs[lane])] = {
            "experts": np.asarray(took["experts"]),
            "groups": np.asarray(took["groups"]) if "groups" in took
            else None,
            "selected": np.asarray(took["selected"]), "first": 0}
    return got


# ---------------------------------------------------------------------------
# for bench/tools/controls.py
# ---------------------------------------------------------------------------
def control(fault: str, cfg):
    """(the program configuration, a function that undoes what was
    patched) of `sound` or of one fault, each read beside `TOLERANCES`:

      one_group    no groups: the top-k over all published experts
      group_max    a group scored by its largest z, not its two largest
      no_scale     the gates not multiplied by routed_scaling_factor
      top_half     index_topk / 2 positions selected, not index_topk
      keys_fp8     the index keys read as float8_e4m3fn: the nearest
                   precision below the stated `cache_dtype`
      pool_fp8     a stored latent row rounded to float8_e4m3fn"""
    from ray_tpu.models import mla_moe
    from ray_tpu.ops import moe

    def as_fp8(a):
        # float8_e4m3fn's 4 exponent and 3 mantissa bits by an op of its
        # own: a convert there and back is the compiler's to elide
        # (xla_allow_excess_precision), and the chip's did for the keys
        # (my chip run, PR 59, call 2: readings equal to the sound run's
        # to the bit).
        return jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3)

    if fault == "sound":
        return cfg, lambda: None
    if fault == "one_group":
        return dataclasses.replace(cfg, expert_groups=1,
                                   expert_groups_kept=1), lambda: None
    if fault == "no_scale":
        return dataclasses.replace(cfg, route_scale=1.0), lambda: None
    if fault == "top_half":
        return dataclasses.replace(
            cfg, index_top_k=cfg.index_top_k // 2), lambda: None
    if fault == "group_max":
        where, name = moe, "_group_scores"
        patched = functools.partial(jnp.max, axis=-1)
    elif fault == "keys_fp8":
        where, name = mla_moe, "paged_index_scores"
        scan = mla_moe.paged_index_scores

        def patched(q, w, pool, *a):
            return scan(q, w, as_fp8(pool), *a)
    elif fault == "pool_fp8":
        where, name = mla_moe, "_latent_row"
        row = mla_moe._latent_row

        def patched(*a):
            return as_fp8(row(*a))
    else:
        raise SystemExit(f"no fault {fault!r}")
    inner = getattr(where, name)
    setattr(where, name, patched)
    return cfg, lambda: setattr(where, name, inner)


# ---------------------------------------------------------------------------
# Operations and bytes a step needs, from shapes alone: what the
# algorithm requires, not what the program happens to execute.
# ---------------------------------------------------------------------------
_LANE_TILE = 128


def _dims(c: dict) -> dict:
    n, nd = c["num_hidden_layers"], c["first_k_dense_replace"]
    return {"d": c["hidden_size"], "v": c["vocab_size"],
            "h": c["num_attention_heads"], "qr": c["q_lora_rank"],
            "r": c["kv_lora_rank"], "dn": c["qk_nope_head_dim"],
            "dr": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
            "hi": c["index_n_heads"], "di": c["index_head_dim"],
            "top": c["index_topk"],
            "fd": c["intermediate_size"], "f": c["moe_intermediate_size"],
            "fs": c["n_shared_experts"] * c["moe_intermediate_size"],
            "e": published_experts(c), "held": held_range(c)[1],
            "k": c["num_experts_per_tok"], "n": n, "nd": nd, "ne": n - nd}


def _itemsize(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]


def _cache_itemsize(c: dict) -> int:
    return _itemsize(c.get("cache_dtype", c["compute_dtype"]))


def matrix_params(c: dict) -> dict:
    """Matrix parameters of one layer's parts, and of what is held here.
    A token's multiply-adds in the absorbed form are these too: the
    up-projections act on its own query and output, head by head."""
    s = _dims(c)
    d, h = s["d"], s["h"]
    parts = {"attn": d * s["qr"] + s["qr"] * h * (s["dn"] + s["dr"])
             + d * (s["r"] + s["dr"]) + s["r"] * h * (s["dn"] + s["dv"])
             + h * s["dv"] * d,
             "index": s["qr"] * s["hi"] * s["di"] + d * (s["di"] + s["hi"]),
             "dense_ffn": 3 * d * s["fd"], "shared": 3 * d * s["fs"],
             "router": d * s["e"], "expert": 3 * d * s["f"]}
    # every weight outside the routed experts that a step reads once: the
    # head, not the embedding (a gather of the step's rows)
    parts["dense"] = s["n"] * (parts["attn"] + parts["index"]) \
        + s["nd"] * parts["dense_ffn"] \
        + s["ne"] * (parts["shared"] + parts["router"]) + s["v"] * d
    parts["total"] = parts["dense"] + s["v"] * d \
        + s["ne"] * s["held"] * parts["expert"]
    return parts


def expected_held_experts(c: dict, rows: float) -> float:
    """Distinct held experts that `rows` tokens take in one layer: held x
    (1 - (1 - k/E)^rows).  Under groups an expert is still taken by a row
    with probability k/E (its group kept with topk_group / n_group, then
    k of the kept groups' experts), rows apart from each other; the held
    experts of one group are taken or not together more than uniform
    routing would have it, which this count does not see.  (0.25 of 8 for
    one row, 1.8 for eight.)"""
    s = _dims(c)
    return s["held"] * (1.0 - (1.0 - s["k"] / s["e"]) ** rows)


def expert_bytes_per_step(c: dict, lanes: int) -> float:
    """Bytes of expert weights one step of `lanes` tokens needs: the held
    experts taken in every expert layer, each once."""
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


def _row_width(c: dict) -> int:
    """Values a position keeps a layer, as the device holds them: (latent
    | roped key) in whole lane tiles of 128 (576 -> 640;
    families/glm4moelite.py `latent_row_width` says why)."""
    s = _dims(c)
    return -(-(s["r"] + s["dr"]) // _LANE_TILE) * _LANE_TILE


# -- a prefill launch: `tokens` rows that together see `context` --
def _launch_rows(tokens: float, context: float):
    """(rows, the positions the launch's last row sees) of a launch of
    `tokens` rows that together see `context` positions (row p sees
    p + 1)."""
    mean = context / tokens if tokens else 0.0
    return tokens, mean + (tokens - 1) / 2.0


def _seen_sum(tokens: float, context: float, cap: float) -> float:
    """Sum over a launch's rows of min(positions the row sees, cap), the
    rows taken as consecutive."""
    rows, last = _launch_rows(tokens, context)
    first = last - rows + 1
    if last <= cap:
        return context
    if first >= cap:
        return rows * cap
    under = cap - first                # rows that see fewer than cap
    return under * (first + cap - 1) / 2.0 + (rows - under) * cap


def index_flops(c: dict, tokens: float, context: float) -> float:
    """FLOPs of the indexer's score products for a launch: every layer,
    index_n_heads x index_head_dim multiply-adds a row and position it
    sees."""
    s = _dims(c)
    return 2.0 * s["n"] * s["hi"] * s["di"] * context


def index_bytes(c: dict, tokens: float, context: float) -> float:
    """Index keys a launch must move: every layer, the lane's live keys
    read once and the rows' own written."""
    s = _dims(c)
    rows, live = _launch_rows(tokens, context)
    return s["n"] * (live + rows) * s["di"] * _cache_itemsize(c)


def attn_flops(c: dict, tokens: float, context: float) -> float:
    """FLOPs of a launch's read of the selected rows in absorbed form: a
    score kv_lora_rank + qk_rope_head_dim wide and a value kv_lora_rank
    wide a head, row and attended position, over min(positions seen,
    index_topk) a row **whatever the program reads**."""
    s = _dims(c)
    return 2.0 * s["n"] * s["h"] * (2 * s["r"] + s["dr"]) \
        * _seen_sum(tokens, context, s["top"])


def attn_bytes(c: dict, tokens: float, context: float) -> float:
    """Latent rows a launch's read must move at the least: the rows some
    query selected, each once, which are at least min(live, index_topk)
    and taken as that, and the rows' own written."""
    s = _dims(c)
    rows, live = _launch_rows(tokens, context)
    return s["n"] * (min(live, s["top"]) + rows) * _row_width(c) \
        * _cache_itemsize(c)


# -- a decode step: `lanes` rows, one a lane, over `live_kv_tokens` --
def _selected(c: dict, live_kv_tokens: float, lanes: float) -> float:
    """Positions a step's lanes attend a layer: min(a lane's length,
    index_topk) each, the lanes taken at their mean length (the counter
    carries their sum; every lane of the benchmark's traffic is past
    index_topk, where the mean is exact)."""
    return min(live_kv_tokens, lanes * _dims(c)["top"])


def index_flops_per_step(c: dict, live_kv_tokens: float, lanes: int) -> float:
    """FLOPs of one decode step's index scan: every layer, every lane's
    row against every live position of its lane."""
    s = _dims(c)
    return 2.0 * s["n"] * s["hi"] * s["di"] * live_kv_tokens


def index_bytes_per_step(c: dict, live_kv_tokens: float, lanes: int) -> float:
    """Index keys one decode step must move: every layer, every live
    position's key read once and the lanes' new keys written."""
    s = _dims(c)
    return s["n"] * (live_kv_tokens + lanes) * s["di"] * _cache_itemsize(c)


def attn_flops_per_step(c: dict, live_kv_tokens: float, lanes: int) -> float:
    """FLOPs of one decode step's read of the selected rows in absorbed
    form, every layer: each head's score and value over min(length,
    index_topk) positions a lane."""
    s = _dims(c)
    return 2.0 * s["n"] * s["h"] * (2 * s["r"] + s["dr"]) \
        * _selected(c, live_kv_tokens, lanes)


def attn_bytes_per_step(c: dict, live_kv_tokens: float, lanes: int) -> float:
    """Latent rows one decode step's read must move: every layer,
    min(length, index_topk) rows a lane (one query row a lane: no two
    lanes share a row) and the lanes' new rows written."""
    s = _dims(c)
    return s["n"] * (_selected(c, live_kv_tokens, lanes) + lanes) \
        * _row_width(c) * _cache_itemsize(c)


def latent_bytes_per_step(c: dict, live_kv_tokens: float, lanes: int) -> float:
    """What one decode step's attention must read and write of the
    sequences' state: the index keys of every live position and the
    selected latent rows (not every live row: the selection is the point)."""
    return index_bytes_per_step(c, live_kv_tokens, lanes) \
        + attn_bytes_per_step(c, live_kv_tokens, lanes)


def latent_flops_per_step(c: dict, live_kv_tokens: float, lanes: int) -> float:
    """FLOPs of one decode step's attention: the index scan, the selected
    read and, for each lane's new position, the two absorptions (q_n
    W_uk^T and o_lat W_uv, every head)."""
    s = _dims(c)
    return index_flops_per_step(c, live_kv_tokens, lanes) \
        + attn_flops_per_step(c, live_kv_tokens, lanes) \
        + 2.0 * s["n"] * s["h"] * s["r"] * (s["dn"] + s["dv"]) * lanes


def decode_step_bytes(c: dict, live_kv_tokens: float, lanes: int) -> float:
    """Bytes one decode step of `lanes` tokens must move: every weight
    outside the routed experts once (the head once; the embedding is a
    gather), of the held experts those the lanes are expected to take,
    every layer's index keys of the live positions and the latent rows
    the lanes select, as stored."""
    return matrix_params(c)["dense"] * _itemsize(c["param_dtype"]) \
        + expert_bytes_per_step(c, lanes) \
        + latent_bytes_per_step(c, live_kv_tokens, lanes)


# -- how the trace names them -------------------------------------------------
def _pool_rows(c: dict) -> int:
    """Rows of one layer of a pooled leaf laid flat: blocks x block_size,
    the blocks what the engine gives `num_slots` x `max_len` positions and
    the null block."""
    eng = c["engine"]
    return (eng["num_slots"] * eng["max_len"] // eng["block_size"] + 1) \
        * eng["block_size"]


# Table entries a trip of the program's index scan reads
# (`ops/attention.py:_INDEX_GROUP_BLOCKS`): the group of gathered keys is
# the score product's operand, and its shape is how the trace names it.
_INDEX_GROUP_BLOCKS = 64


def index_operand(c: dict):
    """What an op that reads or writes stored index keys shows in its HLO
    text, in a launch and in a decode step alike: the pooled leaf or a
    gathered group of its blocks, [.., block_size, index_head_dim], or
    that group as the score product reads it, [(lanes,) group x
    block_size, index_head_dim(, 1)], as a compiled pattern."""
    s = _dims(c)
    eng = c["engine"]
    bs = eng["block_size"]
    group = min(_INDEX_GROUP_BLOCKS, -(-eng["max_len"] // bs)) * bs
    return re.compile(rf"\[(?:\d+,)+{bs},{s['di']}\]"
                      rf"|\[(?:\d+,)?{group},{s['di']}(?:,1)?\]")


def _fetch_operand(c: dict) -> str:
    """The layers' pool laid flat, [layers, blocks x block_size, row
    width]: the operand of the op that fetches selected rows, and of no
    other (the rows' writes take the pool by block)."""
    s = _dims(c)
    return rf"\[{s['n']},{_pool_rows(c)},{_row_width(c)}\]"


def attn_operand(c: dict):
    """What an op of the read of the selected rows shows in its HLO text:
    the fetch (`_fetch_operand`), the buffer of fetched rows [..,
    index_topk, row width] (both products' operand), or a row's scores of
    them [.., heads, index_topk] (the soft-max between the products), as a
    compiled pattern."""
    s = _dims(c)
    return re.compile(
        rf"\[(?:\d+,)*{s['top']},{_row_width(c)}\]"
        rf"|\[(?:\d+,)*{s['h']},{s['top']}\]|" + _fetch_operand(c))


def select_operand(c: dict):
    """What the ops that choose and fetch the selected rows show in their
    HLO text: the exact top-k over a row's scores (on a TPU a sort of
    float32 scores and what rides with them over the tier of candidates,
    k x 2^j up to the table's width; the switch between the tiers, a
    conditional over the whole row of scores, with them), and the fetch of
    the selected rows (`_fetch_operand`), as a compiled pattern."""
    s = _dims(c)
    eng = c["engine"]
    width = -(-eng["max_len"] // eng["block_size"]) * eng["block_size"]
    tiers = [min(s["top"], width)]
    while tiers[-1] < width:
        tiers.append(min(2 * tiers[-1], width))
    tier = "|".join(map(str, tiers))
    return re.compile(
        rf"f32\[(?:\d+,)*(?:{tier})\][^=]*\bsort\("
        rf"|\bconditional\([^\n]*f32\[(?:\d+,)*{width}\]|"
        + _fetch_operand(c))


def prefill_flops(c: dict, tokens: float, context: float) -> float:
    """FLOPs that `tokens` prompt tokens need which together attend over
    `context` positions (a token at position p sees p + 1): the layers'
    matrices with the held experts a token takes (k x held / E expected),
    the absorptions among them, and **the selection's work as done**: the
    indexer over the whole context, the read over min(seen, index_topk).
    The dense read the selection replaces is not counted.  The output
    head, once a prompt, is left out."""
    s, m = _dims(c), matrix_params(c)
    dense = m["dense"] - s["v"] * s["d"]
    routed = s["ne"] * s["k"] * s["held"] / s["e"] * m["expert"]
    return 2.0 * (dense + routed) * tokens \
        + index_flops(c, tokens, context) + attn_flops(c, tokens, context)


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
