"""The dots3note family: everything the harness knows of dots3-note-prev
(`model_type: dots3_note`, dots-studio), the language model: latent
attention (MLA, arXiv:2405.04434) in two kinds of layer.  A
`full_attention` layer scores every earlier position with a second, small
set of heads (the lightning indexer published with DeepSeek-V3.2-Exp),
keeps the `index_topk` best and attends to those alone; a
`sliding_attention` layer is latent attention at its own sizes (the
`swa_*` keys) over the `sliding_window_size` positions up to its own.
Every layer gates its attention's output by head and rescales its normed
latents; layer 0's FFN is a dense SwiGLU, the rest routed experts chosen
by biased sigmoid scores beside a shared expert.  A configuration file
says `"family": "dots3note"`; what the harness asks of a family is listed
at the top of families/mistral.py.  This one also gives `index_operand` /
`index_flops` / `index_bytes` (for `dsa_index_roofline`), `attn_operand` /
`attn_flops` / `attn_bytes` (`dsa_attn_roofline`), `ring_operand` /
`ring_flops` / `ring_bytes` (`latent_swa_roofline`), `select_operand`
(`dsa_select_share`), `routed_choices_per_row`, the experts' chunk
functions, and `TOLERANCES`, with its measurements beside it.

The model, for layer `l` of `num_hidden_layers` (the first that many
entries of `layer_types`), eps `rms_norm_eps`, no biases on any
projection, untied embedding and head, `u` a position's normed input:

    x = E[token]
    x += Attn_l(RMSNorm(x))
    x += FFN_l(RMSNorm'(x))
    logits = RMSNorm_f(x) W_head

  full    c_q = r_q RMSNorm(u W_qa)  (q_lora_rank), r_q = sqrt(hidden_size
          / q_lora_rank);  q = c_q W_qb, per head (q_n qk_nope_head_dim |
          q_r qk_rope_head_dim);  [c | k_r] = u W_kva  (kv_lora_rank |
          qk_rope_head_dim);  c = r_kv RMSNorm(c), r_kv = sqrt(hidden_size
          / kv_lora_rank);  rope (theta `rope_theta`, rotate-half, all
          qk_rope_head_dim dims) on every head's q_r and on the one k_r;
          k_n = c W_uk[h], v = c W_uv[h].
          indexer: q^I = c_q W^I_qb, index_n_heads x index_head_dim, the
          first qk_rope_head_dim dims of each roped (the same theta);
          k^I = LayerNorm(u W^I_k) (gain and bias, eps
          `assumed.index_norm_eps`), the first qk_rope_head_dim roped;
          w = u W^I_w x index_n_heads^-1/2 x index_head_dim^-1/2;
          I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s]) for s <= t;
          S_t = the index_topk positions s <= t of largest I[t, s] (all
          of them while t < index_topk).
          o_h = softmax_{s in S_t}((q_n,h . k_n,s,h + q_r,h . k_r,s) /
          sqrt(qk_nope_head_dim + qk_rope_head_dim)) v_s,h;
          g = sigmoid(u W_g), one value a head; out = concat_h(g_h o_h) W_o.
  window  the same at the `swa_*` sizes and `swa_rope_theta`, without the
          indexer: position t sees t - sliding_window_size < s <= t.
  dense   l < first_k_dense_replace: (silu(h Wg) * (h Wu)) Wd at width
          intermediate_size.
  experts s = sigmoid(h W_r) in float32 over all published experts; the
          num_experts_per_tok largest of s + b are taken (one group); gates
          g = routed_scaling_factor x s[taken] / sum(s[taken]); each expert
          a SwiGLU at width moe_intermediate_size.  **This chip holds
          `n_routed_experts` of them, from `first_local_expert`**: the
          sum runs over the held experts a token took and the rest of its
          experts is left out, in the program and here alike
          (model-configs guide, section 4).
  shared  a SwiGLU at width n_shared_experts x moe_intermediate_size,
          every token, added to the routed sum.

The reference below is those equations in plain `jax.numpy` float32,
independent of `ray_tpu/`: no kernels, no cache, no batching, no scan over
layers, **attention in the plain (expanded) form** (every position's k_n
and v made from its latent, an explicit mask over the whole context), a
block of heads and a block of query rows at a time so that it fits; the
indexer's scores in blocks of query rows; every held expert evaluated on
every token and weighted (zero where not taken), cast to float32 an expert
at a time.  It shares only the parameter tree's layout, which is data:

    embed (V,d)  lm_head (d,V)  final_norm (d,)
    attn.* stacked over the full layers: norm (.,d)  wq_a (.,d,qr)
        q_norm (.,qr)  wq_b (.,qr,H*(dn+dr))  wkv_a (.,d,r+dr)
        kv_norm (.,r)  w_uk (.,H,dn,r)  w_uv (.,H,r,dv)  wo (.,H*dv,d)
        head_gate (.,d,H)  wq_idx (.,qr,Hi*Di)  wk_idx (.,d,Di)
        k_idx_norm, k_idx_bias (.,Di)  w_idx (.,d,Hi)
    attn_window.* over the window layers, the same names at their sizes,
        without the indexer's
    dense.* over the leading dense layers: norm (.,d)
        w_gate, w_up (.,d,f_dense)  w_down (.,f_dense,d)
    ffn.* over the expert layers: norm (.,d)  router (.,d,E published)
        router_bias (.,E published; float32)  shared_gate_up (.,d,2fs)
        shared_down (.,fs,d)  w_gate, w_up (.,E held,d,f)
        w_down (.,E held,f,d)

Callers run it under `jax.default_matmul_precision("highest")`.

**Routing is handed over**, as in families/glm4moelite.py and for its
reason (`_HANDED`, `ROUTER_SLACK`).  **The selection likewise:** at 16k
candidates the gap between the 2,048th score and the next is under the
program's rounding at every row, so `score` also keeps the positions the
program's full layers attended, at every row.  `forward` computes its own
float32 scores, reads how far a handed set strays from its own
top-`index_topk` in units of the row's score spread (standard deviation
over the positions the row sees), refuses a stray beyond SELECT_SLACK or a
set that is not min(t + 1, index_topk) distinct positions <= t (NaN
logits at that row, which `logits_verdict` refuses), and attends over the
handed set; a row for which nothing was handed over attends over the
reference's own set.  (ISSUE 49 asked for the compared rows' sets alone,
the others attending over the reference's own.  Every row's is handed
over instead: a set that differs by the calls at its edge is another
function at that row, its keys and values in the next full layer follow,
and at a small size, 16 of ~100 positions, that alone read as a median
error of 0.18 against 0.02: tests/test_dsa_moe_serving.py.)  A selection
that ignores the indexer strays by the spread itself.

Assumed (the configuration file lists each under `assumed` with its
ground): the rescale, the indexer's formula and its norm's eps, the
window's edge, one group of experts, the rope's pairing, the seeded
selection bias.  Left out: the vision and audio towers and the
multi-token-prediction module, which next-token logits of text do not
pass through.
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
# and selection (above), so LOGITS_REL_EXPERTS holds all 34 of a run
# (`check`: 2 lanes x (the last of 16,384 prompt positions, prefilled in
# 32 launches of 512 rows through pool, index keys and rings, + 16 decode
# steps): the timed lengths, eight selections deep and 32 windows deep).
# Measured on the chip at published widths, 5 layers, 32 of 256 experts (my
# chip runs, PR 49; the cell's runs of calls D to G1 and the faults' tool,
# calls G2 to G4, `chiprun_in/faults.py` of that session: one engine a
# case, the limits loosened so that every reading prints).
#
# LOGITS_REL_EXPERTS: rms error of a position's logits as a share of the
# reference's own.  ROUTER_SLACK: how far a row's set of experts may stray
# from the reference's, as a share of the spread of the token's selection
# scores s + b.  SELECT_SLACK: the same for the set of positions a full
# layer attends at one row, as a share of the spread (standard deviation
# over the positions the row sees) of the row's index scores.
# SELECT_SLACK_MEDIAN: the median of that stray over all rows of a lane
# that see more than index_topk positions (32,000 row-layers a lane), past
# which the whole lane is refused.
#   The program as it is, 14 seeds (eleven runs of the cell, three of the
#   tool): a position's error has medians 0.0184-0.0192 and a largest a
#   seed of 0.0205-0.0234; over the compared rows the larger of the two
#   strays has medians 0.042-0.043 and a largest a seed of 0.059-0.084; a
#   row's selection strays by at most 0.094-0.111 over every row of a lane,
#   and **the lane's median stray reads 0.0399-0.0401 on all four lanes
#   read**.
#   **The index keys kept in 8-bit floats** (float8_e4m3fn, the nearest
#   precision below the stated `cache_dtype`, rounded by eager ops after
#   every prefill launch and decode step; three seeds): the errors do not
#   move (medians 0.0188-0.0194, largest 0.0207-0.0211: with seeded weights
#   which 2,048 of 16k positions a row attends hardly reaches the logits, as
#   the first fault below shows), the compared rows' stray has medians
#   0.097-0.103 and a largest of 0.126-0.180, any row's 0.19-0.21, and **the
#   lane's median 0.0922-0.0929 on all four lanes read**: every run refused,
#   by SELECT_SLACK_MEDIAN alone.  0.065 lies between 0.040 and 0.092 with a
#   factor of 1.6 below and 1.4 above, on two readings that each hold to a
#   hundredth over seeds and lanes.  (A limit a row, which this family had
#   first, would have had to lie between 0.084 and 0.126, a factor of 1.2
#   either side; SELECT_SLACK stays for a row's gross faults, at 0.3, 2.7
#   times the sound runs' largest over any row.)  **Pool, keys and rings in
#   8-bit floats** (one seed): errors 0.0589-0.0784, every position over the
#   limit; strays 0.21-0.29; the lane's median 0.42.  0.05 lies between 0.0234
#   and 0.0589, a factor of 2.1 below and 1.2 above, and 2.9 under the
#   dropped expert's 0.145.
#   At these widths the check also refuses, each read on the chip on one
#   seed (call G2; error median, largest; compared rows' stray median,
#   largest): **the last 2,048 positions in place of the best** (0.0188,
#   0.0214: the logits do not move; 4.91, 5.38), **top-1,024** (not 2,048
#   distinct positions: NaN at every row), **the relu dropped** (0.0188,
#   0.0219; 1.86, 2.48; the lane's median 1.78), **the weights w dropped**
#   (0.0191, 0.0215; 5.20, 6.26), **the head gate dropped** (0.910, 0.969),
#   **the rescale dropped** (1.316, 1.342), **one held expert's output
#   dropped** (0.0194, 0.1448: 7 of 34 positions over a limit, strays to
#   0.137).
#   **What it does not see at these widths, each read there too:** the
#   window one position short (0.0194, 0.0216; 0.043, 0.074: one position of
#   513 under seeded weights is under the rounding), and **the rings alone
#   in 8-bit floats** (0.0192, 0.0218; 0.042, 0.074: three layers' rounding
#   of 513 near-equally weighted rows averages out; with the pool and the
#   keys it is refused, above).  tests/test_dsa_moe_serving.py holds both
#   at a tiny size (the window in float32: thousands of times the exact
#   run's error; the rings in bfloat16: 0.066 against 0.034), with the other
#   faults.  Nor: a layer computed in bfloat16 where the configuration says
#   bfloat16; a router or an indexer wrong by less than its slack
#   everywhere.
TOLERANCES = {"LOGITS_REL_EXPERTS": 0.05, "ROUTER_SLACK": 0.2,
              "SELECT_SLACK": 0.3, "SELECT_SLACK_MEDIAN": 0.065}

_KINDS = {"sliding_attention": "window", "full_attention": "full"}
# What `score` handed over, by a lane's token ids (int32 bytes): the
# experts every position took (T, L_e, k), the positions the rows
# attended (rows, full layers, index_topk), and the first such row.
_HANDED: dict = {}
# The largest stray of a handed selection that the last `forward` read (a
# tool's to print; nothing is decided by it).
LAST = {"select_stray": 0.0, "select_stray_median": 0.0}


# ---------------------------------------------------------------------------
# configuration file -> the program
# ---------------------------------------------------------------------------
def layer_kinds(config: dict) -> list:
    """The kinds of the layers that are run: the first `num_hidden_layers`
    entries of the published `layer_types`."""
    n = config["num_hidden_layers"]
    kinds = list(config["layer_types"][:n])
    if len(kinds) < n or set(kinds) - set(_KINDS):
        raise SpecError(f"layer_types must name {n} layers of "
                        f"{sorted(_KINDS)}")
    return kinds


def published_experts(config: dict) -> int:
    """The router's width: the published count of routed experts, of
    which `n_routed_experts` are held here."""
    return int(config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"]))


def held_range(config: dict):
    """(first, count) of the published experts that this chip holds."""
    return int(config.get("first_local_expert", 0)), \
        int(config["n_routed_experts"])


def _period(kinds: list) -> list:
    """The shortest period that `kinds` repeats, its last one cut short
    where they are not whole periods."""
    for p in range(1, len(kinds) + 1):
        if kinds == (kinds[:p] * len(kinds))[:len(kinds)]:
            return kinds[:p]
    return kinds


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

    try:
        from ray_tpu.models.mla_moe import MLAMoEConfig
    except ImportError:
        MLAMoEConfig = None
    needs = {"layer_pattern", "lead_pattern", "window", "n_heads_window",
             "kv_rank_window", "attn_gate", "latent_rescale", "index_heads",
             "index_dim", "index_top_k"}
    lacks = needs - ({f.name for f in dataclasses.fields(MLAMoEConfig)}
                     if MLAMoEConfig else set())
    if lacks:
        _withdraw_app()
        raise SpecError(
            f"this program's MLAMoEConfig has no {sorted(lacks)}: it cannot "
            f"run a configuration of the dots3note family")
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("topk_method", "noaux_tc"), ("norm_topk_prob", True),
                      ("scoring_func", "sigmoid"), ("rope_scaling", None),
                      ("attention_gate_type", "headwise"),
                      ("swa_attention_gate_type", "headwise"),
                      ("apply_mla_qkv_lora_rescale", True),
                      ("moe_layer_freq", 1), ("tie_word_embeddings", False)):
        if config[key] != want:
            raise SpecError(f"{key} = {config[key]!r}: the program's layers "
                            f"are {key} = {want!r}")
    for heads, kv in (("num_attention_heads", "num_key_value_heads"),
                      ("swa_num_attention_heads", "swa_num_key_value_heads")):
        if config[heads] != config[kv]:
            raise SpecError(f"latent attention has one key and value a "
                            f"head: {kv} = {heads}")
    if config["swa_qk_rope_head_dim"] != config["qk_rope_head_dim"]:
        raise SpecError("the program ropes both kinds' keys at one width")
    kinds = [_KINDS[k] for k in layer_kinds(config)]
    lead = config["first_k_dense_replace"]
    first, count = held_range(config)
    e = published_experts(config)
    return MLAMoEConfig(
        name=config["name"],
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_dense_layers=lead,
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
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        lead_pattern=tuple(kinds[:lead]),
        layer_pattern=tuple(_period(kinds[lead:])),
        window=config["sliding_window_size"],
        n_heads_window=config["swa_num_attention_heads"],
        q_rank_window=config["swa_q_lora_rank"],
        kv_rank_window=config["swa_kv_lora_rank"],
        d_nope_window=config["swa_qk_nope_head_dim"],
        d_v_window=config["swa_v_head_dim"],
        rope_theta_window=float(config["swa_rope_theta"]),
        attn_gate=True,
        latent_rescale=True,
        index_heads=config["index_n_heads"],
        index_dim=config["index_head_dim"],
        index_top_k=config["index_topk"],
        index_norm_eps=float(config["assumed"]["index_norm_eps"]),
        param_dtype=jnp.dtype(config["param_dtype"]),
        compute_dtype=jnp.dtype(config["compute_dtype"]))


def init_params(key, cfg):
    """The program's own initialiser (bench/harness/device.py calls it
    inside one jitted call, on the chip's `rbg` key)."""
    return cfg.init_params(key)


# ---------------------------------------------------------------------------
# the plain float32 reference
# ---------------------------------------------------------------------------
def sizes(c: dict, kind: str) -> dict:
    """The attention sizes of a layer of `kind`, by short names."""
    pre = "swa_" if kind == "sliding_attention" else ""
    d = c["hidden_size"]
    qr, r = c[pre + "q_lora_rank"], c[pre + "kv_lora_rank"]
    return {"h": c[pre + "num_attention_heads"], "qr": qr, "r": r,
            "dn": c[pre + "qk_nope_head_dim"],
            "dr": c[pre + "qk_rope_head_dim"], "dv": c[pre + "v_head_dim"],
            "theta": float(c[pre + "rope_theta"]),
            "r_q": (d / qr) ** 0.5, "r_kv": (d / r) ** 0.5}


def _rms_norm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(F32)


def _layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain.astype(F32) \
        + bias.astype(F32)


def _rope(x, theta, width=None):
    """x (T, heads, hd): rotate pairs (i, i + width/2) of the first `width`
    dimensions (all of them by default) by pos * theta^(-2i/width), the
    rest as they are."""
    t, _, hd = x.shape
    width = width or hd
    half = width // 2
    inv = F32(theta) ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]       # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:width]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., width:]], -1)


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
    dr, theta = c["qk_rope_head_dim"], c["rope_theta"]
    key = _layer_norm(u @ p["wk_idx"].astype(F32), p["k_idx_norm"],
                      p["k_idx_bias"], c["assumed"]["index_norm_eps"])
    key = _rope(key[:, None, :], theta, dr)[:, 0]                # (T, Di)
    q = _rope((cq @ p["wq_idx"].astype(F32)).reshape(t, hi, di), theta, dr)
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
    (n, k) int32, against the reference's own `scores` (n, T) of those
    rows, _INDEX_BLOCK rows at a time.  Returns (mask (n, T) bool of the
    handed positions a row sees, stray (n,): how far the set strays from
    the reference's top-k in units of the row's score spread, bad (n,):
    not min(t + 1, k) distinct positions <= t, or a stray beyond
    SELECT_SLACK)."""
    n, t = scores.shape

    def block(xs):
        scores, handed, at = xs             # (b, T), (b, k), (b,)
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


def attention(x, p, c, kind, handed=None, first=None):
    """x (T, d) -> (Attn(RMSNorm(x)) (T, d), stray (T,), bad (T,)) of a
    layer of `kind` in the plain form: every position's per-head keys and
    values expanded from its latent, _HEAD_BLOCK heads at a time, a
    masked soft-max over the whole context, _QUERY_BLOCK query rows at a
    time.  `handed` (n, index_topk) with `first` (a traced scalar): the
    positions the program attended for rows `first` .. of a full layer;
    `stray` and `bad` are what `handed_selection` reads there, 0 and
    False elsewhere."""
    s = sizes(c, kind)
    h, r, dn, dr, dv = s["h"], s["r"], s["dn"], s["dr"], s["dv"]
    eps, theta = c["rms_norm_eps"], s["theta"]
    u = _rms_norm(x, p["norm"], eps)
    t = u.shape[0]
    cq = F32(s["r_q"]) * _rms_norm(u @ p["wq_a"].astype(F32), p["q_norm"],
                                   eps)
    ckr = u @ p["wkv_a"].astype(F32)
    lat = F32(s["r_kv"]) * _rms_norm(ckr[:, :r], p["kv_norm"], eps)
    k_r = _rope(ckr[:, None, r:], theta)[:, 0]                   # (T, dr)
    at = jnp.arange(t)
    stray, bad = jnp.zeros((t,), F32), jnp.zeros((t,), bool)
    if kind == "sliding_attention":
        seen = (at[None, :] <= at[:, None]) \
            & (at[None, :] > at[:, None] - c["sliding_window_size"])
    elif c["index_topk"]:
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
    scale = F32((dn + dr) ** -0.5)
    gate = jax.nn.sigmoid(u @ p["head_gate"].astype(F32))        # (T, H)
    hb = _HEAD_BLOCK if h % _HEAD_BLOCK == 0 else h
    seen_blocks = _blocks(seen, _QUERY_BLOCK)

    def heads(acc, ws):
        wq_b, w_uk, w_uv, wo, g = ws   # (qr,hb,dn+dr) (hb,dn,r) (hb,r,dv)
        q = jnp.einsum("tr,rhe->the", cq, wq_b.astype(F32))
        q_n, q_r = q[..., :dn], _rope(q[..., dn:], theta)
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
        out = out.reshape(-1, hb, dv)[:t] * g.T[:, :, None]
        return acc + out.reshape(t, hb * dv) @ wo.astype(F32).reshape(
            hb * dv, -1), None

    nb = h // hb
    out, _ = jax.lax.scan(heads, jnp.zeros_like(x), (
        jnp.moveaxis(p["wq_b"].reshape(-1, nb, hb, dn + dr), 1, 0),
        p["w_uk"].reshape(nb, hb, dn, r), p["w_uv"].reshape(nb, hb, r, dv),
        p["wo"].reshape(nb, hb, dv, -1), gate.T.reshape(nb, hb, t)))
    return out, stray, bad


def dense_ffn(u, dp):
    return (jax.nn.silu(u @ dp["w_gate"].astype(F32))
            * (u @ dp["w_up"].astype(F32))) @ dp["w_down"].astype(F32)


def experts(u, fp, taken, c):
    """The routed experts held here over u (T, d).  `taken` (T, k) int32:
    the experts the program took (None: the reference's own top-k of
    s + b).  Returns (this chip's part of the routed sum, margin (T,),
    bad (T,) bool), as families/glm4moelite.py `experts`."""
    k, e = c["num_experts_per_tok"], published_experts(c)
    first, count = held_range(c)
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
    # The bias selects and does not gate.
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


def dense_block(x, ap, dp, handed, first, c, kind):
    """A leading layer on one sequence x (T, d)."""
    out, stray, bad = attention(x, ap, c, kind, handed, first)
    x = x + out
    return x + dense_ffn(_rms_norm(x, dp["norm"], c["rms_norm_eps"]), dp), \
        stray, bad


def expert_block(x, ap, fp, taken, handed, first, c, kind):
    """An expert layer on one sequence x (T, d)."""
    out, stray, bad = attention(x, ap, c, kind, handed, first)
    x = x + out
    u = _rms_norm(x, fp["norm"], c["rms_norm_eps"])
    out, margin, wrong = experts(u, fp, taken, c)
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
            selection=None):
    """tokens (T,) int32 -> (logits (T, V) float32 on the host, margin
    (T,)), one sequence; `margin` is each position's smallest over the
    expert layers (with handed-over sets: 1 - the larger of the experts'
    and the selection's stray).  `routing`: "handed" takes what `score`
    left for these tokens, experts and selection (its own where nothing
    was left), None the reference's own, an array (T, expert layers, k)
    those experts.  `selection`: (first row, (rows, full layers,
    index_topk)) the positions the full layers attended from that row on
    (with "handed": what `score` left).  Parameters are cast to float32 at
    their use, a block of heads and an expert at a time, and the output
    head an eighth of the vocabulary at a time (`_head`).  `jit=jax.jit`
    compiles each kind of layer once and runs it per layer."""
    if isinstance(routing, str):
        left = _HANDED.get(_key(tokens))
        routing = None if left is None else left["experts"]
        if selection is None and left is not None:
            selection = (left["first"], left["selected"])
    kinds, nd = layer_kinds(c), c["first_k_dense_replace"]
    dense_fn = {k: jit(functools.partial(dense_block, c=c, kind=k))
                for k in set(kinds[:nd])}
    expert_fn = {k: jit(functools.partial(expert_block, c=c, kind=k))
                 for k in set(kinds[nd:])}
    x = params["embed"][tokens].astype(F32)
    t = x.shape[0]
    margin = jnp.full((t,), jnp.inf, F32)
    stray = jnp.zeros((t,), F32)
    bad = jnp.zeros((t,), bool)
    n_full = kinds.count("full_attention")
    if routing is not None and routing.shape != (
            t, len(kinds) - nd, c["num_experts_per_tok"]):
        routing, bad = None, ~bad         # not a routing of this model
    if selection is not None:
        first, sel = selection
        sel = np.asarray(sel)
        if not c["index_topk"] or sel.ndim != 3 or sel.shape[1] != n_full \
                or first < 0 or first + sel.shape[0] > t:
            selection, bad = None, ~bad   # not a selection of this model
    seen = {"full_attention": 0, "sliding_attention": 0}
    for i, kind in enumerate(kinds):
        rank = seen[kind]
        seen[kind] += 1
        stack = params["attn" if kind == "full_attention" else "attn_window"]
        ap = {name: a[rank] for name, a in stack.items()}
        handed = first_row = None
        if selection is not None and kind == "full_attention":
            handed = jnp.asarray(sel[:, rank], jnp.int32)
            first_row = jnp.int32(first)
        if i < nd:
            x, far, wrong = dense_fn[kind](
                x, ap, {name: a[i] for name, a in params["dense"].items()},
                handed, first_row)
        else:
            x, m, far, wrong = expert_fn[kind](
                x, ap, {name: a[i - nd] for name, a in params["ffn"].items()},
                None if routing is None else jnp.asarray(routing[:, i - nd]),
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
                select_stray_median=typical)
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
    reading the lane's blocks of latent rows and of index keys and its
    slot's rings) and teacher-forced steps through the function its burst
    scans, both compiled to hand out the experts they took and the
    positions their full layers attended, at every position, which are
    kept for `forward` under each lane's token ids."""
    got, taken = e.score(seqs, n_prompt, routing=True)
    _HANDED.clear()
    for lane, took in enumerate(taken):
        _HANDED[_key(seqs[lane])] = {
            "experts": np.asarray(took["experts"]),
            "selected": np.asarray(took["selected"]), "first": 0}
    return got


# ---------------------------------------------------------------------------
# Operations and bytes a step needs, from shapes alone: what the
# algorithm requires, not what the program happens to execute.
# ---------------------------------------------------------------------------
_LANE_TILE = 128
_RING_TILE = 16


def _dims(c: dict) -> dict:
    kinds, nd = layer_kinds(c), c["first_k_dense_replace"]
    return {"d": c["hidden_size"], "v": c["vocab_size"],
            "full": sizes(c, "full_attention"),
            "slide": sizes(c, "sliding_attention"),
            "n_full": kinds.count("full_attention"),
            "n_slide": kinds.count("sliding_attention"),
            "hi": c["index_n_heads"], "di": c["index_head_dim"],
            "top": c["index_topk"], "window": c["sliding_window_size"],
            "fd": c["intermediate_size"], "f": c["moe_intermediate_size"],
            "fs": c["n_shared_experts"] * c["moe_intermediate_size"],
            "e": published_experts(c), "held": held_range(c)[1],
            "k": c["num_experts_per_tok"], "n": len(kinds), "nd": nd,
            "ne": len(kinds) - nd}


def _itemsize(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]


def _cache_itemsize(c: dict) -> int:
    return _itemsize(c.get("cache_dtype", c["compute_dtype"]))


def matrix_params(c: dict) -> dict:
    """Matrix parameters of the run layers' parts, and of what is held
    here.  A token's multiply-adds in the absorbed form are these too: the
    up-projections act on its own query and output, head by head."""
    s = _dims(c)
    d = s["d"]

    def attn(k):
        return d * k["qr"] + k["qr"] * k["h"] * (k["dn"] + k["dr"]) \
            + d * (k["r"] + k["dr"]) + k["r"] * k["h"] * (k["dn"] + k["dv"]) \
            + k["h"] * k["dv"] * d + d * k["h"]

    index = s["full"]["qr"] * s["hi"] * s["di"] + d * (s["di"] + s["hi"])
    parts = {"attn_full": attn(s["full"]) + index,
             "attn_window": attn(s["slide"]),
             "dense_ffn": 3 * d * s["fd"], "shared": 3 * d * s["fs"],
             "router": d * s["e"], "expert": 3 * d * s["f"]}
    # every weight outside the routed experts that a step reads once: the
    # head, not the embedding (a gather of the step's rows)
    parts["dense"] = s["n_full"] * parts["attn_full"] \
        + s["n_slide"] * parts["attn_window"] \
        + s["nd"] * parts["dense_ffn"] \
        + s["ne"] * (parts["shared"] + parts["router"]) + s["v"] * d
    parts["total"] = parts["dense"] + s["v"] * d \
        + s["ne"] * s["held"] * parts["expert"]
    return parts


def expected_held_experts(c: dict, rows: float) -> float:
    """Distinct held experts that `rows` tokens take in one layer under
    uniform routing: held x (1 - (1 - k/E)^rows).  (1 of 32 for one row,
    7.2 for eight, all 32 from some 150 rows on.)"""
    s = _dims(c)
    return s["held"] * (1.0 - (1.0 - s["k"] / s["e"]) ** rows)


def expert_bytes_per_step(c: dict, lanes: int) -> float:
    """Bytes of expert weights one step of `lanes` tokens needs: the held
    experts taken in every expert layer, each once."""
    s = _dims(c)
    return s["ne"] * expected_held_experts(c, lanes) \
        * matrix_params(c)["expert"] * _itemsize(c["param_dtype"])


def expert_bytes_per_chunk(c: dict, tokens: float) -> float:
    """Bytes of expert weights a prefill chunk of `tokens` needs."""
    return expert_bytes_per_step(c, tokens)


def expert_flops_per_chunk(c: dict, tokens: float) -> float:
    """FLOPs of the routed rows of a chunk: a token takes k experts of
    which held / E are here."""
    s = _dims(c)
    return 2.0 * s["ne"] * tokens * s["k"] * s["held"] / s["e"] \
        * matrix_params(c)["expert"]


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


def _row_width(k: dict) -> int:
    """Values a position keeps in a layer of these sizes, as the device
    holds them: (latent | roped key) in whole lane tiles of 128 (576 ->
    640, 1,088 -> 1,152; families/glm4moelite.py `latent_row_width` says
    why)."""
    return -(-(k["r"] + k["dr"]) // _LANE_TILE) * _LANE_TILE


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
    """FLOPs of the indexer's score products for a launch: every full
    layer, index_n_heads x index_head_dim multiply-adds a row and position
    it sees."""
    s = _dims(c)
    return 2.0 * s["n_full"] * s["hi"] * s["di"] * context


def index_bytes(c: dict, tokens: float, context: float) -> float:
    """Index keys a launch must move: every full layer, the lane's live
    keys read once and the rows' own written."""
    s = _dims(c)
    rows, live = _launch_rows(tokens, context)
    return s["n_full"] * (live + rows) * s["di"] * _cache_itemsize(c)


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
    text: the pooled leaf or a gathered group of its blocks, [..,
    block_size, index_head_dim], or that group as the score product reads
    it, [group x block_size, index_head_dim(, 1)], as a compiled pattern."""
    s = _dims(c)
    eng = c["engine"]
    bs = eng["block_size"]
    group = min(_INDEX_GROUP_BLOCKS, -(-eng["max_len"] // bs)) * bs
    return re.compile(rf"\[(?:\d+,)+{bs},{s['di']}\]"
                      rf"|\[{group},{s['di']}(?:,1)?\]")


def attn_flops(c: dict, tokens: float, context: float) -> float:
    """FLOPs of the full layers' read of the selected rows in absorbed
    form: a score kv_lora_rank + qk_rope_head_dim wide and a value
    kv_lora_rank wide a head, row and attended position, over
    min(positions seen, index_topk) a row **whatever the program reads**."""
    s = _dims(c)
    k = s["full"]
    return 2.0 * s["n_full"] * k["h"] * (2 * k["r"] + k["dr"]) \
        * _seen_sum(tokens, context, s["top"])


def attn_bytes(c: dict, tokens: float, context: float) -> float:
    """Latent rows the full layers' read must move at the least: the rows
    some query selected, each once, which are at least min(live,
    index_topk) and taken as that (the union of a launch's sets is the
    program's to know), and the rows' own written."""
    s = _dims(c)
    rows, live = _launch_rows(tokens, context)
    return s["n_full"] * (min(live, s["top"]) + rows) \
        * _row_width(s["full"]) * _cache_itemsize(c)


def _fetch_operand(c: dict) -> str:
    """The full layers' pool laid flat, [layers, blocks x block_size, row
    width]: the operand of the op that fetches selected rows, and of no
    other (the rows' writes take the pool by block)."""
    s = _dims(c)
    return rf"\[{s['n_full']},{_pool_rows(c)},{_row_width(s['full'])}\]"


def attn_operand(c: dict):
    """What an op of the read of the selected rows shows in its HLO text:
    the fetch (`_fetch_operand`), the buffer of fetched rows [..,
    index_topk, row width] (both products' operand), or a row's scores of
    them [.., heads, index_topk] (the soft-max between the products), as a
    compiled pattern."""
    s = _dims(c)
    return re.compile(
        rf"\[(?:\d+,)*{s['top']},{_row_width(s['full'])}\]"
        rf"|\[(?:\d+,)*{s['full']['h']},{s['top']}\]|" + _fetch_operand(c))


def ring_rows(c: dict) -> int:
    """Rows of a window layer's ring: a window and a chunk in whole
    sublane tiles, as the program lays it out."""
    need = c["sliding_window_size"] + c["engine"]["prefill_chunk"]
    return -(-need // _RING_TILE) * _RING_TILE


def ring_flops(c: dict, tokens: float, context: float) -> float:
    """FLOPs of the window layers' read in absorbed form, over
    min(positions seen, window) a row."""
    s = _dims(c)
    k = s["slide"]
    return 2.0 * s["n_slide"] * k["h"] * (2 * k["r"] + k["dr"]) \
        * _seen_sum(tokens, context, s["window"])


def ring_bytes(c: dict, tokens: float, context: float) -> float:
    """Ring rows a launch must move: every window layer, the window before
    the launch's rows read once and the rows' own written and read."""
    s = _dims(c)
    rows, live = _launch_rows(tokens, context)
    return s["n_slide"] * (min(live, s["window"] + rows) + rows) \
        * _row_width(s["slide"]) * _cache_itemsize(c)


def ring_operand(c: dict):
    """What an op of the window layers' read shows in its HLO text: an
    array whose trailing dimensions are a ring's, [.., ring rows, row
    width], or a row's scores of a ring, [.., heads, ring rows], as a
    compiled pattern."""
    s = _dims(c)
    return re.compile(rf"\[(?:\d+,)*{ring_rows(c)},"
                      rf"{_row_width(s['slide'])}\]"
                      rf"|\[(?:\d+,)*{s['slide']['h']},{ring_rows(c)}\]")


def select_operand(c: dict):
    """What the ops that choose and fetch the selected rows show in their
    HLO text: the exact top-k over a row's scores (on a TPU a sort of
    float32 scores and what rides with them over the tier of candidates,
    k x 2^j up to the table's width, or over the bests of a tier's spans,
    2 k x spans; the switch between the tiers, a conditional over the
    whole row of scores, with them), and the fetch of the selected rows
    (`_fetch_operand`), as a compiled pattern."""
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
    indexer over the whole context, the full layers' read over
    min(seen, index_topk), the window layers' over min(seen, window).  The
    dense read the selection replaces is not counted, so no share of a
    roofline passes 100% for work that was not asked for.  The output
    head, once a prompt, is left out."""
    s, m = _dims(c), matrix_params(c)
    dense = m["dense"] - s["v"] * s["d"]
    routed = s["ne"] * s["k"] * s["held"] / s["e"] * m["expert"]
    return 2.0 * (dense + routed) * tokens \
        + index_flops(c, tokens, context) + attn_flops(c, tokens, context) \
        + ring_flops(c, tokens, context)


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
