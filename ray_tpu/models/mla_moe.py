"""Latent attention (MLA, arXiv:2405.04434) with scored experts on the
served path: a decoder whose KV cache is one low-rank row a position a
layer, read in absorbed form; whose leading layers have a dense FFN and
the rest one rank's share of an expert-parallel FFN chosen by biased
sigmoid scores, beside a shared expert (the layout of the `glm4_moe_lite`
and `deepseek_v3` config families).  `models.hybrid` and
`models.mamba2_moe` are its siblings under the same protocol; unlike
theirs, its sequences are pool blocks alone (`state_by_slot` false), so
the engine shares prefixes, copies on write, speculates and ships frames
as it does for a `TransformerConfig`.

The stack, pre-norm residual, RMSNorm throughout:

    x = E[token]
    x += MLA_l(RMSNorm(x))
    x += FFN_l(RMSNorm'(x))            SwiGLU of width d_ff for l <
                                       n_dense_layers, else experts_l +
                                       shared_l
    logits = RMSNorm_f(x) W_head       (untied)

MLA, H heads, for a position's normed input u:

    c_q = RMSNorm(u W_qa)                       (q_rank)
    q   = c_q W_qb       per head (q_n d_nope | q_r d_rope)
    [c | k_r] = u W_kva                         (kv_rank | d_rope)
    c   = RMSNorm(c);  rope (rotate-half, all d_rope dims) on every
          head's q_r and on the one k_r, which the heads share
    plain form:  k_n = c W_uk[h], v = c W_uv[h];
          softmax((q_n . k_n + q_r . k_r) / sqrt(d_nope + d_rope)) v, W_o

**The served programs run the absorbed form**, chunk and burst alike:
q_lat = q_n W_uk[h]^T (kv_rank), score = (q_lat . c + q_r . k_r) *
scale, o_lat = P c, o = o_lat W_uv[h].  Equal to the plain form up to
rounding (the up-projections move from the keys and values of every
cached position to the queries and outputs of the few new ones), and
the pool then holds `(c | k_r)` after norm and rope and nothing else:
kv_rank + d_rope values a position a layer, whatever H is.  Attention is
multi-query: H query rows of that width against one stored row whose
first kv_rank columns are also the value
(`ops.attention.paged_latent_attention`: a chunk through the block loop
`paged_attention` has, a decode step lowered for a TPU through a Pallas
kernel a layer that copies a lane's live blocks to VMEM, each once for
both products, and nothing else; lowered for anything else through the
loop too).

**A stored row is padded to whole lane tiles** (`row_width`: 576 ->
640).  AOT for a described v5e at the published widths (PR 40; 12
layers, 8,193 blocks of 16, `memory_analysis()` of the width-8 burst
and the 128-row chunk): with flat rows of 576 the pool is a 1.81 GB
array that the programs hold as a 1.84 GB argument **and a 2.02 GB
temporary**: the compiler wants the rows in whole tiles for the
products and copies the pool whole into that layout around every step,
PR 30's finding again; the roped key in a pool of its own (rows of 64,
half a tile) is copied likewise.  Padded, the pool is 2.01 GB, aliased
in and out, and the temporaries are 0.03 GB (burst) and 0.001 GB
(chunk): nothing is copied.  The padding costs 0.2 GB of memory and a
ninth more bytes read a step than the 576 values need; the bytes a
step reads are the 640 stored.

Experts: `ops.moe.moe_mlp_dropless` with `MoEConfig.scoring =
"sigmoid"`: s = sigmoid(router logits) in float32, the `expert_top_k`
largest of s + `router_bias` are taken, gated by their own s
renormalised times `route_scale`; `experts_held` as in
`models.mamba2_moe` (the router at its published width, the stacks the
share).  The shared expert is a dense SwiGLU every token takes, added
once whatever the share.

What a sequence keeps (`LatentState`): `kv` (n_layers, N_blocks,
block_size, row_width), paged as ever, block 0 the null block.
Parameters: `attn.*` stacked over all layers, `dense.*` over the leading
dense layers, `ffn.*` over the expert layers.  The up-projections are
stored by head, `w_uk` (H, d_nope, kv_rank) and `w_uv` (H, kv_rank,
d_v), so that neither form slices a matrix inside a step.  Multi-token
prediction modules are not here: the published forward pass for
next-token logits is the layers above.  Training is not here.
"""
from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import paged_latent_attention
from ray_tpu.ops.moe import MoEConfig, moe_mlp_dropless, routed_zero
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rotary import apply_rope

F32 = jnp.float32
_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")
_LANE_TILE = 128


@dataclasses.dataclass(frozen=True)
class MLAMoEConfig:
    vocab_size: int = 154880
    d_model: int = 2048
    n_layers: int = 47
    n_dense_layers: int = 1
    n_heads: int = 20
    q_rank: int = 768
    kv_rank: int = 512
    d_nope: int = 192
    d_rope: int = 64
    d_v: int = 256
    d_ff: int = 10240                   # the leading dense layers' width
    n_experts: int = 64
    expert_top_k: int = 4
    d_expert: int = 1536
    d_shared: int = 1536
    route_scale: float = 1.8
    # (first, count) of the n_experts held here; None: all of them.
    experts_held: Optional[Tuple[int, int]] = None
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 202752
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    name: str = "mla-moe"

    # The blocks are the sequence: nothing is kept by the engine's slot.
    state_by_slot: ClassVar[bool] = False
    recurrent: ClassVar[bool] = False

    def __post_init__(self):
        if not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError("n_dense_layers leading layers, then at "
                             "least one expert layer")
        self.moe                        # MoEConfig checks the held range

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def row_width(self) -> int:
        """A stored row: (latent | roped key) padded to whole lane tiles."""
        return -(-(self.kv_rank + self.d_rope) // _LANE_TILE) * _LANE_TILE

    @property
    def attention_scale(self) -> float:
        return (self.d_nope + self.d_rope) ** -0.5

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def moe(self) -> MoEConfig:
        return MoEConfig(num_experts=self.n_experts, top_k=self.expert_top_k,
                         held=self.experts_held, scoring="sigmoid",
                         route_scale=self.route_scale)

    @property
    def num_params(self) -> int:
        d, h = self.d_model, self.n_heads
        attn = d * self.q_rank + self.q_rank * h * (self.d_nope + self.d_rope) \
            + d * (self.kv_rank + self.d_rope) \
            + self.kv_rank * h * (self.d_nope + self.d_v) + h * self.d_v * d
        ffn = d * self.n_experts + 3 * d * self.d_shared \
            + self.held[1] * 3 * d * self.d_expert
        return (2 * self.vocab_size * d + self.n_layers * attn
                + self.n_dense_layers * 3 * d * self.d_ff
                + self.n_expert_layers * ffn)

    # -- the sequence state ---------------------------------------------
    def init_state(self, num_blocks: int, block_size: int, num_slots: int,
                   prefill_chunk: int) -> "LatentState":
        return LatentState(kv=jnp.zeros(
            (self.n_layers, num_blocks, block_size, self.row_width),
            self.compute_dtype))

    def kv_read_tokens(self, lengths) -> int:
        """Latent rows one decode step sees, over lanes of `lengths`."""
        return int(self.n_layers * sum(lengths))

    def init_params(self, rng: jax.Array):
        return init_params(rng, self)

    # -- the served step --------------------------------------------------
    def final_logits(self, params, x):
        x = rms_norm(x, params["final_norm"], eps=self.norm_eps)
        return jnp.einsum("btd,dv->btv", x,
                          params["lm_head"].astype(self.compute_dtype))

    def served_step(self, params, state: "LatentState", tokens,
                    block_tables, positions, kv_len, slots=None,
                    routing: bool = False):
        return _served_step(params, state, tokens, block_tables, positions,
                            kv_len, self, routing)


@dataclasses.dataclass
class LatentState:
    kv: jax.Array         # (n_layers, N_blocks, block_size, row_width)

    # The leaves a block table indexes (`models.decoding.pooled_leaves`).
    pooled: ClassVar[Tuple[str, ...]] = ("kv",)

    def resident_bytes(self) -> dict:
        return {"kv_paged": int(self.kv.size * self.kv.dtype.itemsize),
                "kv_window": 0, "recurrent": 0}


jax.tree_util.register_dataclass(LatentState, ["kv"], [])


# The seeded router bias's standard deviation, held by two properties a
# trained `e_score_correction_bias` has (`topk_method: noaux_tc` nudges it
# until the experts' loads are even, arXiv:2408.15664) and a test checks at
# the published router's sizes (64 experts, top-4, inputs of width 2048;
# tests/test_mla_moe_serving.py, CPU, counts only):
#   it decides selections: zeroed, more than half the tokens take another
#     set of experts (0.05: 81%; 0.02: 47%), so a program that drops it
#     from the selection is another function;
#   it leaves the load near even: eight tokens take within a tenth of the
#     25.8 distinct experts that uniform routing gives them (0.05: 24.1;
#     0.1: 21.0, the busiest expert at five times its share), which is
#     what a family's count of expert bytes a step assumes
#     (`expert_bytes_per_step`: at 0.1 eight lanes read 9-10 of 32 held
#     experts a layer where the count says 12-13, and a roofline share
#     over that count reads a fifth too high, by a margin that moves with
#     the seed).
ROUTER_BIAS_STD = 0.05


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def init_params(rng: jax.Array, cfg: MLAMoEConfig):
    """Seeded parameters, the layers of a kind stacked on a leading axis.
    Norm gains and the router's bias are drawn away from their neutral
    values, so that a comparison notices when one is left out (with a
    zero bias, selecting on s + b and gating with s could not be told
    from selecting and gating on either).  The bias is N(0,
    `ROUTER_BIAS_STD`); `router_bias` is float32 whatever `param_dtype`
    is."""
    d, h, r, qr = cfg.d_model, cfg.n_heads, cfg.kv_rank, cfg.q_rank
    dn, dr, dv = cfg.d_nope, cfg.d_rope, cfg.d_v
    fe, fs, held = cfg.d_expert, cfg.d_shared, cfg.held[1]
    dt = cfg.param_dtype
    count = iter(range(1 << 20))

    def draw(shape, scale, dtype=dt, shift=0.0):
        key = jax.random.fold_in(rng, next(count))
        return (shift + scale * jax.random.normal(key, shape, F32)) \
            .astype(dtype)

    def attn(n):
        return {"norm": draw((n, d), 0.1, shift=1.0),
                "wq_a": draw((n, d, qr), d ** -0.5),
                "q_norm": draw((n, qr), 0.1, shift=1.0),
                "wq_b": draw((n, qr, h * (dn + dr)), qr ** -0.5),
                "wkv_a": draw((n, d, r + dr), d ** -0.5),
                "kv_norm": draw((n, r), 0.1, shift=1.0),
                "w_uk": draw((n, h, dn, r), r ** -0.5),
                "w_uv": draw((n, h, r, dv), r ** -0.5),
                "wo": draw((n, h * dv, d), (h * dv) ** -0.5)}

    def dense(n):
        return {"norm": draw((n, d), 0.1, shift=1.0),
                "w_gate": draw((n, d, cfg.d_ff), d ** -0.5),
                "w_up": draw((n, d, cfg.d_ff), d ** -0.5),
                "w_down": draw((n, cfg.d_ff, d), cfg.d_ff ** -0.5)}

    def ffn(n):
        return {"norm": draw((n, d), 0.1, shift=1.0),
                "router": draw((n, d, cfg.n_experts), d ** -0.5),
                "router_bias": draw((n, cfg.n_experts), ROUTER_BIAS_STD,
                                    F32),
                "shared_gate_up": draw((n, d, 2 * fs), d ** -0.5),
                "shared_down": draw((n, fs, d), fs ** -0.5),
                "w_gate": draw((n, held, d, fe), d ** -0.5),
                "w_up": draw((n, held, d, fe), d ** -0.5),
                "w_down": draw((n, held, fe, d), fe ** -0.5)}

    return {"embed": draw((cfg.vocab_size, d), d ** -0.5),
            "attn": attn(cfg.n_layers),
            "dense": dense(cfg.n_dense_layers),
            "ffn": ffn(cfg.n_expert_layers),
            "final_norm": draw((d,), 0.1, shift=1.0),
            "lm_head": draw((d, cfg.vocab_size), d ** -0.5)}


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------
def _to_row_width(x, cfg):
    """Zeros behind x's last axis up to the stored row's width."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                   + [(0, cfg.row_width - x.shape[-1])])


def _latent_row(ap, u, positions, cfg):
    """What a position stores, (S, K, row_width): its normalised latent,
    its roped key, zeros up to the tile."""
    r = cfg.kv_rank
    ckr = jnp.einsum("skd,de->ske", u, ap["wkv_a"].astype(cfg.compute_dtype))
    c = rms_norm(ckr[..., :r], ap["kv_norm"], eps=cfg.norm_eps)
    k_r = apply_rope(ckr[..., None, r:], positions,
                     theta=cfg.rope_theta)[..., 0, :]
    return _to_row_width(jnp.concatenate([c, k_r], axis=-1), cfg)


def _queries(ap, u, positions, cfg):
    """Every head's (q_n (S, K, H, d_nope), roped q_r (S, K, H, d_rope))."""
    cd = cfg.compute_dtype
    cq = rms_norm(jnp.einsum("skd,dr->skr", u, ap["wq_a"].astype(cd)),
                  ap["q_norm"], eps=cfg.norm_eps)
    q = jnp.einsum("skr,re->ske", cq, ap["wq_b"].astype(cd)).reshape(
        *u.shape[:2], cfg.n_heads, cfg.d_nope + cfg.d_rope)
    return q[..., :cfg.d_nope], apply_rope(q[..., cfg.d_nope:], positions,
                                           theta=cfg.rope_theta)


def _mla(ap, x, pool, li, wb, off, block_tables, positions, kv_len, cfg):
    """Layer `li`'s attention over x (S, K, d) in absorbed form: the
    positions' rows written to the pool at [li, wb, off], then read with
    the rest of the lanes' blocks.  Returns (out (S, K, d), pool)."""
    cd = cfg.compute_dtype
    u = rms_norm(x, ap["norm"], eps=cfg.norm_eps)
    pool = pool.at[li, wb, off].set(
        _latent_row(ap, u, positions, cfg).astype(pool.dtype))
    q_n, q_r = _queries(ap, u, positions, cfg)
    q_lat = jnp.einsum("skhn,hnc->skhc", q_n, ap["w_uk"].astype(cd))
    q = _to_row_width(jnp.concatenate([q_lat, q_r], axis=-1), cfg)
    o_lat = paged_latent_attention(
        q, pool, li, block_tables, positions, kv_len, d_v=cfg.kv_rank,
        scale=cfg.attention_scale)
    o = jnp.einsum("skhc,hcv->skhv", o_lat.astype(cd),
                   ap["w_uv"].astype(cd))
    return jnp.einsum("skf,fd->skd", o.reshape(*x.shape[:2], -1),
                      ap["wo"].astype(cd)), pool


def _swiglu(gate_up, down, cd):
    gate, up = gate_up
    return jnp.einsum("skf,fd->skd", jax.nn.silu(gate) * up, down.astype(cd))


def _dense_ffn(dp, x, cfg):
    cd = cfg.compute_dtype
    h = rms_norm(x, dp["norm"], eps=cfg.norm_eps)
    with jax.named_scope("dense_mlp"):
        return _swiglu((
            jnp.einsum("skd,df->skf", h, dp["w_gate"].astype(cd)),
            jnp.einsum("skd,df->skf", h, dp["w_up"].astype(cd))),
            dp["w_down"], cd)


def _expert_ffn(fp, experts, li, x, live, cfg, routing):
    """Routed experts (this rank's share) plus the shared expert over
    x (S, K, d); `live` (S, K): the rows that carry a real token.
    Returns (out, experts visited, routed here, taken)."""
    cd = cfg.compute_dtype
    h = rms_norm(x, fp["norm"], eps=cfg.norm_eps)
    with jax.named_scope("moe"):
        out, visited, *taken, routed = moe_mlp_dropless(
            h, {"router": fp["router"], "router_bias": fp["router_bias"],
                **experts}, cfg.moe, live=live, layer=li,
            return_routing=routing, return_routed=True)
    with jax.named_scope("shared_mlp"):
        gu = jnp.einsum("skd,df->skf", h, fp["shared_gate_up"].astype(cd))
        out = out + _swiglu(jnp.split(gu, 2, axis=-1), fp["shared_down"], cd)
    return out, visited, routed, (taken[0] if routing else None)


def _take(tree, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False), tree)


def _served_step(params, state: LatentState, tokens, block_tables,
                 positions, kv_len, cfg: MLAMoEConfig,
                 routing: bool = False):
    """`tokens` (S, K) at absolute `positions` (S, K) through every
    layer; `kv_len` (S,) is each lane's length once its valid tokens are
    in (0: an idle lane, which writes the null block and is routed to no
    expert).  Returns (state, hidden (S, K, d), experts visited summed
    over the expert layers, the experts every row took (expert layers,
    S, K, top_k) with `routing` else None, the top-k choices of live
    rows that fell on held experts, summed likewise).  Write-then-read,
    as the paged step: the pool is the layer loop's carry.  The leading
    dense layers run before the scan over the expert layers, which
    indexes the weight stacks (`ops.moe` says why)."""
    cd = cfg.compute_dtype
    bs = state.kv.shape[2]
    valid = positions < kv_len[:, None]                    # (S, K)
    live = (kv_len > 0)[:, None]
    wb = jnp.where(live, jnp.take_along_axis(
        block_tables, positions // bs, axis=1), 0)
    off = jnp.where(live, positions % bs, 0)
    x = params["embed"].astype(cd)[tokens]
    ffn = {k: v for k, v in params["ffn"].items()
           if k not in _EXPERT_WEIGHTS}
    experts = {k: params["ffn"][k] for k in _EXPERT_WEIGHTS}
    nd = cfg.n_dense_layers

    def attend(x, pool, li):
        with jax.named_scope("mla_attn"):
            out, pool = _mla(_take(params["attn"], li), x, pool, li, wb,
                             off, block_tables, positions, kv_len, cfg)
        return x + out, pool

    pool = state.kv
    for j in range(nd):
        x, pool = attend(x, pool, j)
        x = x + _dense_ffn(_take(params["dense"], j), x, cfg)

    def layer(carry, i):
        x, pool, visited, routed = carry
        x, pool = attend(x, pool, nd + i)
        out, n, r, taken = _expert_ffn(_take(ffn, i), experts, i, x, valid,
                                       cfg, routing)
        return (x + out, pool, visited + n, routed + r), taken

    zero, none = jnp.int32(0), routed_zero(tokens.size, cfg.moe)
    (x, pool, visited, routed), taken = jax.lax.scan(
        layer, (x, pool, zero, none), jnp.arange(cfg.n_expert_layers))
    return LatentState(kv=pool), x, visited, taken, routed
