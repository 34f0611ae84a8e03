"""Latent attention (MLA, arXiv:2405.04434) with scored experts on the
served path: a decoder whose KV cache is one low-rank row a position a
layer, read in absorbed form; whose leading layers have a dense FFN and
the rest one rank's share of an expert-parallel FFN chosen by biased
sigmoid scores, beside a shared expert (the layout of the `glm4_moe_lite`,
`deepseek_v3` and `dots3_note` config families).  `models.hybrid` and
`models.mamba2_moe` are its siblings under the same protocol.  **A layer
is of one of two kinds** (`layer_pattern` / `lead_pattern`, the names
`TransformerConfig` uses): a *full* layer keeps every position's row in
pool blocks and attends to all of them or, with `index_top_k`, to the
positions a learned indexer scores highest; a *window* layer keeps the
`window` positions up to its own in a ring of latent rows by the engine's
slot, at sizes of its own.  With full layers alone (what every field
defaults to: GLM-4.7-Flash) its sequences are pool blocks alone
(`state_by_slot` false), so the engine shares prefixes, copies on write,
speculates and ships frames as it does for a `TransformerConfig`; **with
a window layer the configuration says `state_by_slot`, and the engine
refuses what rings refuse anywhere** (`serve.llm._refuse_if_by_slot`:
`import_prefix`, `export_streams`; speculation and a mesh at
construction), turns prefix sharing off and launches one chunk of
`prefill_chunk` rows a tick.

The stack, pre-norm residual, RMSNorm throughout:

    x = E[token]
    x += MLA_l(RMSNorm(x))
    x += FFN_l(RMSNorm'(x))            SwiGLU of width d_ff for l <
                                       n_dense_layers, else experts_l +
                                       shared_l
    logits = RMSNorm_f(x) W_head       (untied)

**With `hc_mult` = n > 0 the residual is n streams a position**, X in
R^{n x d}, mixed around every sub-block by manifold-constrained
hyper-connections (`ops.hyper_connections` has the equations: arXiv:
2512.24880 on arXiv:2409.19606; the layout of the `xing4_0` config
family); each sub-block F (MLA_l or FFN_l, with its own pre-norm) has a
mixing of its own (`hc_attn` / `hc_ffn`: Phi, three alphas, b; float32):

    X = (E[token], .., E[token])                    expansion: n copies
    h_pre, h_post, H_res = mixing_F(X)              H_res projected by
                                                    `hc_sinkhorn_iters`
                                                    Sinkhorn-Knopp rounds
    X = H_res X + h_post^T F(RMSNorm(h_pre X))      two a layer
    logits = RMSNorm_f(sum of the streams) W_head   contraction

The streams are laid flat between sub-blocks, (S, K, n d), in
`compute_dtype`; coefficients and both mixes are float32.  `served_step`
still hands (S, K, d) to `final_logits`, and hands out one value more:
the largest |row sum - 1| / |column sum - 1| of the call's H_res
(`hc_res_defect` of the engine's tick log).  With `yarn` the rope of both
kinds is scaled in DeepSeek's convention and the soft-max scale carries
YaRN's factor (`kind()` builds it, `yarn_score_factor`).

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
share).  With `expert_groups` > 1 the selection is limited to groups
(the layout of the `deepseek_v3` / `deepseek_v32` families: the
`expert_groups_kept` groups of consecutive experts whose two best s +
bias sum highest, the top-k inside them alone; the gates as ever).  The
shared expert is a dense SwiGLU every token takes, added once whatever
the share.

**The two kinds, and what a full layer selects.**  A kind has its own
head count, latent ranks, head widths and rope base (`cfg.kind(name)`; a
window layer's where the `*_window` fields give them, else a full
layer's); both may gate the attention's output by head (`attn_gate`:
sigmoid(u W_g), one value a head) and rescale their normed latents by
sqrt(d_model / rank) (`latent_rescale`).  With `index_top_k` a full layer
carries the lightning indexer published with DeepSeek-V3.2:

    q^I = c_q W^I_qb        index_heads x index_dim, the first d_rope
                            dims of each roped
    k^I = LayerNorm(u W^I_k)  (index_dim), the first d_rope roped: one
                            row a position, cached
    w   = u W^I_w * index_heads^-1/2 * index_dim^-1/2
    I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s]),  s <= t
    S_t = the index_top_k positions of largest I[t, s]

and the absorbed read's soft-max runs over S_t alone, chunk and decode
step alike: `ops.attention.paged_index_scores` (the lane's index keys
through its block table, float32 accumulation) and
`paged_latent_attention(selected=)`, which chooses and reads in one (an
exact top-k over positions, never an approximate one: on a TPU a chunk
and a decode step find S_t's least score by a search that sorts nothing
and read live pages whole, that threshold their mask; the rest sort, and
fetch the selected rows; a served call also hands out how many of its
reads took the mask, the burst's `select_masked` of the tick log).  A
window layer reads its slot's ring through `slot_ring_reader` with
`latent_window_attention`, on `ring_rows` / `ring_seen` as every ring.
Scopes in a profile: `mla_attn` around a layer's attention, inside it
`dsa_index`, `dsa_attend` (inside it `dsa_select`), `latent_swa`,
`attn_gate`; `moe` (inside it `moe_groups`), `shared_mlp`, `dense_mlp`;
`hc_pre`, `hc_sinkhorn`, `hc_post` around each (a model of several
streams).

What a sequence keeps (`LatentState`), three leaves: `kv` (full layers,
N_blocks, block_size, row_width), paged as ever, block 0 the null block;
`idx` (full layers, N_blocks, block_size, index_dim), the indexer's keys,
**pooled by the same table** (`pooled` names both, so copy-on-write, a
frame and `resident_bytes()["kv_paged"]` carry both; None without an
indexer); `ring` (window layers, slots + 1, ring rows, the window kind's
row_width), the null slot last (None without window layers; rows: window
+ prefill_chunk in whole sublane tiles, 513 + 512 -> 1,040).
Parameters: `attn.*` stacked over the full layers (the indexer's and the
gate's beside the rest), `attn_window.*` over the window layers, `dense.*`
over the leading dense layers, `ffn.*` over the expert layers, `hc_attn.*`
/ `hc_ffn.*` over all layers (a model of several streams).  The
up-projections are stored by head, `w_uk` (H, d_nope, kv_rank) and `w_uv`
(H, kv_rank, d_v), so that neither form slices a matrix inside a step.
The layer loop scans the periods of the pattern, its body a period's
layers; the leading dense layers run before it and the layers behind the
last whole period after it.  Multi-token prediction modules are not here:
the published forward pass for next-token logits is the layers above.
Training is not here.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, ClassVar, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import (
    latent_window_attention,
    paged_index_scores,
    paged_latent_attention,
    ring_rows,
    slot_ring_reader,
)
from ray_tpu.ops.hyper_connections import (
    hc_coefficients,
    hc_mix_up,
    n_coefficients,
)
from ray_tpu.ops.moe import MoEConfig, moe_mlp_dropless, routed_zero
from ray_tpu.ops.norms import layer_norm, rms_norm
from ray_tpu.ops.rotary import YarnScaling, apply_rope

F32 = jnp.float32
_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")
_LANE_TILE = 128
_RING_TILE = 16           # a ring's rows, in whole bfloat16 sublane tiles
_KINDS = ("full", "window")


class _Kind(NamedTuple):
    """The attention sizes of one kind of layer."""
    heads: int
    q_rank: int
    kv_rank: int
    d_nope: int
    d_v: int
    theta: float
    row_width: int
    scale: float
    rescale_q: float      # on the normed query latent (1.0: none)
    rescale_kv: float     # on the normed key / value latent


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
    # Group-limited routing (`ops.moe.MoEConfig.n_groups`): a row's
    # experts come from the `expert_groups_kept` of `expert_groups` groups
    # of consecutive experts whose two best selection scores sum highest.
    expert_groups: int = 1
    expert_groups_kept: int = 1
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 202752
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    name: str = "mla-moe"
    # The kinds of layer ("full" | "window"), as `TransformerConfig` names
    # them: `lead_pattern` the n_dense_layers leading layers' (empty: all
    # full), `layer_pattern` the period the expert layers repeat, its
    # last one cut short where they are not whole periods.  A window layer
    # sees the `window` positions up to its own and keeps a ring of
    # latent rows a slot; its sizes are its own where given (0: a full
    # layer's).
    layer_pattern: Tuple[str, ...] = ("full",)
    lead_pattern: Tuple[str, ...] = ()
    window: int = 0
    n_heads_window: int = 0
    q_rank_window: int = 0
    kv_rank_window: int = 0
    d_nope_window: int = 0
    d_v_window: int = 0
    rope_theta_window: float = 0.0
    # sigmoid(u W_g), one value a head, on the attention's output.
    attn_gate: bool = False
    # sqrt(d_model / rank) on each normed latent.
    latent_rescale: bool = False
    # A full layer attends to the `index_top_k` positions that
    # `index_heads` heads of `index_dim` score highest (0: to all): the
    # first d_rope dimensions of an index head are roped, its keys one
    # row a position in a pooled leaf of their own.
    index_heads: int = 0
    index_dim: int = 0
    index_top_k: int = 0
    index_norm_eps: float = 1e-6
    # The rope under YaRN, in DeepSeek's convention: `yarn` says how the
    # frequencies are blended and what multiplies cos and sin (its
    # `attention_factor`: mscale over mscale_all_dim's), and the soft-max
    # scale carries (0.1 `yarn_mscale_all_dim` ln(factor) + 1)^2 (0: 1).
    yarn: Optional[YarnScaling] = None
    yarn_mscale_all_dim: float = 0.0
    # Residual streams a position (0: one, `x += out`), mixed around
    # every sub-block by manifold-constrained hyper-connections
    # (`ops.hyper_connections`): the Sinkhorn-Knopp rounds of the
    # stream-to-stream matrix, the eps of its divisions and of the
    # flattened streams' norm, and the clip of its logits.
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)

    recurrent: ClassVar[bool] = False

    def __post_init__(self):
        if not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError("n_dense_layers leading layers, then at "
                             "least one expert layer")
        if self.lead_pattern and len(self.lead_pattern) != self.n_dense_layers:
            raise ValueError("lead_pattern names the n_dense_layers leading "
                             "layers' kinds")
        if not self.layer_pattern or set(self.kinds) - set(_KINDS):
            raise ValueError(f"a layer is one of {_KINDS}")
        if "window" in self.kinds and self.window < 1:
            raise ValueError("window layers need a window")
        if self.index_top_k and not (self.index_heads
                                     and self.index_dim >= self.d_rope):
            raise ValueError("a selection needs index_heads heads of "
                             "index_dim >= d_rope")
        if self.hc_mult == 1 or self.hc_mult < 0:
            raise ValueError("hc_mult: 0 (one residual stream) or the "
                             "streams mixed, at least two")
        self.moe                        # MoEConfig checks the held range

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Every layer's kind, in order."""
        lead = self.lead_pattern or ("full",) * self.n_dense_layers
        p = self.layer_pattern
        return tuple(lead) + tuple(p[i % len(p)]
                                   for i in range(self.n_expert_layers))

    def n_of(self, kind: str) -> int:
        return self.kinds.count(kind)

    @property
    def state_by_slot(self) -> bool:
        """Pool blocks alone are the sequence unless a window layer keeps
        a ring by the engine's slot."""
        return "window" in self.kinds

    def kind(self, kind: str) -> _Kind:
        own = kind == "window"
        heads = own and self.n_heads_window or self.n_heads
        q_rank = own and self.q_rank_window or self.q_rank
        kv_rank = own and self.kv_rank_window or self.kv_rank
        d_nope = own and self.d_nope_window or self.d_nope
        rescale = self.latent_rescale
        # The soft-max scale, built here alone: the published head size's,
        # times YaRN's factor where the rope is scaled.
        scale = (d_nope + self.d_rope) ** -0.5 * self.yarn_score_factor
        return _Kind(
            heads, q_rank, kv_rank, d_nope,
            own and self.d_v_window or self.d_v,
            own and self.rope_theta_window or self.rope_theta,
            -(-(kv_rank + self.d_rope) // _LANE_TILE) * _LANE_TILE,
            scale,
            (self.d_model / q_rank) ** 0.5 if rescale else 1.0,
            (self.d_model / kv_rank) ** 0.5 if rescale else 1.0)

    @property
    def yarn_score_factor(self) -> float:
        """What YaRN puts on the soft-max scale: (0.1 mscale_all_dim
        ln(factor) + 1)^2, 1 without it."""
        if self.yarn is None or not self.yarn_mscale_all_dim:
            return 1.0
        return (0.1 * self.yarn_mscale_all_dim
                * math.log(self.yarn.factor) + 1.0) ** 2

    @property
    def row_width(self) -> int:
        """A full layer's stored row: (latent | roped key) padded to whole
        lane tiles."""
        return self.kind("full").row_width

    @property
    def attention_scale(self) -> float:
        return self.kind("full").scale

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def moe(self) -> MoEConfig:
        return MoEConfig(num_experts=self.n_experts, top_k=self.expert_top_k,
                         held=self.experts_held, scoring="sigmoid",
                         route_scale=self.route_scale,
                         n_groups=self.expert_groups,
                         groups_kept=self.expert_groups_kept)

    @property
    def num_params(self) -> int:
        d = self.d_model

        def attn(kind):
            k = self.kind(kind)
            n = d * k.q_rank + k.q_rank * k.heads * (k.d_nope + self.d_rope) \
                + d * (k.kv_rank + self.d_rope) \
                + k.kv_rank * k.heads * (k.d_nope + k.d_v) \
                + k.heads * k.d_v * d + self.attn_gate * d * k.heads
            if kind == "full" and self.index_top_k:
                n += k.q_rank * self.index_heads * self.index_dim \
                    + d * (self.index_dim + self.index_heads)
            return n

        ffn = d * self.n_experts + 3 * d * self.d_shared \
            + self.held[1] * 3 * d * self.d_expert
        # Two mixes a layer: Phi, three alphas and b (`ops.hyper_connections`).
        c = n_coefficients(self.hc_mult)
        mixing = 2 * self.n_layers * (self.hc_mult * d * c + 3 + c) \
            if self.hc_mult else 0
        return (2 * self.vocab_size * d + mixing
                + sum(self.n_of(kind) * attn(kind) for kind in _KINDS)
                + self.n_dense_layers * 3 * d * self.d_ff
                + self.n_expert_layers * ffn)

    # -- the sequence state ---------------------------------------------
    def ring_rows(self, prefill_chunk: int) -> int:
        """Rows of a window layer's ring: a window and a chunk (a chunk
        is written before it is read, and its first query still sees a
        whole window), in whole sublane tiles."""
        return -(-(self.window + prefill_chunk) // _RING_TILE) * _RING_TILE

    def init_state(self, num_blocks: int, block_size: int, num_slots: int,
                   prefill_chunk: int) -> "LatentState":
        cd = self.compute_dtype
        n_full = self.n_of("full")
        idx = ring = None
        if self.index_top_k:
            idx = jnp.zeros((n_full, num_blocks, block_size, self.index_dim),
                            cd)
        if self.state_by_slot:
            if not (num_slots and prefill_chunk):
                raise ValueError(f"{self.name!r} keeps a ring a slot for its "
                                 f"window layers: num_slots and "
                                 f"prefill_chunk size them")
            ring = jnp.zeros((self.n_of("window"), num_slots + 1,
                              self.ring_rows(prefill_chunk),
                              self.kind("window").row_width), cd)
        return LatentState(
            kv=jnp.zeros((n_full, num_blocks, block_size, self.row_width),
                         cd), idx=idx, ring=ring)

    def _seen(self, kind: str, n: int) -> int:
        """Positions a row that sees `n` of them attends in a layer of
        `kind`."""
        if kind == "window":
            return min(n, self.window)
        return min(n, self.index_top_k) if self.index_top_k else n

    def kv_read_tokens(self, lengths) -> int:
        """Latent rows one decode step attends, over lanes of `lengths`:
        in a full layer the selected positions (all of them without a
        selection), in a window layer the window.  The indexer's scan of
        its own keys is counted apart (`selection_counts`)."""
        return int(sum(self.n_of(kind) * self._seen(kind, n)
                       for kind in _KINDS for n in lengths))

    def selection_counts(self, start: int, rows: int) -> Tuple[int, int]:
        """(positions the indexer scores, positions the full layers then
        attend) for `rows` rows of one lane at positions `start` ..: row
        p scores p + 1 and attends min(p + 1, index_top_k), in every full
        layer.  (0, 0) without a selection."""
        if not self.index_top_k:
            return 0, 0
        k, end = self.index_top_k, start + rows
        scored = (end * (end + 1) - start * (start + 1)) // 2
        low = min(max(k - start, 0), rows)        # rows that see under k
        under = ((start + low) * (start + low + 1)
                 - start * (start + 1)) // 2
        n_full = self.n_of("full")
        return n_full * scored, n_full * (under + (rows - low) * k)

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
                            kv_len, self, slots, routing)


@dataclasses.dataclass
class LatentState:
    kv: jax.Array         # (full layers, N_blocks, block_size, row_width)
    # The indexer's keys, pooled by the same table; None without one.
    idx: Optional[jax.Array] = None   # (full layers, N_blocks, bs, index_dim)
    # The window layers' rings of latent rows, by slot (the null slot
    # last); None for a model whose every layer is full.
    ring: Optional[jax.Array] = None  # (window layers, S + 1, R, row_width)

    @property
    def pooled(self) -> Tuple[str, ...]:
        """The leaves a block table indexes
        (`models.decoding.pooled_leaves`)."""
        return ("kv",) if self.idx is None else ("kv", "idx")

    def resident_bytes(self) -> dict:
        def nbytes(*arrays):
            return int(sum(a.size * a.dtype.itemsize for a in arrays
                           if a is not None))

        return {"kv_paged": nbytes(self.kv, self.idx),
                "kv_window": nbytes(self.ring), "recurrent": 0}


jax.tree_util.register_dataclass(LatentState, ["kv", "idx", "ring"], [])


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
    """Seeded parameters, the layers of a kind stacked on a leading axis
    (`attn` the full layers', `attn_window` the window layers' where
    there are any).  Norm gains and the router's bias are drawn away from
    their neutral values, so that a comparison notices when one is left
    out (with a zero bias, selecting on s + b and gating with s could not
    be told from selecting and gating on either).  The bias is N(0,
    `ROUTER_BIAS_STD`); `router_bias` is float32 whatever `param_dtype`
    is."""
    d, dr = cfg.d_model, cfg.d_rope
    fe, fs, held = cfg.d_expert, cfg.d_shared, cfg.held[1]
    dt = cfg.param_dtype
    count = iter(range(1 << 20))

    def draw(shape, scale, dtype=dt, shift=0.0):
        key = jax.random.fold_in(rng, next(count))
        return (shift + scale * jax.random.normal(key, shape, F32)) \
            .astype(dtype)

    def attn(n, kind):
        k = cfg.kind(kind)
        h, r, qr, dn, dv = k.heads, k.kv_rank, k.q_rank, k.d_nope, k.d_v
        # What reads a rescaled latent is drawn that much smaller, so
        # that queries, keys and values have the size they have without
        # the rescale (a constant on a normed latent is a factor on these
        # matrices): at N(0, 1 / fan_in) the scores of the rescaled model
        # had a deviation of 7 where the other models' have 1, a soft-max
        # so peaked that bfloat16's rounding of a score moved a position's
        # logits by 0.15 of their size (my chip run, PR 49, call C).
        # YaRN's factor on the soft-max scale is a factor on the scores
        # likewise (2.0 at factor 64: a deviation of 2, and a position's
        # logits 0.041-0.056 of their size off the reference's where the
        # other latent models read 0.02-0.03; my chip run, PR 57, call A):
        # the query's up-projection is drawn that much smaller.
        in_q = qr ** -0.5 / k.rescale_q / cfg.yarn_score_factor
        in_kv = r ** -0.5 / k.rescale_kv
        p = {"norm": draw((n, d), 0.1, shift=1.0),
             "wq_a": draw((n, d, qr), d ** -0.5),
             "q_norm": draw((n, qr), 0.1, shift=1.0),
             "wq_b": draw((n, qr, h * (dn + dr)), in_q),
             "wkv_a": draw((n, d, r + dr), d ** -0.5),
             "kv_norm": draw((n, r), 0.1, shift=1.0),
             "w_uk": draw((n, h, dn, r), in_kv),
             "w_uv": draw((n, h, r, dv), in_kv),
             "wo": draw((n, h * dv, d), (h * dv) ** -0.5)}
        if cfg.attn_gate:
            p["head_gate"] = draw((n, d, h), d ** -0.5)
        if kind == "full" and cfg.index_top_k:
            hi, di = cfg.index_heads, cfg.index_dim
            p.update(wq_idx=draw((n, qr, hi * di), in_q),
                     wk_idx=draw((n, d, di), d ** -0.5),
                     k_idx_norm=draw((n, di), 0.1, shift=1.0),
                     k_idx_bias=draw((n, di), 0.1),
                     w_idx=draw((n, d, hi), d ** -0.5))
        return p

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

    params = {"embed": draw((cfg.vocab_size, d), d ** -0.5),
              "attn": attn(cfg.n_of("full"), "full"),
              "dense": dense(cfg.n_dense_layers),
              "ffn": ffn(cfg.n_expert_layers),
              "final_norm": draw((d,), 0.1, shift=1.0),
              "lm_head": draw((d, cfg.vocab_size), d ** -0.5)}
    if cfg.state_by_slot:
        params["attn_window"] = attn(cfg.n_of("window"), "window")
    if cfg.hc_mult:
        # The mixing's parameters, every layer's attention's and every
        # layer's FFN's, float32 whatever `param_dtype` is, drawn behind
        # everything else so that a model without streams keeps its
        # parameters.  No config gives a seeding: alpha 1, Phi N(0, 1 /
        # (n d)) and b N(0, 1), not the paper's near-identity start, so
        # that the dynamic part moves every coefficient by far more than
        # a tolerance and a program that skips it, the projection or the
        # clip is another function.
        n, c = cfg.hc_mult, n_coefficients(cfg.hc_mult)

        def mixing():
            return {"phi": draw((cfg.n_layers, c, n * d), (n * d) ** -0.5,
                                F32),
                    "alpha": jnp.ones((cfg.n_layers, 3), F32),
                    "b": draw((cfg.n_layers, c), 1.0, F32)}

        params["hc_attn"], params["hc_ffn"] = mixing(), mixing()
    return params


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------
def _to_width(x, width):
    """Zeros behind x's last axis up to a stored row's width."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _latent_row(ap, u, positions, cfg, k: _Kind):
    """What a position stores, (S, K, row_width): its normalised latent,
    its roped key, zeros up to the tile."""
    r = k.kv_rank
    ckr = jnp.einsum("skd,de->ske", u, ap["wkv_a"].astype(cfg.compute_dtype))
    c = rms_norm(ckr[..., :r], ap["kv_norm"], eps=cfg.norm_eps)
    if cfg.latent_rescale:
        c = c * k.rescale_kv
    k_r = apply_rope(ckr[..., None, r:], positions, theta=k.theta,
                     yarn=cfg.yarn)[..., 0, :]
    return _to_width(jnp.concatenate([c, k_r], axis=-1), k.row_width)


def _queries(ap, u, positions, cfg, k: _Kind):
    """Every head's (q_n (S, K, H, d_nope), roped q_r (S, K, H, d_rope)),
    and the normed query latent they are made from."""
    cd = cfg.compute_dtype
    cq = rms_norm(jnp.einsum("skd,dr->skr", u, ap["wq_a"].astype(cd)),
                  ap["q_norm"], eps=cfg.norm_eps)
    if cfg.latent_rescale:
        cq = cq * k.rescale_q
    q = jnp.einsum("skr,re->ske", cq, ap["wq_b"].astype(cd)).reshape(
        *u.shape[:2], k.heads, k.d_nope + cfg.d_rope)
    return q[..., :k.d_nope], apply_rope(q[..., k.d_nope:], positions,
                                         theta=k.theta, yarn=cfg.yarn), cq


def _index(ap, u, cq, idx, at, lanes, cfg):
    """The indexer of full layer `at`: its key of every new position
    written to the pooled leaf `idx`, then every query row's score of
    every position of its lane.  Returns ((scores (S, K, T) float32, each
    position's row of a layer's pool laid flat (S, T), index_top_k): what
    `paged_latent_attention(selected=)` chooses the best of, idx); nothing
    is sorted here (the read sorts where it fetches, and only there)."""
    cd, hi, di = cfg.compute_dtype, cfg.index_heads, cfg.index_dim
    theta, positions = cfg.rope_theta, lanes.positions
    bs, tables = idx.shape[2], lanes.block_tables
    with jax.named_scope("dsa_index"):
        key = layer_norm(
            jnp.einsum("skd,de->ske", u, ap["wk_idx"].astype(cd)),
            ap["k_idx_norm"], ap["k_idx_bias"], eps=cfg.index_norm_eps)
        key = apply_rope(key[..., None, :], positions, theta=theta,
                         yarn=cfg.yarn, rotary_dim=cfg.d_rope)[..., 0, :]
        idx = idx.at[at, lanes.wb, lanes.off].set(key.astype(idx.dtype))
        q = jnp.einsum("skr,re->ske", cq, ap["wq_idx"].astype(cd)).reshape(
            *u.shape[:2], hi, di)
        q = apply_rope(q, positions, theta=theta, yarn=cfg.yarn,
                       rotary_dim=cfg.d_rope)
        w = jnp.einsum("skd,dh->skh", u, ap["w_idx"].astype(cd)) \
            .astype(F32) * (hi ** -0.5 * di ** -0.5)
        scores = paged_index_scores(
            q.astype(idx.dtype), w, idx, at, tables, positions, lanes.kv_len)
        # What the read takes the positions' rows by: the sort carries a
        # position's row with its score, the kernel tells a tied position
        # by it.
        rows = jnp.repeat(tables, bs, axis=1) * bs \
            + jnp.arange(tables.shape[1] * bs) % bs
    return (scores, rows, cfg.index_top_k), idx


class _Lanes(NamedTuple):
    """What a call's lanes are, for every layer alike."""
    block_tables: jax.Array
    positions: jax.Array
    kv_len: jax.Array
    wb: jax.Array             # (S, K): the pool block each row is written to
    off: jax.Array            # and its row there
    slot: Any = None          # (S, 1): the lanes' engine slots
    ring_row: Any = None      # (S, K): the ring row each row is written to
    read_ring: Any = None     # `slot_ring_reader`'s


def _attention(ap, x, state, kind, at, lanes: _Lanes, cfg, routing=False):
    """The attention of the `at`-th layer of `kind` over x (S, K, d) in
    absorbed form: the positions' rows written to layer `at` of the pool
    (full) or of the rings (window), then read with the rest of what the
    lanes keep there.  Returns (out (S, K, d), state, the positions a
    full layer selected if `routing` asks and it selects, else None,
    from a layer that selects 1 where it read its selection as a mask and
    0 where it fetched, else None)."""
    cd = cfg.compute_dtype
    k = cfg.kind(kind)
    kv, idx, ring = state
    selected = masked = None
    u = rms_norm(x, ap["norm"], eps=cfg.norm_eps)
    row = _latent_row(ap, u, lanes.positions, cfg, k)
    if kind == "window":
        ring = ring.at[at, lanes.slot, lanes.ring_row].set(
            row.astype(ring.dtype), mode="drop")
    else:
        kv = kv.at[at, lanes.wb, lanes.off].set(row.astype(kv.dtype))
    q_n, q_r, cq = _queries(ap, u, lanes.positions, cfg, k)
    q_lat = jnp.einsum("skhn,hnc->skhc", q_n, ap["w_uk"].astype(cd))
    q = _to_width(jnp.concatenate([q_lat, q_r], axis=-1), k.row_width)
    if kind == "window":
        with jax.named_scope("latent_swa"):
            o_lat = lanes.read_ring(q, ring, ring, at)
    elif cfg.index_top_k:
        best, idx = _index(ap, u, cq, idx, at, lanes, cfg)
        with jax.named_scope("dsa_attend"):
            o_lat, selected, masked = paged_latent_attention(
                q, kv, at, lanes.block_tables, lanes.positions, lanes.kv_len,
                d_v=k.kv_rank, scale=k.scale, selected=best + (routing,))
    else:
        o_lat = paged_latent_attention(
            q, kv, at, lanes.block_tables, lanes.positions, lanes.kv_len,
            d_v=k.kv_rank, scale=k.scale)
    o = jnp.einsum("skhc,hcv->skhv", o_lat.astype(cd),
                   ap["w_uv"].astype(cd))
    if cfg.attn_gate:
        with jax.named_scope("attn_gate"):
            o = o * jax.nn.sigmoid(jnp.einsum(
                "skd,dh->skh", u, ap["head_gate"].astype(cd)))[..., None]
    out = jnp.einsum("skf,fd->skd", o.reshape(*x.shape[:2], -1),
                     ap["wo"].astype(cd))
    return out, (kv, idx, ring), selected, masked


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


def _nth(i, per: int, rank: int):
    """Index of the `rank`-th of `per` layers a period in period `i`."""
    return i if per == 1 else i * per + rank


def _served_step(params, state: LatentState, tokens, block_tables,
                 positions, kv_len, cfg: MLAMoEConfig, slots=None,
                 routing: bool = False):
    """`tokens` (S, K) at absolute `positions` (S, K) through every
    layer; `kv_len` (S,) is each lane's length once its valid tokens are
    in (0: an idle lane, which writes the null block and the null slot's
    ring and is routed to no expert); `slots` (S,) the lanes' engine
    slots, where window layers keep rings.  Returns (state, hidden (S, K,
    d), experts visited summed over the expert layers, with `routing`
    what every row took else None, the top-k choices of live rows that
    fell on held experts, summed likewise: under groups of experts three
    counts, `ops.moe.routed_zero`).  What a row took: the experts
    of every expert layer (expert layers, S, K, top_k); from a model that
    selects positions or mixes streams a dict of that under "experts"
    and, under "selected", the positions every full layer attended (full
    layers, S, K, index_top_k), under "hc_defect" the largest defect of
    the row's own mixes (1, S, K), under "groups" the groups of experts
    the row kept (expert layers, S, K, expert_groups_kept).
    Write-then-read, as the paged step: pool, index keys and rings are
    the layer loop's carry.  The leading dense layers
    run before the scan, which runs over the periods of the layer
    pattern, its body a period's layers, and indexes the weight stacks
    (`ops.moe` says why); the layers behind the last whole period run
    after it.

    With `hc_mult` the hidden state between sub-blocks is the position's
    streams laid flat, (S, K, hc_mult d): the embedding copied into each
    behind the lookup, every sub-block read through a mix-down and added
    through a mix-up (`ops.hyper_connections`), the streams summed before
    the hidden state is handed back, (S, K, d) as ever.  A sixth value
    then: the largest defect of the projected stream-to-stream matrices
    over the call's valid rows and its mixes (None from a model with one
    stream).  A seventh from a model that selects positions: how many of
    its layers read their selection as a mask (`ops.attention.
    _attend_masked`'s own predicate, summed; None from any other)."""
    cd = cfg.compute_dtype
    if cfg.state_by_slot and slots is None:
        raise ValueError(f"{cfg.name!r} keeps rings by slot: a served call "
                         f"needs the lanes' slots")
    bs = state.kv.shape[2]
    valid = positions < kv_len[:, None]                    # (S, K)
    live = (kv_len > 0)[:, None]
    wb = jnp.where(live, jnp.take_along_axis(
        block_tables, positions // bs, axis=1), 0)
    off = jnp.where(live, positions % bs, 0)
    lanes = _Lanes(block_tables, positions, kv_len, wb, off)
    if cfg.state_by_slot:
        k = cfg.kind("window")
        lanes = lanes._replace(
            slot=slots[:, None],
            ring_row=ring_rows(positions, kv_len, state.ring.shape[2]),
            read_ring=slot_ring_reader(
                functools.partial(latent_window_attention, d_v=k.kv_rank,
                                  scale=k.scale),
                slots, positions, kv_len, cfg.window, state.ring.shape[1]))
    x = params["embed"].astype(cd)[tokens]
    hc = cfg.hc_mult
    if hc:
        x = jnp.tile(x, (1, 1, hc))     # every stream starts as the embedding
    ffn = {k: v for k, v in params["ffn"].items()
           if k not in _EXPERT_WEIGHTS}
    experts = {k: params["ffn"][k] for k in _EXPERT_WEIGHTS}
    nd, kinds = cfg.n_dense_layers, cfg.kinds
    period = cfg.layer_pattern
    per = {kind: period.count(kind) for kind in set(period)}
    lead = {kind: kinds[:nd].count(kind) for kind in _KINDS}
    stacks = {"full": params["attn"], "window": params.get("attn_window")}

    def read(x, defect, mixing, layer):
        """What the sub-block of layer `layer()` reads of x, how its
        output goes back (None: added), and the defect so far.  (The
        layer's index is made here, so that a model without streams
        lowers to the text it had.)"""
        if not hc:
            return x, None, defect
        hp = _take(params[mixing], layer())
        u, h_post, h_res, short = hc_coefficients(
            x, hp["phi"], hp["alpha"], hp["b"], n=hc,
            iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
            clamp=cfg.hc_res_clamp)
        return u, (h_res, h_post), jnp.maximum(defect, short)

    def add(x, out, mix):
        return x + out if mix is None else hc_mix_up(x, out, *mix, n=hc)

    def attend(x, defect, seq, masked, kind, at, layer):
        u, mix, defect = read(x, defect, "hc_attn", layer)
        with jax.named_scope("mla_attn"):
            out, seq, selected, took = _attention(
                _take(stacks[kind], at), u, seq, kind, at, lanes, cfg,
                routing)
        if took is not None:
            masked = masked + took
        return add(x, out, mix), defect, seq, masked, selected

    def expert_layer(carry, i, j):
        """Layer `j` of period `i` behind the leading layers."""
        x, seq, visited, routed, defect, masked = carry
        kind = period[j]

        def layer():
            return nd + _nth(i, len(period), j)

        x, defect, seq, masked, selected = attend(
            x, defect, seq, masked, kind,
            lead[kind] + _nth(i, per[kind], period[:j].count(kind)), layer)
        li = _nth(i, len(period), j)
        u, mix, defect = read(x, defect, "hc_ffn", layer)
        out, n, r, taken = _expert_ffn(_take(ffn, li), experts, li, u, valid,
                                       cfg, routing)
        return (add(x, out, mix), seq, visited + n, routed + r,
                defect, masked), taken, selected

    def run(carry, i, n):
        """The first `n` layers of period `i`."""
        taken, selected = [], []
        for j in range(n):
            carry, t, sel = expert_layer(carry, i, j)
            taken.append(t)
            selected.append(sel)
        return carry, (taken, [sel for sel in selected if sel is not None])

    seq = (state.kv, state.idx, state.ring)
    chosen = []                     # the full layers' selections, in order
    defect = jnp.zeros(tokens.shape, F32) if hc else None   # a row's largest
    masked = jnp.int32(0) if cfg.index_top_k else None  # reads by the mask
    for j in range(nd):
        layer = functools.partial(int, j)
        x, defect, seq, masked, selected = attend(
            x, defect, seq, masked, kinds[j], kinds[:j].count(kinds[j]),
            layer)
        chosen += [] if selected is None else [selected[None]]
        u, mix, defect = read(x, defect, "hc_ffn", layer)
        x = add(x, _dense_ffn(_take(params["dense"], j), u, cfg), mix)
    n_periods, n_tail = divmod(cfg.n_expert_layers, len(period))
    zero, none = jnp.int32(0), routed_zero(tokens.size, cfg.moe)
    carry, (taken, selected) = jax.lax.scan(
        lambda carry, i: run(carry, i, len(period)),
        (x, seq, zero, none, defect, masked), jnp.arange(n_periods))

    def in_order(by_rank):
        """(periods, ..) a layer of the period -> (layers, ..)."""
        if len(by_rank) == 1:
            return by_rank[0]
        both = jnp.stack(by_rank, axis=1)
        return both.reshape(-1, *both.shape[2:])

    if routing:
        taken = in_order(taken)
        chosen += [in_order(selected)] if selected else []
    if n_tail:
        carry, (more, selected) = run(carry, n_periods, n_tail)
        if routing:
            taken = jnp.concatenate([taken, jnp.stack(more)])
            chosen += [sel[None] for sel in selected]
    x, (kv, idx, ring), visited, routed, defect, masked = carry
    if hc:      # the final norm and the head read the sum of the streams
        x = sum(x[..., i * cfg.d_model:(i + 1) * cfg.d_model].astype(F32)
                for i in range(hc)).astype(cd)
    groups = cfg.expert_groups > 1
    if routing and (cfg.index_top_k or hc or groups):
        k = cfg.expert_top_k    # the kept groups ride behind (`ops.moe`)
        taken = {"experts": taken[..., :k], "groups": taken[..., k:]} \
            if groups else {"experts": taken}
        if cfg.index_top_k:
            taken["selected"] = jnp.concatenate(chosen)
        if hc:
            taken["hc_defect"] = defect[None]
    if hc:
        defect = jnp.max(jnp.where(valid, defect, 0.0))
    return (LatentState(kv=kv, idx=idx, ring=ring), x, visited,
            taken if routing else None, routed, defect, masked)
