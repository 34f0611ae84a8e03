"""Decoder-only transformer (Llama-style), TPU-first.

Design choices driven by the hardware (not by the reference, which has no
model code — its Train library wraps user torch modules,
reference: python/ray/train/torch/train_loop_utils.py:158):

- **Stacked layers + `lax.scan`**: all blocks' params are stacked on a
  leading "layers" axis; one block is traced once.  Compile time is O(1) in
  depth, and XLA pipelines the scan body.
- **bf16 compute / f32 master params**: params cast to `compute_dtype` at
  use; matmuls hit the MXU at full rate.
- **Logical-axis sharding**: every param and major activation is annotated
  with logical names resolved against the active mesh; the same model runs
  DDP, FSDP, 2-D fsdp×tp, or with ring-attention sequence parallelism by
  changing the rule table / mesh only.
- **`jax.checkpoint`** around each block: rematerialize activations in
  backward, trading MXU FLOPs for HBM.
- GQA via kv-head broadcast; RoPE with explicit positions (sequence shards
  feed global offsets).
- **A layer pattern is a period**: layers of two kinds of attention
  (`full`: causal; `window`: causal over the last `window` positions,
  unscaled rope) repeat with period `layer_pattern`, and the stack is one
  scan over periods whose body is the period's layers, so compile time
  stays O(1) in depth.  Period 1 (every model without a pattern) is the
  scan over layers it always was.
- **A third kind keeps no KV at all**: a `linear` layer is a Gated
  DeltaNet mixer (`ops.gated_delta`): a matrix of state a head by the
  engine's slot, corrected and written a position, beside the rows of
  a short convolution.  It has no head, rope or `wq` of an attention
  layer, so in such a model everything a kind's mixer owns is stacked by
  kind and `blocks` keeps what every layer shares.  Served only.
- **A fourth kind keeps two rows**: a `conv` layer is a gated short
  convolution (`ops.short_conv`; LFM2's mixer): `[B | C | z]` of the
  normed input, a depth-wise causal convolution of `conv_kernel` rows over
  `B * z`, times `C`.  By the engine's slot it keeps the convolution's
  last inputs and nothing else; it may lead the stack.  Served only.
- **A stack run more than once**: `loop_passes` = R applies the whole
  stack R times to the hidden state with the same weights (a looped
  language model, arXiv:2510.25741), the final norm after every pass and
  its output the next pass's input.  A pass of a layer has keys and
  values of its own, so a position keeps R x the full layers' planes of
  the pool (`kv_planes`: the pool's leading axis is no longer the count
  of layers that have weights), and a gate after each pass says which
  pass's state a row's logits are read from (`exit_threshold`).  Served
  only.
- **What differs by layer lives outside the layers' stacks**: leading
  layers whose FFN is dense (`lead_pattern`) are blocks of their own
  before the scan; where the kinds differ in query heads
  (`n_heads_window`), what has a kind's width (`wq`, `wo`, the head gate)
  is stacked by kind beside the stacks every layer shares.  Such a model
  is served (`models.decoding`); `forward` below, the train and offline
  path, says that it does not take it.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.ring_attention import (
    make_ring_attention, make_sharded_attention)
from ray_tpu.ops.ulysses import make_ulysses_attention
from ray_tpu.ops.rotary import YarnScaling, apply_rope
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES, LogicalRules, with_logical_constraint)
from ray_tpu.parallel.mesh import AXIS_SEQ


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 1536
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    # "full" recomputes the whole block in backward (min memory);
    # "dots" saves matmul outputs and recomputes only elementwise ops,
    # trading HBM for the +2N/6N recompute FLOPs full remat pays.
    remat_policy: str = "full"
    # Context-parallel attention when seq_shards > 1: "ring" rotates
    # k/v around the ICI ring; "ulysses" all-to-alls seq<->head
    # sharding (sp must divide the head count). Both exact.
    sp_attention: str = "ring"
    # MoE (0 experts = dense MLP; Mixtral-style when > 0)
    n_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    name: str = "transformer"
    # The size of a head (`head_dim`) where the width does not give it;
    # 0: d_model // n_heads.
    d_head: int = 0
    # Width of one expert's FFN; 0: d_ff, as Mixtral's.
    d_expert: int = 0
    # RMSNorm over each head of q and of k, learned gain, before the rope.
    qk_norm: bool = False
    # The kinds of attention of one period of layers, "full" or "window",
    # repeated n_layers // len(layer_pattern) times; (): every layer full.
    layer_pattern: Tuple[str, ...] = ()
    # A window layer's query at position t sees t - window < p <= t.
    window: int = 0
    # The full layers' rope scaling (a window layer's rope is unscaled).
    yarn: Optional[YarnScaling] = None
    # What a window layer has of its own, 0: as a full layer.  Its query
    # heads (`n_heads` is the full layers'; KV heads do not differ by
    # kind), its rope's base, and how many of a head's dimensions its
    # rope turns.
    n_heads_window: int = 0
    rope_theta_window: float = 0.0
    rotary_dim_window: int = 0
    # How many of a head's dimensions a full layer's rope turns, the
    # first that many (`ops.rotary.apply_rope`); 0: all.
    rotary_dim: int = 0
    # A gate on the attention's output, its width a query head: 1 (or
    # True) one value a head, `head_dim` one an element; sigmoid(the
    # layer's normed input x head_gate), arXiv:2505.06708.  0: none.
    attn_gate: int = 0
    # The kinds of the leading layers, before the periods, whose FFN is
    # dense at width d_ff whatever n_experts says.  They count in
    # n_layers; what follows them is whole periods and then, behind
    # leading layers alone, the first layers of one more (40 = 1 + 9 x 4
    # + 3; without them n_layers is whole periods or a mistake).
    lead_pattern: Tuple[str, ...] = ()
    # Width of a SwiGLU beside the routed experts that every token
    # takes, added once to their sum; 0: none.
    d_shared: int = 0
    # One rank's share of the experts, (first, count), and how they are
    # scored and scaled: `ops.moe.MoEConfig`'s `held`, `scoring` and
    # `route_scale`, served only (`ops.moe.moe_mlp_dropless`).
    experts_held: Optional[Tuple[int, int]] = None
    expert_scoring: str = "softmax"
    route_scale: float = 1.0
    # Generation by diffusion over blocks, served only (`models.decoding.
    # paged_denoise_burst`); 0: a next-token model, what every other is.
    # A position sees every position up to the end of its own block of
    # `diffusion_block` (causal between blocks, both ways inside one), row
    # i of the logits predicts the token at position i itself, and a
    # sequence grows a block at a time: `denoise_steps` passes over a block
    # whose open rows hold `mask_token_id`, each filling the most confident
    # of them, then one pass that commits the finished block's K / V.
    diffusion_block: int = 0
    denoise_steps: int = 0
    mask_token_id: int = 0
    # A "linear" layer (`ops.gated_delta`), served only: its key and
    # value heads (a key head serves linear_v_heads // linear_k_heads
    # value heads), their sizes, the width of the causal convolution over
    # [q | k | v], the positions of one chunk of the rule's matrix form,
    # and the dtype a slot's state is kept in.
    linear_k_heads: int = 0
    linear_v_heads: int = 0
    linear_d_k: int = 0
    linear_d_v: int = 0
    linear_conv: int = 4
    linear_chunk: int = 64
    linear_state_dtype: Any = jnp.float32
    # Every RMSNorm of the stack but a linear layer's gated one multiplies
    # by 1 + w (a gain stored about 0), served only.
    norm_plus_one: bool = False
    # The shared expert's output times sigmoid(the FFN's normed input x
    # `shared_scale`), one value a token; served only.
    shared_gate: bool = False
    # A "conv" layer (`ops.short_conv`), served only: the rows of its
    # depth-wise causal convolution, the current one counted (a slot keeps
    # `conv_kernel` - 1 rows of `d_model` a layer).
    conv_kernel: int = 3
    # The kinds of the layers behind the last whole period, where they
    # are not the first layers of one more: (): `tail_pattern` follows
    # from `n_layers`.
    layer_tail: Tuple[str, ...] = ()
    # A selection bias a routed expert (`blocks["router_bias"]`, float32):
    # the top-k are taken of the sigmoid scores + it, the gates are the
    # scores' (`ops.moe.MoEConfig.scoring` "sigmoid"); served only.
    router_bias: bool = False
    # The whole stack is applied `loop_passes` times in a row to the
    # hidden state, the same weights in every pass, `final_norm` after
    # every pass (the last pass's is the one before the head); pass r of
    # full layer l keeps its keys and values in plane r x L_full + l of
    # the pool.  1: a stack run once, what every other model is.  Served
    # only.
    loop_passes: int = 1
    # A second RMSNorm a sub-block, on its output before it is added to
    # the residual (`blocks["attn_post_norm"]` / `["mlp_post_norm"]`,
    # gains drawn about (2 L)^-1/2): x + N(Attn(N(x))), x + N(FFN(N(x))).
    # Served only.
    post_norm: bool = False
    # An exit gate behind every pass (`params["exit_gate"]`: `w` (d,) and
    # a bias `b`): lam_r = sigmoid(h_{r+1} . w + b) of the pass's normed
    # output, p_r = lam_r prod_{s<r} (1 - lam_s), the last pass taking
    # what is left; a row's logits are read from the first pass at which
    # the summed p reaches `exit_threshold` (every pass still runs for
    # every row and writes its KV: later positions read all of them).  At
    # 1 that is the last pass.  0: no gate, the head reads the last pass.
    exit_threshold: float = 0.0

    def __post_init__(self):
        pattern = tuple(self.layer_pattern)
        lead = tuple(self.lead_pattern)
        object.__setattr__(self, "layer_pattern", pattern)
        object.__setattr__(self, "lead_pattern", lead)
        object.__setattr__(self, "attn_gate", int(self.attn_gate))
        tail = tuple(self.layer_tail)
        object.__setattr__(self, "layer_tail", tail)
        if set(pattern) - {"full", "window", "linear", "conv"} \
                or set(lead) - {"full", "window", "conv"}:
            raise ValueError(f"layer_pattern {pattern}, lead_pattern {lead}: "
                             f"a layer is 'full', 'window', 'conv' or, "
                             f"behind the leading ones, 'linear'")
        if pattern and not lead and not tail \
                and self.n_layers % len(pattern):
            raise ValueError(f"n_layers {self.n_layers} is not whole "
                             f"periods of {pattern}")
        if tail and (set(tail) - set(pattern) or (
                self.n_layers - len(lead) - len(tail)) % len(pattern)):
            raise ValueError(
                f"layer_tail {tail} stands behind whole periods of "
                f"{pattern} and is made of their kinds: n_layers "
                f"{self.n_layers}, lead_pattern {lead}")
        if len(lead) + len(tail) >= self.n_layers:
            raise ValueError(f"lead_pattern {lead} leaves none of "
                             f"{self.n_layers} layers to the periods")
        if ("window" in pattern + lead) != (self.window > 0):
            raise ValueError("window layers and a window come together: "
                             f"layer_pattern {pattern}, window {self.window}")
        if self.n_experts <= 0 and (self.d_shared or self.experts_held):
            raise ValueError("a shared expert and a held share stand "
                             "beside routed experts: n_experts is 0")
        if self.shared_gate and not self.d_shared:
            raise ValueError("shared_gate gates a shared expert: d_shared "
                             "is 0")
        if self.attn_gate not in (0, 1, self.head_dim):
            raise ValueError(f"attn_gate {self.attn_gate}: a gate is one "
                             f"value a head (1) or one an element "
                             f"(head_dim = {self.head_dim})")
        if "linear" in pattern:
            hk, hv = self.linear_k_heads, self.linear_v_heads
            if not (hk > 0 and hv % hk == 0 and hv > 0 and self.linear_d_k
                    > 0 and self.linear_d_v > 0 and self.linear_conv >= 2
                    and self.linear_chunk > 0):
                raise ValueError(
                    f"a linear layer has linear_k_heads ({hk}) key heads "
                    f"that divide its linear_v_heads ({hv}) value heads, "
                    f"their sizes linear_d_k / linear_d_v, a convolution "
                    f"of 2 rows or more and a chunk")
            if "window" in pattern + lead or self.diffusion_block:
                raise ValueError(
                    "a linear layer's state is carried from chunk to chunk "
                    "of a launch and its rows are seen once: a window "
                    "layer's ring is laid out by a launch's rows and a "
                    "block's passes run its rows again")
        if "conv" in pattern + lead:
            if self.conv_kernel < 2:
                raise ValueError(f"conv_kernel {self.conv_kernel}: a conv "
                                 f"layer convolves 2 rows or more")
            if "linear" in pattern:
                raise ValueError(
                    "conv and linear layers in one pattern: a slot keeps "
                    "one stack of convolution rows (`PagedKVCache.lconv`) "
                    "and theirs differ in width")
            if "window" in pattern + lead or self.diffusion_block:
                raise ValueError(
                    "a conv layer's rows are carried through a launch and "
                    "seen once: a window layer's ring is laid out by a "
                    "launch's rows and a block's passes run its rows again")
        if self.router_bias and (self.n_experts <= 0
                                 or self.expert_scoring != "sigmoid"):
            raise ValueError("router_bias moves the selection among sigmoid "
                             "scores: expert_scoring is "
                             f"{self.expert_scoring!r}, n_experts "
                             f"{self.n_experts}")
        if self.loop_passes < 1 or not 0.0 <= self.exit_threshold <= 1.0:
            raise ValueError(f"loop_passes {self.loop_passes} is 1 or more "
                             f"and exit_threshold {self.exit_threshold} a "
                             f"share of 1")
        if self.loop_passes == 1 and self.exit_threshold:
            raise ValueError("exit_threshold chooses among the passes of a "
                             "stack run more than once: loop_passes is 1")
        if self.loop_passes > 1:
            beside = [name for name, there in (
                ("window", self.window), ("layer_pattern 'linear'",
                                          "linear" in pattern),
                ("layer_pattern 'conv'", "conv" in pattern + lead),
                ("diffusion_block", self.diffusion_block),
                ("n_experts", self.n_experts > 0)) if there]
            if beside:
                raise ValueError(
                    f"loop_passes {self.loop_passes} beside {beside}: a "
                    f"pass of a full layer keeps a plane of the pool; a "
                    f"ring or a recurrent state by slot a pass, a block's "
                    f"passes inside a pass of the stack and the experts' "
                    f"counts a pass have no form here")
        block = self.diffusion_block
        if block:
            if not (block >= 2 and 1 <= self.denoise_steps <= block
                    and 0 <= self.mask_token_id < self.vocab_size):
                raise ValueError(
                    f"diffusion_block {block}: a block is 2 rows or more "
                    f"(`paged_attention` masks a launch of one row a lane "
                    f"by its own position), denoise_steps "
                    f"{self.denoise_steps} fills 1 to {block} rows a pass, "
                    f"and mask_token_id {self.mask_token_id} is a row of "
                    f"the {self.vocab_size}-row embedding")
            if "window" in pattern + lead:
                raise ValueError("a block's rows see each other both ways: "
                                 "a window layer's ring has no such mask")
        elif self.denoise_steps or self.mask_token_id:
            raise ValueError("denoise_steps and mask_token_id come with a "
                             "diffusion_block")
        self.moe                        # MoEConfig checks share and scoring

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def period(self) -> Tuple[str, ...]:
        return self.layer_pattern or ("full",)

    @property
    def n_periods(self) -> int:
        """Whole periods after the leading layers: the scan's length."""
        return (self.n_layers - len(self.lead_pattern)
                - len(self.layer_tail)) // len(self.period)

    @property
    def tail_pattern(self) -> Tuple[str, ...]:
        """The layers behind the last whole period: `layer_tail`, else the
        first of one more."""
        return self.layer_tail or self.period[
            :(self.n_layers - len(self.lead_pattern)) % len(self.period)]

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The kind of every layer, in order."""
        return (self.lead_pattern + self.period * self.n_periods
                + self.tail_pattern)

    def n_of(self, kind: str) -> int:
        """Layers of `kind` in the model."""
        return self.kinds.count(kind)

    def heads(self, kind: str) -> int:
        """Query heads of an attention layer of `kind`."""
        return (self.n_heads_window if kind == "window" else 0) \
            or self.n_heads

    @property
    def heads_by_kind(self) -> bool:
        """Whether the kinds differ in query heads, so that what has a
        kind's width is stacked by kind (`init_params`)."""
        return self.heads("window") != self.n_heads

    @property
    def mixers_by_kind(self) -> bool:
        """Whether a kind's whole mixer is stacked by kind, `blocks`
        keeping the norms and the FFN alone: a model with linear or conv
        layers, which share no weight of an attention layer's."""
        return self.recurrent

    @property
    def linear_conv_dim(self) -> int:
        """Channels of a linear layer's convolution: q, k and v."""
        return 2 * self.linear_k_heads * self.linear_d_k \
            + self.linear_v_heads * self.linear_d_v

    @property
    def n_experts_held(self) -> int:
        """Experts whose weights are here: the share, or all."""
        return self.experts_held[1] if self.experts_held else self.n_experts

    @property
    def n_expert_layers(self) -> int:
        """Layers whose FFN is the experts: all but the leading ones."""
        return self.n_layers - len(self.lead_pattern) \
            if self.n_experts > 0 else 0

    @property
    def state_by_slot(self) -> bool:
        """Whether a served sequence keeps more than pool blocks: a ring a
        slot for each window layer, a state and conv rows a slot for each
        linear layer, conv rows for each conv layer (`models.decoding`)."""
        return "window" in self.period or self.recurrent

    @property
    def recurrent(self) -> bool:
        """Whether some of that is recurrent state, which the engine
        zeroes when a slot changes hands (`reset_slot`)."""
        return "linear" in self.period \
            or "conv" in self.period + self.lead_pattern

    @property
    def launch_spans_chunks(self) -> bool:
        """Whether a launch's rows lay none of the state by slot out: a
        launch of m x `linear_chunk` rows is m chunks of the rule, the
        state handed on inside the program, and a conv layer continues
        its convolution over any number of rows (the engine then builds
        launch tiers above `prefill_chunk`)."""
        return self.recurrent

    @staticmethod
    def reset_slot(cache, slot):
        """Zero one slot's recurrent state (a request is admitted to it,
        or a preempted stream will re-prefill)."""
        return dataclasses.replace(cache, **{
            name: getattr(cache, name).at[:, slot].set(0)
            for name in ("lconv", "lstate")
            if getattr(cache, name) is not None})

    @property
    def kv_planes(self) -> int:
        """Planes of K and of V a position keeps in the pool (its leading
        axis): one a full layer and pass of the stack."""
        return self.loop_passes * self.n_of("full")

    def kv_read_tokens(self, lengths) -> int:
        """KV positions one decode step sees over lanes of `lengths`:
        every position in a full layer, once a pass of the stack, at most
        the window in a window layer."""
        return int(self.kv_planes * sum(lengths) + self.n_of("window")
                   * sum(min(int(n), self.window) for n in lengths))

    def rope(self, kind: str) -> dict:
        """`apply_rope`'s keywords for a layer of `kind`."""
        full = kind == "full"
        part = self.rotary_dim if full else \
            self.rotary_dim_window or self.rotary_dim
        return {"theta": self.rope_theta if full
                else self.rope_theta_window or self.rope_theta,
                "yarn": self.yarn if full else None,
                **({"rotary_dim": part} if part else {})}

    @property
    def expert_width(self) -> int:
        return self.d_expert or self.d_ff

    @property
    def moe(self):
        if self.n_experts <= 0:
            return None
        from ray_tpu.ops.moe import MoEConfig

        return MoEConfig(num_experts=self.n_experts, top_k=self.expert_top_k,
                         capacity_factor=self.capacity_factor,
                         held=self.experts_held, scoring=self.expert_scoring,
                         route_scale=self.route_scale,
                         grouped_from_rows=16 if self.diffusion_block else 0)

    @property
    def num_params(self) -> int:
        """Parameters held here: of the experts, the share."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        kv = self.n_kv_heads * self.head_dim
        experts = self.n_experts_held * 3 * d * self.expert_width \
            + d * self.n_experts + 3 * d * self.d_shared
        total = v * d * (1 if self.tie_embeddings else 2) + d
        experts += d if self.shared_gate else 0
        experts += self.n_experts if self.router_bias else 0
        conv = 4 * d * d + self.conv_kernel * d
        inner = self.linear_v_heads * self.linear_d_v
        linear = d * (self.linear_conv_dim + inner + 2 * self.linear_v_heads) \
            + self.linear_conv * self.linear_conv_dim \
            + 2 * self.linear_v_heads + self.linear_d_v + inner * d
        for i, kind in enumerate(self.kinds):
            h = self.heads(kind)
            dense = self.n_experts <= 0 or i < len(self.lead_pattern)
            total += (4 if self.post_norm else 2) * d \
                + (3 * d * f if dense else experts)
            attention = d * h * self.head_dim * 2 + d * kv * 2 \
                + d * h * self.attn_gate \
                + (2 * self.head_dim if self.qk_norm else 0)
            total += {"linear": linear, "conv": conv}.get(kind, attention)
        return total + (d + 1 if self.exit_threshold else 0)


def init_params(rng: jax.Array, cfg: TransformerConfig):
    """Parameter pytree; per-layer tensors stacked on a leading L axis:
    `blocks`, over the layers behind the leading ones.  A leading layer
    (`cfg.lead_pattern`) is a block of its own, unstacked, in the list
    `lead`.  Where the kinds differ in query heads, `blocks` lacks what
    has a kind's width (`wq`, `wo`, `head_gate`), which `kinds[kind]`
    stacks over the layers of that kind behind the leading ones.  In a
    model with linear layers (`cfg.mixers_by_kind`) `blocks` keeps the
    norms and the FFN alone and `kinds[kind]` a kind's whole mixer: an
    attention layer's `wq`, `wk`, `wv`, `wo`, gate and QK-norm, a linear
    layer's `in_qkvz` ([q | k | v | z]), `in_ba` ([b | a]), `conv_w`,
    `A_log`, `dt_bias` (float32 both), `gate_norm` and `out_proj`, a conv
    layer's `in_proj` ([B | C | z]), `conv_w` and `out_proj`; a leading
    conv layer's block holds those in place of an attention's.  With
    `cfg.post_norm` the norms gain `attn_post_norm` / `mlp_post_norm`, with
    `cfg.exit_threshold` the tree gains `exit_gate` (`w` (d,), `b` ());
    the passes of `cfg.loop_passes` share every weight."""
    d, f = cfg.d_model, cfg.d_ff
    hd = cfg.head_dim
    nkv = cfg.n_kv_heads
    keys = jax.random.split(rng, 8)
    dt = cfg.param_dtype

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)).astype(dt)

    def gain(key, shape, neutral=True):
        """A norm's gain: 1 (`neutral`) or drawn about it, so that a
        comparison notices a gain left out; about 0 in a model whose
        norms add the 1 themselves, where nothing is neutral."""
        if neutral and not cfg.norm_plus_one:
            return jnp.ones(shape, dt)
        return ((0.0 if cfg.norm_plus_one else 1.0) + 0.1 * jax.random.normal(
            key, shape, jnp.float32)).astype(dt)

    def wide(k, l, nh):
        """What of `l` layers (() for one) is `nh` query heads wide."""
        out = {"wq": dense(k[1], (*l, d, nh * hd), d),
               "wo": dense(k[4], (*l, nh * hd, d), nh * hd)}
        if cfg.attn_gate:
            out["head_gate"] = dense(jax.random.fold_in(k[4], 2),
                                     (*l, d, nh * cfg.attn_gate), d)
        return out

    def norms(k, l):
        out = {"attn_norm": gain(jax.random.fold_in(k[0], 1), (*l, d)),
               "mlp_norm": gain(jax.random.fold_in(k[0], 2), (*l, d))}
        if cfg.post_norm:
            # Drawn about (2 L)^-1/2, the scale GPT-2 gives its residual
            # branches: a pass of 2 L normed sub-blocks then adds a unit of
            # variance to the stream.  About 1, every sub-block's output
            # is as large as the stream it joins and seeded weights make
            # the stack chaotic: a rounding grows 2.6-fold a pass of 48
            # layers (`bench/families/ouro.py`, beside `TOLERANCES`).
            for i, name in enumerate(("attn_post_norm", "mlp_post_norm")):
                out[name] = ((2.0 * cfg.n_layers) ** -0.5 * (1.0 + 0.1 * (
                    jax.random.normal(jax.random.fold_in(k[0], 4 + i),
                                      (*l, d), jnp.float32)))).astype(dt)
        return out

    def narrow(k, l):
        """`l` attention layers' keys, values and QK-norm: what no kind
        of attention layer has a width of its own for."""
        out = {"wk": dense(k[2], (*l, d, nkv * hd), d),
               "wv": dense(k[3], (*l, d, nkv * hd), d)}
        if cfg.qk_norm:
            for i, name in enumerate(("q_norm", "k_norm")):
                out[name] = gain(jax.random.fold_in(k[1], 1 + i), (*l, hd),
                                 neutral=False)
        return out

    def attention(k, l, nh):
        """`l` layers' attention and norms; `nh` None: without what is
        stacked by kind."""
        return {**norms(k, l), **({} if nh is None else wide(k, l, nh)),
                **narrow(k, l)}

    def linear(k, l):
        """`l` linear layers' mixers.  The decay is drawn so that a step
        keeps exp(g) mostly in (0.2, 1), the slowest heads remembering
        over hundreds of positions: a state lost between two launches
        then still shows many positions later."""
        hv, dv = cfg.linear_v_heads, cfg.linear_d_v
        inner, conv = hv * dv, cfg.linear_conv_dim
        rate = jnp.exp(jax.random.uniform(
            k[5], (*l, hv), jnp.float32, math.log(1e-3), 0.0))
        return {"in_qkvz": dense(k[1], (*l, d, conv + inner), d),
                "in_ba": dense(k[2], (*l, d, 2 * hv), d),
                "conv_w": dense(k[3], (*l, cfg.linear_conv, conv),
                                cfg.linear_conv),
                "A_log": jnp.log(rate),
                "dt_bias": jax.random.uniform(k[6], (*l, hv), jnp.float32,
                                              -1.0, 1.0),
                "gate_norm": (1.0 + 0.1 * jax.random.normal(
                    k[7], (*l, dv), jnp.float32)).astype(dt),
                "out_proj": dense(k[4], (*l, inner, d), inner)}

    def conv(k, l):
        """`l` conv layers' mixers."""
        return {"in_proj": dense(k[1], (*l, d, 3 * d), d),
                "conv_w": dense(k[3], (*l, cfg.conv_kernel, d),
                                cfg.conv_kernel),
                "out_proj": dense(k[4], (*l, d, d), d)}

    def swiglu(k, l, width, prefix="w_"):
        return {prefix + "gate": dense(k[5], (*l, d, width), d),
                prefix + "up": dense(k[6], (*l, d, width), d),
                prefix + "down": dense(k[7], (*l, width, d), width)}

    def keys_of(i):
        return jax.random.split(jax.random.fold_in(rng, i), 8)

    n_lead = len(cfg.lead_pattern)
    l = (cfg.n_layers - n_lead,)
    blocks = norms(keys, l) if cfg.mixers_by_kind else attention(
        keys, l, None if cfg.heads_by_kind else cfg.n_heads)
    if cfg.n_experts > 0:
        e, f = cfg.n_experts, cfg.expert_width
        held = cfg.n_experts_held
        blocks.update({
            "router": dense(jax.random.fold_in(keys[5], 1), (*l, d, e), d),
            "w_gate": dense(keys[5], (*l, held, d, f), d),
            "w_up": dense(keys[6], (*l, held, d, f), d),
            "w_down": dense(keys[7], (*l, held, f, d), f),
        })
        if cfg.d_shared:
            blocks.update(swiglu(keys_of(98), l, cfg.d_shared, "shared_"))
        if cfg.shared_gate:
            blocks["shared_scale"] = dense(keys_of(97)[0], (*l, d, 1), d)
        if cfg.router_bias:
            # Away from 0, so that a comparison notices a selection made
            # on the scores alone (`models.mla_moe.ROUTER_BIAS_STD` says
            # why no wider).
            blocks["router_bias"] = 0.05 * jax.random.normal(
                keys_of(96)[0], (*l, e), jnp.float32)
    else:
        blocks.update(swiglu(keys, l, f))
    params = {
        "embed": dense(keys[0], (cfg.vocab_size, d), d ** 0.5 * d),  # ~N(0, 1/sqrt(d))
        "blocks": blocks,
        # After every pass of a stack run more than once: drawn about 1,
        # so that a comparison notices it applied twice before the head.
        "final_norm": gain(jax.random.fold_in(keys[0], 3), (d,),
                           neutral=cfg.loop_passes == 1),
    }
    if n_lead:
        params["lead"] = [
            {**({**norms(keys_of(100 + i), ()), **conv(keys_of(100 + i), ())}
                if kind == "conv"
                else attention(keys_of(100 + i), (), cfg.heads(kind))),
             **swiglu(keys_of(100 + i), (), cfg.d_ff)}
            for i, kind in enumerate(cfg.lead_pattern)]

    def of_kind(k, n, kind):
        """What `kinds[kind]` stacks over the `n` layers of `kind`."""
        if kind == "linear":
            return linear(k, n)
        if kind == "conv":
            return conv(k, n)
        own = wide(k, n, cfg.heads(kind))
        return {**own, **narrow(k, n)} if cfg.mixers_by_kind else own

    if cfg.mixers_by_kind or cfg.heads_by_kind:
        behind = cfg.kinds[n_lead:]
        params["kinds"] = {
            kind: of_kind(keys_of(200 + i), (behind.count(kind),), kind)
            for i, kind in enumerate(sorted(set(behind)))}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(jax.random.fold_in(rng, 99), (d, cfg.vocab_size), d)
    if cfg.exit_threshold:
        # Three quarters of a projection's scale and a small bias: over
        # normed rows lam lies in (0.1, 0.9) (2.9 standard deviations),
        # so that at a threshold under 1 rows leave at every pass.
        k = jax.random.split(jax.random.fold_in(rng, 95))
        params["exit_gate"] = {
            "w": (0.75 * d ** -0.5 * jax.random.normal(
                k[0], (d,), jnp.float32)).astype(dt),
            "b": (0.1 * jax.random.normal(k[1], (), jnp.float32)).astype(dt)}
    return params


def param_logical_axes(cfg: TransformerConfig):
    """Pytree of logical-axis tuples matching `init_params` exactly."""
    def wide(l):
        out = {"wq": (*l, "embed", "heads"), "wo": (*l, "heads", "embed")}
        if cfg.attn_gate:
            out["head_gate"] = (*l, "embed", "heads")
        return out

    def norms(l):
        return {name: (*l, "embed") for name in (
            "attn_norm", "mlp_norm", *(("attn_post_norm", "mlp_post_norm")
                                       if cfg.post_norm else ()))}

    def narrow(l):
        out = {"wk": (*l, "embed", "kv_heads"),
               "wv": (*l, "embed", "kv_heads")}
        if cfg.qk_norm:
            out.update({"q_norm": (*l, "head_dim"),
                        "k_norm": (*l, "head_dim")})
        return out

    def attention(l, by_kind):
        return {**norms(l), **({} if by_kind else wide(l)), **narrow(l)}

    def linear(l):
        return {"in_qkvz": (*l, "embed", "heads"),
                "in_ba": (*l, "embed", None), "conv_w": (*l, None, "heads"),
                "A_log": (*l, None), "dt_bias": (*l, None),
                "gate_norm": (*l, "head_dim"),
                "out_proj": (*l, "heads", "embed")}

    def conv(l):
        return {"in_proj": (*l, "embed", "heads"),
                "conv_w": (*l, None, "embed"),
                "out_proj": (*l, "heads", "embed")}

    def swiglu(l, prefix="w_"):
        return {prefix + "gate": (*l, "embed", "mlp"),
                prefix + "up": (*l, "embed", "mlp"),
                prefix + "down": (*l, "mlp", "embed")}

    l = ("layers",)
    blocks = norms(l) if cfg.mixers_by_kind else attention(
        l, cfg.heads_by_kind)
    if cfg.n_experts > 0:
        blocks.update({
            "router": ("layers", "embed", "expert"),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        })
        if cfg.d_shared:
            blocks.update(swiglu(l, "shared_"))
        if cfg.shared_gate:
            blocks["shared_scale"] = ("layers", "embed", None)
        if cfg.router_bias:
            blocks["router_bias"] = ("layers", "expert")
    else:
        blocks.update(swiglu(l))
    axes = {
        "embed": ("vocab", "embed"),
        "blocks": blocks,
        "final_norm": ("embed",),
    }
    if cfg.lead_pattern:
        axes["lead"] = [{**({**norms(()), **conv(())} if kind == "conv"
                            else attention((), False)), **swiglu(())}
                        for kind in cfg.lead_pattern]
    behind = sorted(set(cfg.kinds[len(cfg.lead_pattern):]))
    if cfg.mixers_by_kind:
        own = {"linear": linear, "conv": conv}
        axes["kinds"] = {kind: own[kind](l) if kind in own
                         else {**wide(l), **narrow(l)} for kind in behind}
    elif cfg.heads_by_kind:
        axes["kinds"] = {kind: wide(l) for kind in behind}
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    if cfg.exit_threshold:
        axes["exit_gate"] = {"w": ("embed",), "b": ()}
    return axes


def _repeated_kv(attn_impl):
    """For an `attn_impl` that takes K/V at the query heads' count (ring,
    Ulysses): GQA broadcast before the call.  The flash kernel takes the KV
    heads as they are and shares each among its group itself."""
    def impl(q, k, v):
        rep = q.shape[2] // k.shape[2]
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return attn_impl(q, k, v)
    return impl


def gain_of(w, cfg: TransformerConfig):
    """The gain an RMSNorm of the stack multiplies by, from its stored
    `w`: `w`, or 1 + w (float32) in a model whose norms add the 1."""
    return 1.0 + w.astype(jnp.float32) if cfg.norm_plus_one else w


def qk_normed(bp, q, k, cfg: TransformerConfig):
    """q, k (B, T, heads, D) under the block's QK-norm, where it has one."""
    if not cfg.qk_norm:
        return q, k
    return (rms_norm(q, gain_of(bp["q_norm"], cfg), eps=cfg.norm_eps),
            rms_norm(k, gain_of(bp["k_norm"], cfg), eps=cfg.norm_eps))


def _block(x, bp, cfg: TransformerConfig, rules: LogicalRules, *,
           attn_impl, positions, kind: str = "full"):
    cd = cfg.compute_dtype
    h = rms_norm(x, bp["attn_norm"], eps=cfg.norm_eps)
    q = jnp.einsum("btd,dh->bth", h, bp["wq"].astype(cd))
    k = jnp.einsum("btd,dh->bth", h, bp["wk"].astype(cd))
    v = jnp.einsum("btd,dh->bth", h, bp["wv"].astype(cd))
    b, t = x.shape[:2]
    q = q.reshape(b, t, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    q, k = qk_normed(bp, q, k, cfg)
    q = apply_rope(q, positions, **cfg.rope(kind))
    k = apply_rope(k, positions, **cfg.rope(kind))
    q = with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"), rules)
    attn = attn_impl(q, k, v)     # k/v at n_kv_heads: (B,T,nkv,hd)
    attn = attn.reshape(b, t, cfg.n_heads * cfg.head_dim)
    x = x + jnp.einsum("bth,hd->btd", attn, bp["wo"].astype(cd))
    x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)

    h = rms_norm(x, bp["mlp_norm"], eps=cfg.norm_eps)
    aux = {}
    if cfg.n_experts > 0:
        from ray_tpu.ops.moe import moe_mlp

        moe_params = {"router": bp["router"], "w_gate": bp["w_gate"],
                      "w_up": bp["w_up"], "w_down": bp["w_down"]}
        out, aux = moe_mlp(h, moe_params, cfg.moe, rules=rules)
        x = x + out
    else:
        gate = jnp.einsum("btd,df->btf", h, bp["w_gate"].astype(cd))
        up = jnp.einsum("btd,df->btf", h, bp["w_up"].astype(cd))
        hidden = jax.nn.silu(gate) * up
        hidden = checkpoint_name(hidden, "ff_hidden")
        hidden = with_logical_constraint(hidden, ("batch", "seq", "mlp"),
                                         rules)
        x = x + jnp.einsum("btf,fd->btd", hidden, bp["w_down"].astype(cd))
    return with_logical_constraint(x, ("batch", "seq", "embed"), rules), aux


def forward(params, tokens, cfg: TransformerConfig, *,
            rules: LogicalRules = DEFAULT_RULES, mesh: Mesh | None = None,
            positions=None, seq_shards: int = 1, return_aux: dict | None = None):
    """tokens (B, T) int32 → logits (B, T, vocab) in compute dtype.

    `seq_shards > 1` switches attention to the context-parallel kernel
    (`cfg.sp_attention`: ring or ulysses) over the `sp`
    mesh axis (requires `mesh`); positions then carry global offsets — the
    caller passes globally-consistent `positions` or we default to 0..T-1
    of the *global* view (pjit global shapes make this automatic).
    """
    served_only = [name for name, differs in (
        ("lead_pattern", cfg.lead_pattern), ("n_layers", cfg.tail_pattern),
        ("n_heads_window", cfg.heads_by_kind), ("attn_gate", cfg.attn_gate),
        ("d_shared", cfg.d_shared), ("experts_held", cfg.experts_held),
        ("expert_scoring", cfg.expert_scoring != "softmax"),
        ("route_scale", cfg.route_scale != 1.0),
        ("diffusion_block", cfg.diffusion_block),
        ("layer_pattern 'linear'", "linear" in cfg.period),
        ("layer_pattern 'conv'", "conv" in cfg.kinds),
        ("router_bias", cfg.router_bias),
        ("norm_plus_one", cfg.norm_plus_one),
        ("shared_gate", cfg.shared_gate),
        ("loop_passes", cfg.loop_passes > 1),
        ("post_norm", cfg.post_norm),
        ("exit_threshold", cfg.exit_threshold)) if differs]
    if served_only:
        raise ValueError(
            f"{cfg.name!r} is a served model (`models.decoding`): the train "
            f"and offline path has no form of its {served_only}")
    cd = cfg.compute_dtype
    b, t = tokens.shape
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.int32)

    if seq_shards > 1:
        if cfg.state_by_slot:
            raise ValueError(
                f"{cfg.name!r} has sliding-window layers: neither ring nor "
                f"Ulysses attention masks a window (seq_shards must be 1)")
        if mesh is None:
            raise ValueError("sequence parallelism requires a mesh")
        if cfg.sp_attention not in ("ring", "ulysses"):
            # Both schemes are numerically exact, so a typo would
            # silently benchmark the wrong communication pattern.
            raise ValueError(
                f"sp_attention={cfg.sp_attention!r}: expected 'ring' "
                f"or 'ulysses'")
        if cfg.sp_attention == "ulysses":
            attn_impl = make_ulysses_attention(mesh, axis=AXIS_SEQ,
                                               causal=True)
        else:
            attn_impl = make_ring_attention(mesh, axis=AXIS_SEQ,
                                            causal=True)
        attn_impl = _repeated_kv(attn_impl)
    else:
        attn_impl = lambda q, k, v: flash_attention(q, k, v, True, None)  # noqa: E731
        if mesh is not None:
            # A Mosaic kernel cannot be partitioned by XLA: under a
            # sharded jit it must see per-device shards (batch over
            # dp/fsdp, query and KV heads over tp; no collective is added).
            # T stays whole: the plain kernel's causal mask is local, so on a
            # mesh with sp > 1 each sp device attends over the full
            # sequence, as it did before the wrapper.
            attn_impl = make_sharded_attention(attn_impl, mesh, axis=None)

    x = params["embed"].astype(cd)[tokens]
    x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)

    period = cfg.period
    block_fns = {kind: _remat(functools.partial(
        _block, cfg=cfg, rules=rules, positions=positions, kind=kind,
        attn_impl=attn_impl if kind == "full" else _window_attention(cfg)),
        cfg) for kind in set(period)}

    def scan_body(x, bp):
        aux = None
        for j, kind in enumerate(period):
            x, aux_j = block_fns[kind](x, _layer_of(bp, j, len(period)))
            aux = aux_j if aux is None else jax.tree.map(jnp.add, aux, aux_j)
        return x, aux

    x, aux_stacked = jax.lax.scan(
        scan_body, x, _by_period(params["blocks"], len(period)))
    if return_aux is not None:
        return_aux.update({k: jnp.sum(v)
                           for k, v in (aux_stacked or {}).items()})
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("btd,vd->btv", x, params["embed"].astype(cd))
    else:
        logits = jnp.einsum("btd,dv->btv", x, params["lm_head"].astype(cd))
    return with_logical_constraint(logits, ("batch", "seq", "vocab"), rules)


def _by_period(blocks, p: int):
    """The layers' stacks (L, ..) as the xs of a scan over periods of `p`
    layers, (L // p, p, ..): a reshape, no copy.  Period 1: as they are."""
    if p == 1:
        return blocks
    return jax.tree.map(
        lambda a: a.reshape(a.shape[0] // p, p, *a.shape[1:]), blocks)


def _layer_of(bp, j: int, p: int):
    """Layer `j` of one period's slice of `_by_period`."""
    return bp if p == 1 else jax.tree.map(lambda a: a[j], bp)


def _window_attention(cfg: TransformerConfig):
    """A window layer's attention on the train and offline path: the
    masked XLA reference, and it says so; never full attention in
    silence.  The Pallas kernel has no window mask yet (ROADMAP R4)."""
    def impl(q, k, v):
        warnings.warn(
            f"window attention (window {cfg.window}) takes the XLA "
            f"reference (O(T^2) memory) for q{q.shape}: the Pallas kernel "
            f"has no window mask", stacklevel=2)
        return mha_reference(q, k, v, causal=True, window=cfg.window)
    return impl


def _remat(block_fn, cfg: TransformerConfig):
    if cfg.remat:
        if cfg.remat_policy == "dots":
            block_fn = jax.checkpoint(
                block_fn,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        elif cfg.remat_policy == "ff":
            # Save only the big FF activation (w_down's input): kills
            # that recompute matmul for ~1/3 the HBM of "dots".
            if cfg.n_experts > 0:
                raise ValueError(
                    "remat_policy='ff' names only the dense-MLP "
                    "activation; with n_experts > 0 nothing would be "
                    "saved (silent full remat) — use 'dots' or 'full'")
            block_fn = jax.checkpoint(
                block_fn,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "ff_hidden"))
        else:
            block_fn = jax.checkpoint(block_fn)
    return block_fn


def loss_fn(params, batch, cfg: TransformerConfig, *,
            rules: LogicalRules = DEFAULT_RULES, mesh: Mesh | None = None,
            seq_shards: int = 1):
    """Next-token cross entropy in f32.  batch: {"tokens": (B, T+1) int32}
    or {"tokens": (B,T), "targets": (B,T)}."""
    tokens = batch["tokens"]
    if "targets" in batch:
        inputs, targets = tokens, batch["targets"]
    else:
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
    aux: dict = {}
    logits = forward(params, inputs, cfg, rules=rules, mesh=mesh,
                     seq_shards=seq_shards,
                     return_aux=aux).astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("mask")
    nll = logz - tgt
    if mask is not None:
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    else:
        loss = jnp.mean(nll)
    if aux:  # MoE auxiliary losses (load balance + z-loss)
        loss = loss + 0.01 * aux.get("moe_load_balance_loss", 0.0) \
            + aux.get("moe_z_loss", 0.0)
    return loss


class Transformer:
    """Thin OO veneer over the functional API (config + params bundle)."""

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg

    def init(self, rng):
        return init_params(rng, self.cfg)

    def logical_axes(self):
        return param_logical_axes(self.cfg)

    def apply(self, params, tokens, **kw):
        return forward(params, tokens, self.cfg, **kw)

    def loss(self, params, batch, **kw):
        return loss_fn(params, batch, self.cfg, **kw)
