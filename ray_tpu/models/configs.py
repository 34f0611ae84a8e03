"""Named model configs (GPT-2 124M, Llama-3-8B, Llama-2-7B-class, Mixtral,
plus test/bench sizes)."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from ray_tpu.models.hybrid import HybridConfig
from ray_tpu.models.mamba2_moe import Mamba2MoEConfig
from ray_tpu.models.mla_moe import MLAMoEConfig
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.ops.rotary import YarnScaling

TINY = TransformerConfig(
    name="tiny", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=2, d_ff=128, max_seq_len=256, remat=False,
)

# GPT-2 small scale (124M-class), llama-ified architecture.
GPT2_124M = TransformerConfig(
    name="gpt2-124m", vocab_size=50304, d_model=768, n_layers=12, n_heads=12,
    n_kv_heads=12, d_ff=3072, max_seq_len=1024, tie_embeddings=True,
)

# ~350M bench model: fits one chip with Adam state, big enough to load the MXU.
BENCH_350M = TransformerConfig(
    name="bench-350m", vocab_size=32000, d_model=1024, n_layers=24, n_heads=16,
    n_kv_heads=16, d_ff=4096, max_seq_len=2048,
)

# ~1.4B GPT-2-XL-class bench point: fits a 16GB-HBM chip with remat +
# bf16 compute + a FACTORED optimizer (adafactor — fp32 Adam m/v alone
# would be ~11GB; factored second moments are the standard big-model-on-
# small-HBM choice, as in T5/PaLM training).
BENCH_1B4 = TransformerConfig(
    name="bench-1b4", vocab_size=32000, d_model=2048, n_layers=20,
    n_heads=16, n_kv_heads=16, d_ff=8192, max_seq_len=2048,
)

LLAMA2_7B = TransformerConfig(
    name="llama2-7b", vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
    n_kv_heads=32, d_ff=11008, max_seq_len=4096,
)

LLAMA3_8B = TransformerConfig(
    name="llama3-8b", vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
    n_kv_heads=8, d_ff=14336, max_seq_len=8192, rope_theta=500000.0,
)

TINY_MOE = TransformerConfig(
    name="tiny-moe", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=4, d_ff=128, max_seq_len=256, remat=False,
    n_experts=4, expert_top_k=2,
)

MIXTRAL_8X7B = TransformerConfig(
    name="mixtral-8x7b", vocab_size=32000, d_model=4096, n_layers=32,
    n_heads=32, n_kv_heads=8, d_ff=14336, max_seq_len=8192,
    rope_theta=1000000.0, n_experts=8, expert_top_k=2,
)

# The decoder-hybrid-decoder stack (models/hybrid.py) at test size: two
# (Mamba, window) pairs, the (Mamba, full) pair, one (GMU, cross) pair.
TINY_HYBRID = HybridConfig(
    name="tiny-hybrid", vocab_size=512, d_model=64, n_layers=8, n_heads=8,
    n_kv_heads=4, d_ff=128, window=24, d_state=4, dt_rank=8,
    max_seq_len=512, param_dtype=jnp.float32,
)

# Phi-4-mini-flash-reasoning's published sizes (3.85 B parameters).
PHI4_MINI_FLASH = HybridConfig(name="phi4-mini-flash")

# A layer pattern in the homogeneous stack (three window layers to one
# full layer with its own YaRN rope), a head size the width does not
# give, QK-norm and many small experts, at test size: the window (12) is
# shorter than the tests' prompts and so is one turn of its ring.
TINY_WINDOW_MOE = TransformerConfig(
    name="tiny-window-moe", vocab_size=512, d_model=48, n_layers=8,
    n_heads=4, n_kv_heads=2, d_head=16, d_ff=160, d_expert=24,
    n_experts=8, expert_top_k=4, qk_norm=True,
    layer_pattern=("window", "window", "window", "full"), window=12,
    rope_theta=10000.0,
    yarn=YarnScaling(factor=4.0, original_max_len=32, beta_fast=4.0,
                     beta_slow=1.0, attention_factor=1.138629436111989),
    norm_eps=1e-6, max_seq_len=512, remat=False,
)

# Mellum2-12B-A2.5B-Instruct's published sizes (12.15 B parameters, 2.43 B
# active a token): 64 experts of width 896, top-8; `d_ff` (the config's
# intermediate_size) is used by no layer.
MELLUM2_12B = dataclasses.replace(
    TINY_WINDOW_MOE, name="mellum2-12b", vocab_size=98304, d_model=2304,
    n_layers=28, n_heads=32, n_kv_heads=4, d_head=128, d_ff=7168,
    d_expert=896, n_experts=64, expert_top_k=8, window=1024,
    rope_theta=500000.0,
    yarn=YarnScaling(factor=16.0, original_max_len=8192, beta_fast=32.0,
                     beta_slow=1.0, attention_factor=1.2772588722239782),
    max_seq_len=131072, param_dtype=jnp.bfloat16)

# The state-space / attention hybrid with routed experts
# (models/mamba2_moe.py) at test size: two periods of (Mamba, Mamba,
# attention, Mamba), 8 experts top-3 of which this rank holds the upper
# four, a shared expert, multipliers away from 1.
TINY_MAMBA2_MOE = Mamba2MoEConfig(
    name="tiny-mamba2-moe", vocab_size=512, d_model=64, n_layers=8,
    layer_pattern=("mamba", "mamba", "attention", "mamba"), n_heads=4,
    n_kv_heads=2, d_head=16, attention_scale=1.0 / 16, ssm_heads=8,
    ssm_head_dim=16, d_state=8, n_experts=8, expert_top_k=3, d_expert=24,
    d_shared=48, experts_held=(4, 4), embedding_multiplier=3.0,
    residual_multiplier=0.5, logits_scaling=4.0, max_seq_len=512,
    param_dtype=jnp.float32)

# granite-4.0-h-small's published sizes (32 B parameters, 9 B active a
# token), every expert held.
GRANITE4_H_SMALL = Mamba2MoEConfig(name="granite-4.0-h-small")

# Latent attention with scored experts (models/mla_moe.py) at test size:
# a dense first layer and three expert layers, 8 experts top-3 by biased
# sigmoid scores of which this rank holds the lower four, a shared
# expert; a stored row of 24 + 8 values padded to a tile of 128.
TINY_MLA_MOE = MLAMoEConfig(
    name="tiny-mla-moe", vocab_size=512, d_model=64, n_layers=4,
    n_dense_layers=1, n_heads=4, q_rank=32, kv_rank=24, d_nope=12,
    d_rope=8, d_v=16, d_ff=96, n_experts=8, expert_top_k=3, d_expert=24,
    d_shared=24, route_scale=1.8, experts_held=(0, 4), rope_theta=10000.0,
    max_seq_len=512, param_dtype=jnp.float32)

# GLM-4.7-Flash's published sizes (29.9 B parameters, ~3 B active a
# token), every expert held; its multi-token-prediction module is not
# part of the stack.
GLM_4_7_FLASH = MLAMoEConfig(name="glm-4.7-flash")

# `MLAMoEConfig`'s two kinds of layer at test size: a dense full layer,
# one period of (full, window, window, window) and two layers more; a full
# layer's 4 heads attend to the 16 positions that 4 index heads of 16 score
# highest, a window layer's 2 heads over its own, larger latent to the 12
# positions up to their own; a gate a head, the latents rescaled.
TINY_DSA_MOE = dataclasses.replace(
    TINY_MLA_MOE, name="tiny-dsa-moe", n_layers=7,
    lead_pattern=("full",),
    layer_pattern=("full", "window", "window", "window"), window=12,
    n_heads_window=2, q_rank_window=32, kv_rank_window=40, d_nope_window=20,
    d_v_window=16, rope_theta_window=500.0, attn_gate=True, latent_rescale=True,
    index_heads=4, index_dim=16, index_top_k=16, route_scale=1.0)

# dots3-note-prev's published sizes, the language model (279.6 B
# parameters of the published 288 B: the towers and the multi-token
# prediction module are not part of the stack), every expert held: a dense
# full layer, a full layer, then three window layers to one full layer.
DOTS3_NOTE_PREV = MLAMoEConfig(
    name="dots3-note-prev", vocab_size=152064, d_model=5120, n_layers=46,
    n_dense_layers=1, n_heads=128, q_rank=1024, kv_rank=512, d_nope=128,
    d_rope=64, d_v=128, d_ff=13824, n_experts=256, expert_top_k=8,
    d_expert=1536, d_shared=1536, route_scale=1.0, rope_theta=8e7,
    max_seq_len=524288, lead_pattern=("full",),
    layer_pattern=("full", "window", "window", "window"), window=513,
    n_heads_window=64, kv_rank_window=1024, d_nope_window=192,
    rope_theta_window=5e4, attn_gate=True, latent_rescale=True,
    index_heads=64, index_dim=128, index_top_k=2048)

# `MLAMoEConfig` with a residual of four streams at test size: two dense
# layers and three expert layers (the scan runs three periods), every
# stream-to-stream matrix projected by 20 Sinkhorn-Knopp rounds, the rope
# under YaRN in DeepSeek's convention (cos and sin x 1, the soft-max scale
# x (0.1 ln 8 + 1)^2), all 8 experts held, top-3, a shared expert.
TINY_MHC_MLA_MOE = dataclasses.replace(
    TINY_MLA_MOE, name="tiny-mhc-mla-moe", n_layers=5, n_dense_layers=2,
    experts_held=None, route_scale=2.0, hc_mult=4,
    yarn=YarnScaling(factor=8.0, original_max_len=64, attention_factor=1.0),
    yarn_mscale_all_dim=1.0)

# Xing4.0-29B-A4B's published sizes (29.5 B parameters, ~4 B active a
# token), every expert held: two dense layers, 38 expert layers, four
# residual streams a position; its multi-token-prediction module is not
# part of the stack.
XING4_0_29B_A4B = MLAMoEConfig(
    name="xing4.0-29b-a4b", vocab_size=131072, d_model=3584, n_layers=40,
    n_dense_layers=2, n_heads=32, q_rank=768, kv_rank=512, d_nope=128,
    d_rope=64, d_v=128, d_ff=9216, n_experts=64, expert_top_k=4,
    d_expert=1024, d_shared=1024, route_scale=2.0, rope_theta=10000.0,
    norm_eps=1e-6, max_seq_len=262144, hc_mult=4,
    yarn=YarnScaling(factor=64.0, original_max_len=4096, beta_fast=32.0,
                     beta_slow=1.0, attention_factor=1.0),
    yarn_mscale_all_dim=1.0)

# Latent attention that selects in every layer with experts chosen group
# by group, at test size: a dense layer and four expert layers, all full;
# 4 heads attend to the 16 positions that 4 index heads of 16 score
# highest, under YaRN (cos and sin x 1, the soft-max scale x (0.1 ln 8 +
# 1)^2); 16 experts in 4 groups of 4 of which 2 groups are kept, top-3,
# gates x 2.5, this rank holding half of group 0 (experts 0-1); a shared
# expert.  Pool blocks alone are the sequence: prefixes are shared,
# blocks copied on write, frames shipped.
TINY_GROUP_MOE = dataclasses.replace(
    TINY_MLA_MOE, name="tiny-group-moe", n_layers=5, n_experts=16,
    experts_held=(0, 2), expert_groups=4, expert_groups_kept=2,
    route_scale=2.5, norm_eps=1e-6, index_heads=4, index_dim=16,
    index_top_k=16,
    yarn=YarnScaling(factor=8.0, original_max_len=64, attention_factor=1.0),
    yarn_mscale_all_dim=1.0)

# DeepSeek-V3.2-Exp's published sizes (671.9 B parameters for the 61
# layers, embedding and head; ~37 B active a token), every expert held:
# three dense layers, 58 expert layers of 256 experts in 8 groups of which
# 4 are kept, every layer selecting its 2,048 positions; its
# multi-token-prediction module is not part of the stack.
DEEPSEEK_V3_2_EXP = MLAMoEConfig(
    name="deepseek-v3.2-exp", vocab_size=129280, d_model=7168, n_layers=61,
    n_dense_layers=3, n_heads=128, q_rank=1536, kv_rank=512, d_nope=128,
    d_rope=64, d_v=128, d_ff=18432, n_experts=256, expert_top_k=8,
    d_expert=2048, d_shared=2048, route_scale=2.5, expert_groups=8,
    expert_groups_kept=4, rope_theta=10000.0, norm_eps=1e-6,
    max_seq_len=163840, index_heads=64, index_dim=128, index_top_k=2048,
    yarn=YarnScaling(factor=40.0, original_max_len=4096, beta_fast=32.0,
                     beta_slow=1.0, attention_factor=1.0),
    yarn_mscale_all_dim=1.0)

# What a layer can have of its own in the homogeneous stack, at test
# size: a dense first layer before two periods of three window layers to
# one full layer, 8 query heads in a window layer and 6 in a full one over
# 2 KV heads (groups of 4 and of 3), a full layer's rope under YaRN over
# half a head and a window layer's unscaled over the whole, a gate a head
# on the attention's output, 8 experts top-3 by sigmoid scores x 2.5 of
# which this rank holds the lower four, a shared expert.
TINY_GATED_MOE = TransformerConfig(
    name="tiny-gated-moe", vocab_size=512, d_model=48, n_layers=9,
    n_heads=6, n_heads_window=8, n_kv_heads=2, d_head=16, d_ff=160,
    d_expert=24, d_shared=24, n_experts=8, expert_top_k=3,
    experts_held=(0, 4), expert_scoring="sigmoid", route_scale=2.5,
    attn_gate=True, lead_pattern=("full",),
    layer_pattern=("window", "window", "window", "full"), window=12,
    rope_theta=50000.0, rope_theta_window=10000.0, rotary_dim=8,
    rotary_dim_window=16,
    yarn=YarnScaling(factor=4.0, original_max_len=32, beta_fast=4.0,
                     beta_slow=1.0, attention_factor=1.138629436111989),
    norm_eps=1e-6, max_seq_len=512, remat=False,
)

# Laguna-XS.2's published sizes (33.44 B parameters, 3.0 B active a
# token): every expert held.  40 layers are the dense one, nine periods
# and three window layers more.
LAGUNA_XS_2 = dataclasses.replace(
    TINY_GATED_MOE, name="laguna-xs.2", vocab_size=100352, d_model=2048,
    n_layers=40, n_heads=48, n_heads_window=64, n_kv_heads=8, d_head=128,
    d_ff=8192, d_expert=512, d_shared=512, n_experts=256, expert_top_k=8,
    experts_held=None, window=512, rope_theta=500000.0, rotary_dim=64,
    rotary_dim_window=128,
    yarn=YarnScaling(factor=64.0, original_max_len=4096, beta_fast=64.0,
                     beta_slow=1.0, attention_factor=1.4158883083359672),
    max_seq_len=262144, param_dtype=jnp.bfloat16)

# Generation by diffusion over blocks in the homogeneous stack, at test
# size: blocks of 4 positions filled in 2 denoising passes and committed,
# QK-norm, a head size the width does not give, 8 small experts top-4, no
# shared expert; float32 so that a test holds it to the reference's tokens.
TINY_BLOCK_DIFFUSION_MOE = TransformerConfig(
    name="tiny-block-diffusion-moe", vocab_size=512, d_model=48, n_layers=3,
    n_heads=4, n_kv_heads=2, d_head=16, d_ff=160, d_expert=24, n_experts=8,
    expert_top_k=4, qk_norm=True, rope_theta=10000.0, norm_eps=1e-6,
    max_seq_len=512, remat=False, param_dtype=jnp.float32,
    compute_dtype=jnp.float32, diffusion_block=4, denoise_steps=2,
    mask_token_id=500,
)

# SDAR-30B-A3B-Chat's published sizes (30.53 B parameters, ~3 B active a
# token): 128 experts of width 768, top-8; `d_ff` (the config's
# intermediate_size) is used by no layer.  Block length, passes and the
# mask token are the family's released defaults, not keys of its config.
SDAR_30B_A3B = dataclasses.replace(
    TINY_BLOCK_DIFFUSION_MOE, name="sdar-30b-a3b", vocab_size=151936,
    d_model=2048, n_layers=48, n_heads=32, n_kv_heads=4, d_head=128,
    d_ff=6144, d_expert=768, n_experts=128, expert_top_k=8,
    rope_theta=1000000.0, max_seq_len=32768, param_dtype=jnp.bfloat16,
    compute_dtype=jnp.bfloat16, mask_token_id=151669)

# A third kind of layer in the homogeneous stack, at test size: two
# periods of three linear layers (Gated DeltaNet: 2 key heads serving 4
# value heads of 8, a convolution of 4 rows, chunks of 8 positions) to one
# full layer whose output is gated element by element and whose rope turns
# a quarter of a head; every norm's gain 1 + w; 8 experts top-3 of which
# this rank holds the upper four, beside a shared expert under a gate of
# its own.  float32, so that a test holds it to the reference's logits.
TINY_GATED_DELTA_MOE = TransformerConfig(
    name="tiny-gated-delta-moe", vocab_size=512, d_model=48, n_layers=8,
    n_heads=4, n_kv_heads=2, d_head=16, d_ff=160, d_expert=24, d_shared=24,
    n_experts=8, expert_top_k=3, experts_held=(4, 4), qk_norm=True,
    attn_gate=16, rotary_dim=4, shared_gate=True, norm_plus_one=True,
    layer_pattern=("linear", "linear", "linear", "full"), linear_k_heads=2,
    linear_v_heads=4, linear_d_k=8, linear_d_v=8, linear_conv=4,
    linear_chunk=8, rope_theta=10000.0, norm_eps=1e-6, max_seq_len=512,
    remat=False, param_dtype=jnp.float32, compute_dtype=jnp.float32,
)

# Qwen3-Next-80B-A3B-Instruct's published sizes (79.7 B parameters, ~3 B
# active a token), every expert held: twelve periods of three linear
# layers to one full layer, 512 experts of width 512, top-10; `d_ff` (the
# config's intermediate_size) is used by no layer; its multi-token-
# prediction module is not part of the stack.
QWEN3_NEXT_80B_A3B = dataclasses.replace(
    TINY_GATED_DELTA_MOE, name="qwen3-next-80b-a3b", vocab_size=151936,
    d_model=2048, n_layers=48, n_heads=16, n_kv_heads=2, d_head=256,
    d_ff=5120, d_expert=512, d_shared=512, n_experts=512, expert_top_k=10,
    experts_held=None, attn_gate=256, rotary_dim=64, linear_k_heads=16,
    linear_v_heads=32, linear_d_k=128, linear_d_v=128, linear_chunk=64,
    rope_theta=10000000.0, max_seq_len=262144, param_dtype=jnp.bfloat16,
    compute_dtype=jnp.bfloat16)

# A fourth kind of layer, at test size: two leading conv layers (gated
# short convolutions of 3 rows, `ops.short_conv`) with a dense FFN, two
# periods of one full layer (QK-norm, heads of 16) to three conv layers,
# and a tail that is no prefix of a period; 8 experts top-4 by sigmoid
# scores under a selection bias, all held; the head is the embedding.
# float32, so that a test holds it to the reference's logits.
TINY_SHORT_CONV_MOE = TransformerConfig(
    name="tiny-short-conv-moe", vocab_size=512, d_model=64, n_layers=13,
    n_heads=4, n_kv_heads=2, d_head=16, d_ff=160, d_expert=24, n_experts=8,
    expert_top_k=4, expert_scoring="sigmoid", router_bias=True, qk_norm=True,
    lead_pattern=("conv", "conv"),
    layer_pattern=("full", "conv", "conv", "conv"),
    layer_tail=("full", "conv", "full"), conv_kernel=3, rope_theta=10000.0,
    norm_eps=1e-5, tie_embeddings=True, max_seq_len=512, remat=False,
    param_dtype=jnp.float32, compute_dtype=jnp.float32,
)

# LFM2-8B-A1B's published sizes (8.34 B parameters, ~1.5 B active a
# token): `layer_types` item by item, 18 conv layers and 6 full layers of
# 32 query / 8 KV heads of 64; the two leading layers' FFN dense at 7168,
# the other 22 of 32 experts of width 1792, top-4, every one held.
LFM2_8B_A1B = dataclasses.replace(
    TINY_SHORT_CONV_MOE, name="lfm2-8b-a1b", vocab_size=65536, d_model=2048,
    n_layers=24, n_heads=32, n_kv_heads=8, d_head=64, d_ff=7168,
    d_expert=1792, n_experts=32,
    layer_tail=("full", "conv", "conv", "full", "conv", "conv"),
    rope_theta=1000000.0, max_seq_len=128000, param_dtype=jnp.bfloat16,
    compute_dtype=jnp.bfloat16)

# A stack run more than once, at test size: 3 layers x 3 passes over one
# set of weights (a position keeps 9 planes of the pool), 4 heads of 16
# ungrouped, a norm behind each sub-block, the final norm after every
# pass and an exit gate behind it, threshold 0.6: rows of one launch
# leave at different passes.  float32, so that a test holds it to the
# reference's logits.
TINY_LOOPED = TransformerConfig(
    name="tiny-looped", vocab_size=512, d_model=64, n_layers=3, n_heads=4,
    n_kv_heads=4, d_head=16, d_ff=160, loop_passes=3, post_norm=True,
    exit_threshold=0.6, rope_theta=10000.0, norm_eps=1e-6, max_seq_len=512,
    remat=False, param_dtype=jnp.float32, compute_dtype=jnp.float32,
)

# Ouro-2.6B's published sizes (2.67 B parameters): 48 full layers of 16
# query and 16 KV heads of 128, SwiGLU 5632, applied four times in a row
# (`total_ut_steps`); a position keeps 192 planes of K and V, 1.5 MiB in
# bfloat16; `early_exit_threshold` 1: the head reads the last pass.
OURO_2_6B = dataclasses.replace(
    TINY_LOOPED, name="ouro-2.6b", vocab_size=49152, d_model=2048,
    n_layers=48, n_heads=16, n_kv_heads=16, d_head=128, d_ff=5632,
    loop_passes=4, exit_threshold=1.0, rope_theta=1000000.0,
    max_seq_len=65536, param_dtype=jnp.bfloat16,
    compute_dtype=jnp.bfloat16)

REGISTRY = {c.name: c for c in [TINY, GPT2_124M, BENCH_350M, BENCH_1B4,
                                LLAMA2_7B,
                                LLAMA3_8B, TINY_MOE, MIXTRAL_8X7B,
                                TINY_HYBRID, PHI4_MINI_FLASH,
                                TINY_WINDOW_MOE, MELLUM2_12B,
                                TINY_MAMBA2_MOE, GRANITE4_H_SMALL,
                                TINY_MLA_MOE, GLM_4_7_FLASH,
                                TINY_DSA_MOE, DOTS3_NOTE_PREV,
                                TINY_MHC_MLA_MOE, XING4_0_29B_A4B,
                                TINY_GROUP_MOE, DEEPSEEK_V3_2_EXP,
                                TINY_GATED_MOE, LAGUNA_XS_2,
                                TINY_BLOCK_DIFFUSION_MOE, SDAR_30B_A3B,
                                TINY_GATED_DELTA_MOE, QWEN3_NEXT_80B_A3B,
                                TINY_SHORT_CONV_MOE, LFM2_8B_A1B,
                                TINY_LOOPED, OURO_2_6B]}


def get(name: str):
    return REGISTRY[name]
