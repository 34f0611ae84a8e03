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
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.ring_attention import (
    make_ring_attention, make_sharded_attention)
from ray_tpu.ops.ulysses import make_ulysses_attention
from ray_tpu.ops.rotary import apply_rope
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

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def moe(self):
        if self.n_experts <= 0:
            return None
        from ray_tpu.ops.moe import MoEConfig

        return MoEConfig(num_experts=self.n_experts, top_k=self.expert_top_k,
                         capacity_factor=self.capacity_factor)

    @property
    def num_params(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        kv = self.n_kv_heads * self.head_dim
        mlp = 3 * d * f if self.n_experts <= 0 else \
            self.n_experts * 3 * d * f + d * self.n_experts
        per_layer = d * d * 2 + d * kv * 2 + mlp + 2 * d
        emb = v * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb + d


def init_params(rng: jax.Array, cfg: TransformerConfig):
    """Parameter pytree; per-layer tensors stacked on a leading L axis."""
    d, f, l = cfg.d_model, cfg.d_ff, cfg.n_layers
    hd = cfg.head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    keys = jax.random.split(rng, 8)
    dt = cfg.param_dtype

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)).astype(dt)

    blocks = {
        "attn_norm": jnp.ones((l, d), dt),
        "wq": dense(keys[1], (l, d, nh * hd), d),
        "wk": dense(keys[2], (l, d, nkv * hd), d),
        "wv": dense(keys[3], (l, d, nkv * hd), d),
        "wo": dense(keys[4], (l, nh * hd, d), nh * hd),
        "mlp_norm": jnp.ones((l, d), dt),
    }
    if cfg.n_experts > 0:
        e = cfg.n_experts
        blocks.update({
            "router": dense(jax.random.fold_in(keys[5], 1), (l, d, e), d),
            "w_gate": dense(keys[5], (l, e, d, f), d),
            "w_up": dense(keys[6], (l, e, d, f), d),
            "w_down": dense(keys[7], (l, e, f, d), f),
        })
    else:
        blocks.update({
            "w_gate": dense(keys[5], (l, d, f), d),
            "w_up": dense(keys[6], (l, d, f), d),
            "w_down": dense(keys[7], (l, f, d), f),
        })
    params = {
        "embed": dense(keys[0], (cfg.vocab_size, d), d ** 0.5 * d),  # ~N(0, 1/sqrt(d))
        "blocks": blocks,
        "final_norm": jnp.ones((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(jax.random.fold_in(rng, 99), (d, cfg.vocab_size), d)
    return params


def param_logical_axes(cfg: TransformerConfig):
    """Pytree of logical-axis tuples matching `init_params` exactly."""
    blocks = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
    }
    if cfg.n_experts > 0:
        blocks.update({
            "router": ("layers", "embed", "expert"),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        })
    else:
        blocks.update({
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        })
    axes = {
        "embed": ("vocab", "embed"),
        "blocks": blocks,
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
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


def _block(x, bp, cfg: TransformerConfig, rules: LogicalRules, *,
           attn_impl, positions):
    cd = cfg.compute_dtype
    h = rms_norm(x, bp["attn_norm"], eps=cfg.norm_eps)
    q = jnp.einsum("btd,dh->bth", h, bp["wq"].astype(cd))
    k = jnp.einsum("btd,dh->bth", h, bp["wk"].astype(cd))
    v = jnp.einsum("btd,dh->bth", h, bp["wv"].astype(cd))
    b, t = x.shape[:2]
    q = q.reshape(b, t, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, theta=cfg.rope_theta)
    k = apply_rope(k, positions, theta=cfg.rope_theta)
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
    cd = cfg.compute_dtype
    b, t = tokens.shape
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.int32)

    if seq_shards > 1:
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

    block_fn = functools.partial(_block, cfg=cfg, rules=rules,
                                 attn_impl=attn_impl, positions=positions)
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

    def scan_body(x, bp):
        x, aux = block_fn(x, bp)
        return x, aux

    x, aux_stacked = jax.lax.scan(scan_body, x, params["blocks"])
    if return_aux is not None:
        return_aux.update({k: jnp.sum(v)
                           for k, v in (aux_stacked or {}).items()})
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("btd,vd->btv", x, params["embed"].astype(cd))
    else:
        logits = jnp.einsum("btd,dv->btv", x, params["lm_head"].astype(cd))
    return with_logical_constraint(logits, ("batch", "seq", "vocab"), rules)


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
