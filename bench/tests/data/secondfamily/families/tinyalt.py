"""A second family, kept for bench/tests/test_families.py only: the tiny
model of bench/tests/data/tinyroot under other key names (`dim`,
`depth`, `heads`, ...), with its own copy of the plain float32
reference.  It enters a copy of the tiny benchmark as files and entries
alone.  It gives only what its one served cell asks for: no costs (no
roofline lists its cell), no `serve_programs`, no tolerances."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.harness import spec

F32 = jnp.float32


def program_config(config: dict):
    from ray_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        name=config["name"], vocab_size=config["vocab_size"],
        d_model=config["dim"], n_layers=config["depth"],
        n_heads=config["heads"], n_kv_heads=config["kv_heads"],
        d_ff=config["ffn_dim"], max_seq_len=config["max_seq"],
        rope_theta=float(config["rope_base"]),
        norm_eps=float(config["norm_eps"]), tie_embeddings=False,
        param_dtype=jnp.dtype(config["param_dtype"]),
        compute_dtype=jnp.dtype(config["compute_dtype"]),
        n_experts=int(config.get("experts", 0)),
        expert_top_k=int(config.get("experts_per_token", 2)))


def init_params(key, cfg):
    from ray_tpu.models.transformer import init_params as init

    return init(key, cfg)


def score(e, config, seqs, n_prompt):
    """The engine is the first family's, so its scoring entry serves."""
    return spec.family({"family": "mistral"}).score(e, config, seqs, n_prompt)


def _norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rotate(x, base):
    t, _, hd = x.shape
    ang = jnp.arange(t, dtype=F32)[:, None] \
        * base ** (-jnp.arange(hd // 2, dtype=F32) / (hd // 2))[None, :]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def layer(x, p, c):
    """One block on x (T, d): (output, each token's routing margin)."""
    p = {n: a.astype(F32) for n, a in p.items()}
    t, h, hkv = x.shape[0], c["heads"], c["kv_heads"]
    hd = c["dim"] // h
    y = _norm(x, p["attn_norm"], c["norm_eps"])
    q = _rotate((y @ p["wq"]).reshape(t, h, hd), c["rope_base"])
    k = _rotate((y @ p["wk"]).reshape(t, hkv, hd), c["rope_base"])
    v = (y @ p["wv"]).reshape(t, hkv, hd)
    k, v = (jnp.repeat(a, h // hkv, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    x = x + jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v).reshape(
        t, h * hd) @ p["wo"]
    y = _norm(x, p["mlp_norm"], c["norm_eps"])
    if not c.get("experts"):
        return (x + _swiglu(y, p["w_gate"], p["w_up"], p["w_down"]),
                jnp.full((t,), jnp.inf, F32))
    n = c["experts_per_token"]
    logits = y @ p["router"]
    top, idx = jax.lax.top_k(logits, n + 1)
    margin = (top[:, n - 1] - top[:, n]) \
        / jnp.sqrt(jnp.mean(logits * logits, -1))
    gates = jax.nn.softmax(top[:, :n], -1)
    out = jnp.zeros_like(x)
    for j in range(c["experts"]):
        w = jnp.sum(jnp.where(idx[:, :n] == j, gates, 0.0), -1, keepdims=True)
        out = out + w * _swiglu(y, p["w_gate"][j], p["w_up"][j],
                                p["w_down"][j])
    return x + out, margin


def forward(params, tokens, c, jit=lambda f: f):
    """tokens (T,) -> (logits (T, V) float32, margin (T,))."""
    fn = jit(functools.partial(layer, c=c))
    x = params["embed"][tokens].astype(F32)
    margin = jnp.full(x.shape[:1], jnp.inf, F32)
    for i in range(c["depth"]):
        x, m = fn(x, {n: a[i] for n, a in params["blocks"].items()})
        margin = jnp.minimum(margin, m)
    return _norm(x, params["final_norm"].astype(F32), c["norm_eps"]) \
        @ params["lm_head"].astype(F32), margin
