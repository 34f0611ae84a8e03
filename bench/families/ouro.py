"""The ouro family: everything the harness knows of Ouro-2.6B (`model_type:
ouro`, ByteDance; a looped language model, "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741): a dense decoder whose whole
stack of layers is applied `total_ut_steps` times in a row to the hidden
state, with the same weights, each sub-block between two norms, the final
norm after every pass, and an exit gate behind it.  A configuration file
says `"family": "ouro"`; what the harness asks of a family is listed at the
top of families/mistral.py.  This one also gives `attn_kv_bytes_per_launch`
(for `loop_attn_roofline.decode`), `control` (for bench/tools/controls.py)
and `TOLERANCES`, with its measurements beside it.

The model.  N(u; w) = u / rms(u) * w in float32 with eps `rms_norm_eps` (a
plain gain), no bias in any projection, R = `total_ut_steps`, L =
`num_hidden_layers`:

    h_0 = E[token]
    for r in 0 .. R-1:                      the same weights in every pass
        x = h_r
        for l in 0 .. L-1:
            x = x + N(Attn_l(N(x; input_layernorm_l)); input_layernorm_2_l)
            x = x + N(MLP_l(N(x; post_attention_layernorm_l));
                      post_attention_layernorm_2_l)
        h_{r+1} = N(x; norm)                after every pass; feeds the next
        lam_r   = sigmoid(h_{r+1} . w_gate + b_gate)        one value a row
    logits = h_{e+1} W_head                 e: the row's exit pass, below

  Attn_l in pass r: q = u Wq, k = u Wk, v = u Wv (`num_attention_heads`
        heads of `head_dim`, as many KV heads: no grouping at the published
        sizes, grouped where a configuration says fewer); rope by
        half-rotation over the whole head at theta `rope_theta`; causal
        softmax(q k^T / sqrt(head_dim)) v **over the keys and values that
        pass r of layer l made**, never another pass's; Wo.
  MLP_l W_down (silu(u W_gate) * (u W_up)), width `intermediate_size`.
  Exit  p_r = lam_r prod_{s<r} (1 - lam_s) for r < R-1, the last pass takes
        what is left; C_r = sum_{s<=r} p_s; a row exits at the first r with
        C_r >= `early_exit_threshold` (the last pass where none does), and
        its logits are read from that pass's h_{r+1}.  Every pass still
        runs for every row (later positions attend over all R caches), so
        the gate chooses which state the head reads and saves nothing.  At
        the published threshold 1 that is the last pass for every row.

The reference below is those equations in plain `jax.numpy` float32,
independent of `ray_tpu/`: two Python loops (passes, layers), attention
with an explicit mask in blocks of queries, no cache: pass r attends over
pass r's own keys of the whole sequence.  It shares only the parameter
tree's layout, which is data:

    embed (V,d)  lm_head (d,V)  final_norm (d,)  exit_gate.w (d,)  .b ()
    blocks.{attn_norm, attn_post_norm, mlp_norm, mlp_post_norm} (L,d)
    blocks.wq (L,d,H*hd)  blocks.{wk,wv} (L,d,Hkv*hd)  blocks.wo (L,H*hd,d)
    blocks.{w_gate,w_up} (L,d,f)  blocks.w_down (L,f,d)

Callers run it under `jax.default_matmul_precision("highest")`.

**Nothing of the model is cut**: all 48 layers, all 4 passes, every head,
the whole vocabulary are held on the one chip, so the model-configs guide's
test that the shares of a cut layer add up to the uncut one has nothing to
add up and does not apply.

Assumed, because `config.json` leaves them to the model's code (the
configuration file lists each under `assumed` with its ground): the two
norms behind the sub-blocks and their names; `norm` between passes as well
as before the head; a cache a pass; the gate's form, its bias and the rule
above; no bias on q / k / v / o; half-rotation rope; the gate's weights
drawn so that lam lies in (0.1, 0.9).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.spec import SpecError

F32 = jnp.float32

# The comparison that decides `correct` (bench/harness/reference.py), for
# this family: no router, so every compared position is held to LOGITS_REL,
# all 68 of a run (`check`: 4 lanes x (the last of 250 prompt positions,
# prefilled in launches of 128 and 122 rows: every pass's KV handed from
# one launch to the next through the pool, a ragged tail, + 16 decode
# steps through the decode kernel)).  192 layer passes in bfloat16 against
# float32 is the deepest chain any cell compares.  Measured on the chip at
# the published widths, whole (my chip runs, PR 69, calls B and C; PERF.md
# section 7 has the table).
#
# LOGITS_REL: rms error of a position's logits as a share of the
# reference's own; the verdict holds the worst of the 68 positions to it.
#   The program as it is, eleven seeds (calls B and C: two bare checks
#   and the checks of nine runs of the cell, seven of them from the final
#   tree's archive): a position's error has medians 0.0284-0.0296 and a
#   largest a seed of 0.0304-0.0354.  **By
#   the count of passes** (`total_ut_steps` 2 / 3 / 4, one seed): medians
#   0.0234 / 0.0262 / 0.0289, largest 0.0259 / 0.0286 / 0.0319: a tenth
#   more a pass of 48 layers.  That is so because the gains of the norms
#   behind the sub-blocks are drawn about (2 L)^-1/2 = 0.102
#   (`models/transformer.py:init_params`).  **Drawn about 1 (call A) the
#   same check read medians 0.215-0.258, largest 0.230-0.292, and by passes
#   0.034 / 0.089 / 0.22: 2.6-fold a pass.**  A sub-block's normed output is
#   then as large as the stream it joins, a perturbation of a pass's input
#   leaves the pass's norm 2.6 times its relative size, and rounding of
#   0.013 in the first pass is a quarter of the logits by the fourth: the
#   seeded model is chaotic, which no trained one is, and a fault as large
#   as the final norm applied twice (0.27-0.29 there) hid under it.
#   **The pool in 8-bit floats** (`control("cache_fp8")`: K and V of every
#   plane through 4 bits of exponent and 3 of mantissa, the nearest
#   precision below the stated bfloat16; two seeds, call B): medians
#   0.0474-0.0475, largest 0.0536-0.0543.  0.044 lies between 0.0354 and
#   0.0536 with a factor of 1.24 below and 1.22 above; the control is
#   refused on both seeds.  (K and V are a small part of what a position's
#   logits rest on where 192 sub-blocks each add a tenth of a unit: the
#   room is narrow, and the readings are steady: 0.002 between seeds.)
#   The four faults of `control`, one seed (call B): a pass left out 0.366
#   (largest 0.399), pass 0's planes read by every pass 0.940, the second
#   norms left out 1.369, the final norm applied twice 0.103 (0.111): each
#   at least 2.3 times the limit.
#   **What it cannot see:** a layer computed in bfloat16 where the
#   configuration says bfloat16 (the stated dtype is the program's); a row
#   that leaves at another pass than the reference's where its summed exit
#   probability lies within rounding of a threshold under 1 (the cell runs
#   the published threshold 1: the last pass; tier-1 holds 0.6 in float32).
TOLERANCES = {"LOGITS_REL": 0.044}


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


def program_config(config: dict):
    import dataclasses

    from ray_tpu.models.transformer import TransformerConfig

    needs = {"loop_passes", "post_norm", "exit_threshold"}
    lacks = needs - {f.name for f in dataclasses.fields(TransformerConfig)}
    if lacks:
        _withdraw_app()
        raise SpecError(
            f"this program's TransformerConfig has no {sorted(lacks)}: it "
            f"cannot run a configuration of the ouro family")
    if set(config["layer_types"]) != {"full_attention"} \
            or len(config["layer_types"]) < config["num_hidden_layers"] \
            or config.get("sliding_window") or config.get("rope_scaling") \
            or config.get("use_sliding_window"):
        raise SpecError("the family's layers are full attention under an "
                        "unscaled rope: layer_types, sliding_window, "
                        "rope_scaling say otherwise")
    if config["hidden_act"] != "silu" or config.get("tie_word_embeddings"):
        raise SpecError("the family's FFN is a SwiGLU and its head untied")
    return TransformerConfig(
        name=config["name"],
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_head=config["head_dim"],
        d_ff=config["intermediate_size"],
        loop_passes=config["total_ut_steps"],
        post_norm=True,
        exit_threshold=float(config["early_exit_threshold"]),
        rope_theta=float(config["rope_theta"]),
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
def _rms_norm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(F32)


def _rope(x, theta):
    """x (T, heads, hd): rotate pairs (i, i + hd / 2) of the whole head."""
    t, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


_QUERY_BLOCK = 512


def attention(u, p, c):
    """One pass of one layer's attention over the normed input u (T, d):
    the keys and values are this call's own, queries _QUERY_BLOCK at a
    time against the whole context."""
    t = u.shape[0]
    h, hkv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    q = _rope((u @ p["wq"].astype(F32)).reshape(t, h, hd),
              float(c["rope_theta"]))
    k = _rope((u @ p["wk"].astype(F32)).reshape(t, hkv, hd),
              float(c["rope_theta"]))
    v = (u @ p["wv"].astype(F32)).reshape(t, hkv, hd)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    out = []
    for lo in range(0, t, _QUERY_BLOCK):
        hi = min(lo + _QUERY_BLOCK, t)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k) / jnp.sqrt(F32(hd))
        seen = jnp.arange(t)[None, :] <= jnp.arange(lo, hi)[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", prob, v))
    return jnp.concatenate(out, 0).reshape(t, h * hd) @ p["wo"].astype(F32)


def swiglu(u, p):
    return (jax.nn.silu(u @ p["w_gate"].astype(F32))
            * (u @ p["w_up"].astype(F32))) @ p["w_down"].astype(F32)


def block(x, p, c):
    """One pass of one layer on one sequence x (T, d): each sub-block
    between two norms."""
    eps = c["rms_norm_eps"]
    x = x + _rms_norm(attention(_rms_norm(x, p["attn_norm"], eps), p, c),
                      p["attn_post_norm"], eps)
    return x + _rms_norm(swiglu(_rms_norm(x, p["mlp_norm"], eps), p),
                         p["mlp_post_norm"], eps)


def pass_end(x, final_norm, gate, c):
    """What follows a pass: (h = N(x; norm), lam (T,))."""
    h = _rms_norm(x, final_norm, c["rms_norm_eps"])
    return h, jax.nn.sigmoid(h @ gate["w"].astype(F32)
                             + gate["b"].astype(F32))


def exit_passes(lams, threshold: float):
    """lams (R, T) -> each row's exit pass (T,) int32: the first r at which
    the summed exit probability reaches `threshold`, the last pass where
    none does (its probability is what is left)."""
    left = jnp.ones_like(lams[0])
    gone = jnp.zeros_like(lams[0])
    out = jnp.full(lams[0].shape, len(lams) - 1, jnp.int32)
    done = jnp.zeros(lams[0].shape, bool)
    for r, lam in enumerate(lams):
        gone = gone + (lam * left if r < len(lams) - 1 else left)
        leaves = ~done & (gone >= threshold)
        out = jnp.where(leaves, r, out)
        done, left = done | leaves, left * (1.0 - lam)
    return out


_HEAD_BLOCKS = 8


def _head(h, lm_head, jit):
    """h (T, d) W_head -> (T, V) float32 **on the host**, an eighth of the
    vocabulary at a time (families/glm4moelite.py says why: the lanes'
    logits on the device stood beside the engine's pool and parameters)."""
    vocab = lm_head.shape[1]
    n = _HEAD_BLOCKS if vocab % _HEAD_BLOCKS == 0 else 1
    cols = vocab // n
    part = jit(lambda x, w: x @ w.astype(F32))
    out = np.empty((h.shape[0], vocab), np.float32)
    for i in range(n):
        out[:, i * cols:(i + 1) * cols] = part(
            h, lm_head[:, i * cols:(i + 1) * cols])
    return out


def forward(params, tokens, c, jit=lambda f: f, passes=False):
    """tokens (T,) int32 -> (logits (T, V) float32 on the host, margin
    (T,), infinite: no router), one sequence.  Parameters are cast to
    float32 a layer at a time, at their use.  `jit=jax.jit` compiles the
    layer once and runs it R x L times.  `passes`: also each row's exit
    pass, (T,)."""
    block_fn = jit(functools.partial(block, c=c))
    end_fn = jit(functools.partial(pass_end, c=c))
    x = params["embed"][tokens].astype(F32)
    states, lams = [], []
    for _ in range(c["total_ut_steps"]):
        for i in range(c["num_hidden_layers"]):
            x = block_fn(x, {n: a[i] for n, a in params["blocks"].items()})
        x, lam = end_fn(x, params["final_norm"], params["exit_gate"])
        states.append(x)
        lams.append(lam)
    took = exit_passes(lams, float(c["early_exit_threshold"]))
    h = jnp.take_along_axis(jnp.stack(states), took[None, :, None],
                            axis=0)[0]
    out = (_head(h, params["lm_head"], jit),
           jnp.full(x.shape[:1], jnp.inf, F32))
    return (*out, took) if passes else out


def row_loss(params, row, c, jit=lambda f: f):
    """Mean next-token cross entropy of one row (T+1,) under the head's
    logits, float32.  (The model's training loss is over the exit
    distribution, which `config.json` does not give: the program trains no
    such model and no cell asks for this.)"""
    logits, _ = forward(params, row[:-1], c, jit=jit)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, row[1:, None], axis=-1)[:, 0]
    return jnp.mean(logz - tgt)


# ---------------------------------------------------------------------------
# the engine's own logits
# ---------------------------------------------------------------------------
def score(e, config: dict, seqs, n_prompt: int):
    """The engine's scoring entry, its prompts prefilled in launches of
    `prefill_chunk` rows (the launches a tick beside a burst uses; an idle
    engine's wider tiers would take the check's prompt in one): every
    pass's KV is handed from one launch to the next through the pool, and
    the last launch is padded."""
    tiers = e._chunk_tiers
    e._chunk_tiers = [t for t in tiers if t <= e.prefill_chunk]
    try:
        return e.score(seqs, n_prompt)
    finally:
        e._chunk_tiers = tiers


# What bench/tools/controls.py prints beside a verdict: nothing this
# reference reads of its own.
LAST: dict = {}

FAULTS = ("pass_left_out", "plane_of_another_pass", "no_post_norm",
          "final_norm_twice", "cache_fp8")


def control(fault: str, cfg):
    """For bench/tools/controls.py and the tests: (the program
    configuration, a function that undoes the patch) of `sound` and of one
    fault at a time in the program, each of which the comparison must
    refuse:

      pass_left_out          the stack run R - 1 times
      plane_of_another_pass  every pass reads the planes of pass 0 (and
                             writes its own)
      no_post_norm           a sub-block's output added as it is
      final_norm_twice       the head norms the last pass's normed state
                             again
      cache_fp8              K and V of every plane rounded to an 8-bit
                             float (4 bits of exponent, 3 of mantissa: the
                             nearest precision below the stated
                             `cache_dtype`) after every launch and step of
                             the scoring entry, by `lax.reduce_precision`
                             in a donated program of its own (a pair of
                             converts inside a jit the TPU compiler drops:
                             families/laguna.py)

    The readings are beside `TOLERANCES`."""
    import dataclasses

    if fault == "sound":
        return cfg, lambda: None
    if fault not in FAULTS:
        raise SystemExit(f"no fault {fault!r}")
    if fault == "pass_left_out":
        return dataclasses.replace(cfg, loop_passes=cfg.loop_passes - 1), \
            lambda: None
    from ray_tpu.models import decoding
    from ray_tpu.serve.llm import PagedLLMEngine

    if fault == "cache_fp8":
        inner = PagedLLMEngine.score

        def fp8(a):
            return jax.lax.reduce_precision(a, exponent_bits=4,
                                            mantissa_bits=3)

        # its own donated program: the pool is rounded where it lies (an
        # eager convert and back needs a second pool beside 11.8 GB)
        rounded = jax.jit(lambda cache: dataclasses.replace(
            cache, k=fp8(cache.k), v=fp8(cache.v)), donate_argnums=0)

        def score(e, seqs, n_prompt, **kw):
            if not getattr(e, "_rounds_cache", False):
                inner(e, np.ones((1, 9), np.int64), 8, **kw)  # builds them
                for name in ("_prefill_chunk_fn", "_score_step"):
                    program = getattr(e, name)

                    def keeping(*a, _program=program, **k):
                        cache, *rest = _program(*a, **k)
                        return (rounded(cache), *rest)

                    setattr(e, name, keeping)
                e._rounds_cache = True
            return inner(e, seqs, n_prompt, **kw)

        PagedLLMEngine.score = score
        return cfg, lambda: setattr(PagedLLMEngine, "score", inner)

    name, patched = {
        "plane_of_another_pass": ("paged_attention", lambda inner: (
            lambda q, k, v, layer, *a, **kw: inner(
                q, k, v, layer % cfg.n_of("full"), *a, **kw))),
        "no_post_norm": ("_post_norm", lambda inner: (
            lambda out, *a: out)),
        "final_norm_twice": ("_final_logits", lambda inner: (
            lambda params, x, c: inner(params, decoding.rms_norm(
                x, params["final_norm"], eps=c.norm_eps), c))),
    }[fault]
    inner = getattr(decoding, name)
    setattr(decoding, name, patched(inner))
    return cfg, lambda: setattr(decoding, name, inner)


# ---------------------------------------------------------------------------
# Operations and bytes a step needs, from shapes alone: what the algorithm
# requires, not what the program happens to execute.  A token costs the
# layers' weights and the KV once a pass, the head once.
# ---------------------------------------------------------------------------
def _dims(c: dict) -> dict:
    h, hd = c["num_attention_heads"], c["head_dim"]
    return {"d": c["hidden_size"], "v": c["vocab_size"], "q": h * hd,
            "kv": c["num_key_value_heads"] * hd,
            "f": c["intermediate_size"], "n": c["num_hidden_layers"],
            "r": c["total_ut_steps"]}


def _itemsize(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]


def layer_params(c: dict) -> int:
    """Matrix parameters of one layer: q, o, k, v and the SwiGLU."""
    s = _dims(c)
    return 2 * s["d"] * s["q"] + 2 * s["d"] * s["kv"] + 3 * s["d"] * s["f"]


def total_params(c: dict) -> int:
    """Matrix parameters held: the layers once (every pass shares them),
    the embedding and the untied head."""
    s = _dims(c)
    return s["n"] * layer_params(c) + 2 * s["v"] * s["d"]


def layer_passes(c: dict) -> int:
    """Layers one token goes through: R x L."""
    s = _dims(c)
    return s["r"] * s["n"]


def kv_row_bytes(c: dict) -> int:
    """K and V of one position in one plane of the pool (one pass of one
    layer)."""
    return 2 * _dims(c)["kv"] * _itemsize(
        c.get("cache_dtype", c["compute_dtype"]))


def attn_kv_bytes_per_launch(c: dict, live_kv_tokens: float) -> float:
    """Bytes one attention read of a decode step must move (one pass of
    one layer): K and V of the lanes' `live_kv_tokens` positions in one
    plane, whatever implements the read."""
    return kv_row_bytes(c) * live_kv_tokens


def attn_kv_bytes_per_step(c: dict, live_kv_tokens: float) -> float:
    """Those reads of a whole step: R x L planes (the tick log's
    `kv_read_tokens` x a row's bytes)."""
    return layer_passes(c) * attn_kv_bytes_per_launch(c, live_kv_tokens)


def expert_bytes_per_step(c: dict, lanes: int) -> float:
    return 0.0


def expert_operand(c: dict):
    return None


def decode_step_bytes(c: dict, live_kv_tokens: float, lanes: int) -> float:
    """Bytes one decode step of `lanes` tokens must move: every layer's
    weights once **a pass** (R streams of the layers: the same weights,
    read again, since 4.9 GB stay in no cache), the head once (the
    embedding's lookup is a gather), and the live positions' K and V in
    every plane: R x L x `live_kv_tokens` rows."""
    s = _dims(c)
    w = _itemsize(c["param_dtype"])
    return (layer_passes(c) * layer_params(c) + s["d"] * s["v"]) * w \
        + attn_kv_bytes_per_step(c, live_kv_tokens)


def prefill_flops(c: dict, tokens: float, context: float) -> float:
    """FLOPs that `tokens` prompt tokens need which together attend over
    `context` positions (a token at position p attends p + 1): the layers'
    matrices and the attention's scores and values, R times.  The head,
    once a prompt, is left out."""
    s = _dims(c)
    return layer_passes(c) * (2.0 * layer_params(c) * tokens
                              + 4.0 * s["q"] * context)


# ---------------------------------------------------------------------------
# for bench/tools/memory_fit.py
# ---------------------------------------------------------------------------
def serve_programs(config: dict, place):
    """What a replica of `config` keeps resident, as shapes, and its
    largest programs lowered at the engine's sizes: the widest decode
    burst and one prefill chunk."""
    from ray_tpu.models.decoding import (
        init_paged_cache, make_paged_engine_fns)

    cfg = program_config(config)
    eng = config["engine"]
    n_blocks = eng["num_slots"] * eng["max_len"] // eng["block_size"] + 1
    b_max = -(-eng["max_len"] // eng["block_size"])
    params = place(jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)))
    cache = place(jax.eval_shape(
        lambda: init_paged_cache(cfg, n_blocks, eng["block_size"])))
    rng = place(jax.eval_shape(lambda: jax.random.key(0)))
    chunk_fn, burst_fn, _ = make_paged_engine_fns(cfg)

    def arr(shape, dtype):
        return place(jax.ShapeDtypeStruct(shape, dtype))

    w, ch = eng["num_slots"], eng["prefill_chunk"]
    return {"params": params, "pool": cache}, [
        (f"paged_decode_burst w={w}", burst_fn.lower(
            params, cache, arr((w,), jnp.int32), arr((w, b_max), jnp.int32),
            arr((w,), jnp.int32), arr((w,), jnp.bool_),
            arr((w,), jnp.float32), rng, n_steps=eng["max_burst"])),
        (f"paged_prefill_chunk c={ch}", chunk_fn.lower(
            params, cache, arr((ch,), jnp.int32), arr((b_max,), jnp.int32),
            arr((), jnp.int32), arr((), jnp.int32)))]
