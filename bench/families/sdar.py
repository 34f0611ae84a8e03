"""The sdar family: everything the harness knows of SDAR-30B-A3B-Chat
(`model_type: sdar_moe`, JetLM): a decoder of 48 alike layers (grouped-query
attention with QK-norm, 128 small experts of which a token takes 8, no
shared expert) that is not a next-token model: it **generates by diffusion
over blocks**.  A configuration file says `"family": "sdar"`; what the
harness asks of a family is listed at the top of families/mistral.py.  This
one gives what a served cell's metrics ask for (`score`, `forward`,
`expert_operand`, `expert_bytes_per_step`, `prefill_flops`,
`serve_programs`), and for its own metrics `denoise_pass_bytes`,
`select_operand` and `passes_per_block`, `generate_reference` for the CPU
tests, and `TOLERANCES`, with its measurements beside it.

The model, with block length B (`assumed.diffusion_block`) and
`blk(i) = i // B`, `u` a position's normed input, eps `rms_norm_eps`, no
biases, untied embedding and head (`intermediate_size` is used by no layer:
`decoder_sparse_step` 1, `mlp_only_layers` []):

    x = E[token];  x += Attn_l(RMSNorm(x));  x += MoE_l(RMSNorm'(x))
    logits = RMSNorm_f(x) W_head

  Attn   q = u Wq (H heads of `head_dim`), k = u Wk, v = u Wv (Hkv heads),
         q and k each under an RMSNorm over `head_dim` with a learned gain
         (assumed: below), then the rope (theta `rope_theta` over all of
         `head_dim` by absolute position, half-rotation layout);
         **position i sees j iff blk(j) <= blk(i)**: causal between
         blocks, both ways inside one.  softmax(q k^T / sqrt(head_dim) +
         mask) v, query head j reading KV head j // (H / Hkv); out Wo.
  MoE    router logits u Wr (E), float32 soft-max over all E, the
         num_experts_per_tok largest, their weights renormalised to sum 1
         (`norm_topk_prob`).  sum_e g_e W_down,e (silu(W_gate,e u) *
         W_up,e u), experts `moe_intermediate_size` wide.
  Head   **row i of the logits predicts the token at position i itself**
         (no shift: a masked position is filled from its own row).

  Generation (`generate_reference`; the family's published `generate`
  procedure at the assumed block length and schedule): the prompt's
  P // B whole blocks stand; its last P % B tokens are given rows of the
  first generated block.  A block starts with its open rows holding
  `mask_token_id`.  A denoising pass runs the sequence so far with the
  block as it stands, takes for each open row x0 = argmax of its logits and
  its confidence p(x0) under the row's soft-max, and fills the n_t open
  rows of highest confidence (`low_confidence_static`: n_t = B // T, the
  first B % T passes one more; of equal confidences the lower position
  first).  After T = `denoise_steps` passes no row is open; one commit
  pass runs the finished block (in a system with a cache, the pass whose
  K / V is kept; here, with no cache, a forward whose result the next
  block's passes recompute anyway: it is run all the same and counted, so
  that reference and program make the same T + 1 passes a block).
  Generation ends at `max_tokens` or at the block that holds the end
  token; what a block holds past either is dropped.

The reference below is those equations in plain `jax.numpy` float32,
independent of `ray_tpu/`: no kernels, no cache, no batching; attention
under a dense (n, n) block mask in blocks of queries, every expert
evaluated on every token and weighted (zero where not taken);
`generate_reference` recomputes the whole sequence every pass.  It shares
only the parameter tree's layout, which is data (families/mellum.py lists
it; `q_norm` / `k_norm` (L, head_dim)).  Callers run it under
`jax.default_matmul_precision("highest")`.

**Routing is handed over**, as in families/mellum.py and for its reason
(8 of 128 taken: the reference's own gap between the last expert taken and
the first left out is under ROUTER_MARGIN nearly everywhere).  `score`
asks the engine's scoring entry for the experts the program took, keeps
them with the open rows it drew in `_HANDED` under the lane's token ids,
and `forward`, which `deployment.logits_check` calls next in the same
process on the same tokens, looks them up there; it holds the program's
choice to ROUTER_SLACK as mellum's does.

**What `correct` compares** (`check`: 2 lanes, `prompt_len` 1025,
`decode_steps` 31: positions 1024 .. 1055, eight whole blocks at a timed
length).  `score` prefills positions 0 .. 1023 by the engine's chunk
program, then for each block runs one pass of the engine's own burst body
(`paged_block_pass`) over the block with a seeded set of open rows masked
(every count from 1 to 4 among the eight, `_open_rows`) and keeps the
logits of all four rows, then the commit pass with the true tokens.
`forward` gives for each of those rows the reference's logits with that
block's open rows masked and every earlier block clean: one whole forward
a compared block (later blocks are invisible to the block's rows, so every
forward has one shape).  A wrong mask, a K / V kept from a pass that saw
mask tokens, or a commit left out moves every later block's logits.

Departures from the published procedure, each where it is made: which rows
of a block are open is carried as booleans, not found by comparing tokens
with the mask id (`generate_reference`: the two agree unless the model
emits the mask id or a prompt holds it, and a request must still stream
exactly `max_tokens`); the schedule is the static one
(`low_confidence_static`), not the confidence threshold
(`low_confidence_dynamic`).  Assumed, because `config.json` leaves them to
the family's convention (the configuration file lists them under
`assumed`): the block length 4 and the passes a block, the mask token's
id, QK-norm (unconditional and keyless in the config family whose keys
these are), no shift of the logits (the masked-diffusion objective over
blocks that the family publishes), the half-rotation rope layout.
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
# (the docstring above), so LOGITS_REL_EXPERTS holds all 64 of a run
# (`check`: 2 lanes x 8 blocks x 4 rows at positions 1024 .. 1055, the
# blocks' open rows drawn 1 to 4 of 4).  Measured on the chip at published
# widths, depth 6: the readings beside each number.
#
# ROUTER_SLACK, in the units of ROUTER_MARGIN (a share of the rms of the
# token's router logits).  LOGITS_REL_EXPERTS: rms error of a position's
# logits as a share of the reference's own.
#   PR 52 (my chip runs, calls 1 to 3: 12 seeds x 64 positions x 6 layers,
#   two of them in a probe, ten in the cell's own runs): the program as it
#   is strays from the reference's set by at most 0.023-0.027 a seed (read
#   on two seeds; mellum's, the same stack, 0.017-0.038 on 26) and a
#   position's error has medians 0.0069-0.0076 and a largest a seed of
#   0.0075-0.0089 (largest 0.00886).
#   With the pool rounded to 8-bit floats (float8_e4m3fn, the nearest
#   precision below the cache dtype the configuration states) after every
#   prefill chunk and after every pass, by eager ops, two seeds (call 3):
#   medians 0.0186 / 0.0220, largest 0.0258 / 0.0291, strays up to 0.121 /
#   0.117: not correct by both limits (a stray over the slack makes its
#   position's logits NaN; the median alone is over the error's limit).
#   0.016 lies between 0.0089 and 0.0258 with a factor of 1.8 and 1.6 of
#   room; 0.08 between 0.027 (0.038 on mellum's seeds) and 0.117.  What
#   the check fails at the tiny size (tests/test_block_diffusion_serving.py:
#   `test_the_comparison_has_teeth`): a causal mask inside a block, a
#   block's K / V kept from a pass that saw a mask token, a commit left
#   out.
TOLERANCES = {"LOGITS_REL_EXPERTS": 0.016, "ROUTER_SLACK": 0.08}

# What `score` handed over: {a lane's token ids (int32 bytes): {"start",
# "open" (blocks, B) bool, "clean" (T, L, k), "noised" (T - start, L, k)}}.
_HANDED: dict = {}


# ---------------------------------------------------------------------------
# configuration file -> the program
# ---------------------------------------------------------------------------
def _assumed(config: dict) -> dict:
    a = config.get("assumed", {})
    try:
        return {"block": int(a["diffusion_block"]),
                "steps": int(a["denoise_steps"]),
                "mask": int(a["mask_token_id"]),
                "qk_norm": bool(a.get("qk_norm", True))}
    except KeyError as e:
        raise SpecError(f"configuration {config.get('name')!r}: `assumed` "
                        f"lacks {e.args[0]!r} (block length, passes a block "
                        f"and the mask token are not keys of the source's "
                        f"config)") from None


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

    needs = {"d_head", "d_expert", "qk_norm", "diffusion_block",
             "denoise_steps", "mask_token_id"}
    lacks = needs - {f.name for f in dataclasses.fields(TransformerConfig)}
    if lacks:
        _withdraw_app()
        raise SpecError(
            f"this program's TransformerConfig has no {sorted(lacks)}: it "
            f"cannot run a configuration of the sdar family")
    for key, want in (("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("use_sliding_window", False),
                      ("norm_topk_prob", True), ("attention_bias", False),
                      ("rope_scaling", None), ("hidden_act", "silu")):
        if config[key] != want:
            raise SpecError(f"{key} = {config[key]!r}: the program's layers "
                            f"are {key} = {want!r} throughout")
    a = _assumed(config)
    if config["assumed"].get("remasking", "low_confidence_static") \
            != "low_confidence_static":
        raise SpecError("the program fills a block by the static schedule "
                        "(low_confidence_static)")
    return TransformerConfig(
        name=config["name"],
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_head=config["head_dim"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_experts=config["num_experts"],
        expert_top_k=config["num_experts_per_tok"],
        qk_norm=a["qk_norm"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        param_dtype=jnp.dtype(config["param_dtype"]),
        compute_dtype=jnp.dtype(config["compute_dtype"]),
        remat=False,
        diffusion_block=a["block"], denoise_steps=a["steps"],
        mask_token_id=a["mask"])


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
    return x * jax.lax.rsqrt(var + eps) * gain


def _rope(x, c):
    """x (T, heads, hd) at positions 0 .. T - 1: rotate pairs
    (i, i + hd/2)."""
    t, _, hd = x.shape
    inv = float(c["rope_theta"]) ** (-jnp.arange(hd // 2, dtype=F32)
                                     * 2.0 / hd)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


_QUERY_BLOCK = 512


def attention(u, bp, c):
    """Grouped-query attention of the normed input u (T, d) under the
    block mask, queries _QUERY_BLOCK at a time against the whole
    sequence."""
    t = u.shape[0]
    h, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    eps, b = c["rms_norm_eps"], _assumed(c)["block"]
    q = (u @ bp["wq"]).reshape(t, h, hd)
    k = (u @ bp["wk"]).reshape(t, hkv, hd)
    v = (u @ bp["wv"]).reshape(t, hkv, hd)
    if _assumed(c)["qk_norm"]:
        q = _rms_norm(q, bp["q_norm"], eps)
        k = _rms_norm(k, bp["k_norm"], eps)
    q, k = _rope(q, c), _rope(k, c)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    out = []
    for lo in range(0, t, _QUERY_BLOCK):
        hi = min(lo + _QUERY_BLOCK, t)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k) / jnp.sqrt(F32(hd))
        qp, kp = jnp.arange(lo, hi)[:, None], jnp.arange(t)[None, :]
        seen = kp // b <= qp // b
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v))
    return jnp.concatenate(out, 0).reshape(t, h * hd) @ bp["wo"]


def moe(u, bp, taken, c):
    """The sparse block over u (T, d).  `taken` (T, k) int32: the experts
    the program took (None: the reference's own top-k).  Returns (out,
    margin (T,), bad (T,) bool), as families/mellum.py's."""
    k, e = c["num_experts_per_tok"], c["num_experts"]
    logits = u @ bp["router"].astype(F32)                      # (T, E)
    top, idx = jax.lax.top_k(logits, k + 1)
    rms = jnp.sqrt(jnp.mean(jnp.square(logits), axis=-1))
    if taken is None:
        taken = idx[:, :k]
        margin = (top[:, k - 1] - top[:, k]) / rms
        bad = jnp.zeros(margin.shape, bool)
    else:
        mine = jnp.sum(jax.nn.one_hot(taken, e, dtype=F32), axis=1) > 0
        kth = top[:, k - 1]
        lowest_in = jnp.min(jnp.where(mine, logits, jnp.inf), axis=-1)
        highest_out = jnp.max(jnp.where(mine, -jnp.inf, logits), axis=-1)
        stray = jnp.maximum(jnp.maximum(kth - lowest_in, highest_out - kth),
                            0.0) / rms
        margin = 1.0 - stray
        bad = (stray > TOLERANCES["ROUTER_SLACK"]) \
            | (jnp.sum(mine, axis=-1) != k)
    gates = jax.nn.softmax(jnp.take_along_axis(logits, taken, axis=-1),
                           axis=-1)                            # (T, k)
    weight = jnp.sum(jax.nn.one_hot(taken, e, dtype=F32) * gates[..., None],
                     axis=1)                                   # (T, E)

    def one(acc, ex):
        gate, up, down, w = ex
        hidden = jax.nn.silu(u @ gate.astype(F32)) * (u @ up.astype(F32))
        return acc + w[:, None] * (hidden @ down.astype(F32)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        bp["w_gate"], bp["w_up"], bp["w_down"], weight.T))
    return out, margin, bad


_EXPERTS = ("w_gate", "w_up", "w_down", "router")


def block(x, bp, taken, c):
    """One decoder layer on one sequence x (T, d)."""
    a = {n: w.astype(F32) for n, w in bp.items() if n not in _EXPERTS}
    eps = c["rms_norm_eps"]
    x = x + attention(_rms_norm(x, a["attn_norm"], eps), a, c)
    out, margin, bad = moe(_rms_norm(x, a["mlp_norm"], eps), bp, taken, c)
    return x + out, margin, bad


def _final_norm(x, gain, eps):
    return _rms_norm(x, gain.astype(F32), eps)


def _head_block(x, columns):
    return x @ columns.astype(F32)


def _key(tokens) -> bytes:
    return np.asarray(tokens).astype(np.int32).tobytes()


_USED = ("num_attention_heads", "num_key_value_heads", "head_dim",
         "rms_norm_eps", "rope_theta", "num_experts", "num_experts_per_tok")
_PARTS: dict = {}


def _parts(c, jit):
    """The three functions a forward is made of, under `jit`: made once a
    process for the numbers `block` reads of `c`, so that `jax.jit`
    compiles each shape once however many forwards a check or a
    generation makes."""
    key = (jit, tuple(c[k] for k in _USED), tuple(_assumed(c).items()))
    if key not in _PARTS:
        _PARTS[key] = (
            jit(functools.partial(block, c=c)),
            jit(functools.partial(_final_norm, eps=c["rms_norm_eps"])),
            jit(_head_block))
    return _PARTS[key]


def _forward(params, tokens, c, parts, routing, rows=None):
    """One whole forward of the tokens as they stand (T,): (logits (T, V),
    margin (T,)), NaN where a handed routing is refused; with `rows` (lo,
    hi), the head over those positions alone ((hi - lo, V): the layers
    still run the whole sequence).  `parts`: `_parts`.  Parameters are
    cast to float32 a layer at a time, at their use, and the output head
    an eighth of the vocabulary at a time."""
    n_layers = c["num_hidden_layers"]
    layer, final_norm, head = parts
    x = params["embed"][tokens].astype(F32)
    margin = jnp.full(x.shape[:1], jnp.inf, F32)
    bad = jnp.zeros(x.shape[:1], bool)
    if routing is not None and routing.shape != (
            x.shape[0], n_layers, c["num_experts_per_tok"]):
        routing, bad = None, ~bad         # not a routing of this model
    for i in range(n_layers):
        x, m, b = layer(
            x, {n: a[i] for n, a in params["blocks"].items()},
            None if routing is None else jnp.asarray(routing[:, i]))
        margin, bad = jnp.minimum(margin, m), bad | b
        # The host waits for the layer: it runs far ahead of the device
        # otherwise, and every layer it has sliced out of the stacks ahead
        # of its turn (1.25 GB each at the published widths) lies beside
        # the resident model meanwhile (14.6 GB at its peak against 9.1
        # resident: my chip run, PR 52, call 2).
        x.block_until_ready()
    if rows is not None:
        x, margin, bad = (a[rows[0]:rows[1]] for a in (x, margin, bad))
    x = final_norm(x, params["final_norm"])
    out = params["embed"].T if c.get("tie_word_embeddings") \
        else params["lm_head"]
    cols = -(-out.shape[1] // 8)
    logits = jnp.concatenate([head(x, out[:, i:i + cols])
                              for i in range(0, out.shape[1], cols)], axis=1)
    return jnp.where(bad[:, None], jnp.nan, logits), margin


def forward(params, tokens, c, jit=lambda f: f, routing="handed"):
    """tokens (T,) int32 -> (logits (T, V) float32, margin (T,)), one
    sequence; `margin` is each position's smallest over the layers.

    With nothing handed over for these tokens (`routing` None, or no
    `score` before it): one forward of the tokens as they are, every block
    clean, under the reference's own top-k.  `routing` an array (T, L, k):
    that forward with those experts.  With what `score` left for them
    ("handed"): for every compared block (from position `start`, B rows
    each) one whole forward of the tokens with that block's open rows
    holding the mask token, every earlier block clean (later blocks are
    invisible to the block's rows and stay as they are: one shape a
    forward), under the experts the program took (the clean passes' for
    earlier rows, the denoising pass's for the block's own); the block's
    B rows of it are the compared logits.  Rows before `start` are zeros:
    nothing is compared there."""
    handed = _HANDED.get(_key(tokens)) if isinstance(routing, str) else None
    parts = _parts(c, jit)
    if handed is None:
        return _forward(params, tokens, c, parts,
                        None if isinstance(routing, str) else routing)
    b, mask = _assumed(c)["block"], _assumed(c)["mask"]
    start, t = handed["start"], len(tokens)
    tokens = np.asarray(tokens)
    logits = [jnp.zeros((start, c["vocab_size"]), F32)]
    margin = [jnp.full((start,), jnp.inf, F32)]
    for i, open_rows in enumerate(handed["open"]):
        at = start + i * b
        toks, route = tokens.copy(), handed["clean"].copy()
        toks[at:at + b] = np.where(open_rows, mask, toks[at:at + b])
        route[at:at + b] = handed["noised"][at - start:at - start + b]
        lg, m = _forward(params, jnp.asarray(toks, jnp.int32), c, parts,
                         route, rows=(at, at + b))
        logits.append(lg)
        margin.append(m)
    assert start + len(handed["open"]) * b == t, (start, t)
    return jnp.concatenate(logits), jnp.concatenate(margin)


def fills(c: dict) -> list:
    """Open rows a block's denoising passes fill, pass by pass
    (`low_confidence_static`): B // T each, the first B % T one more."""
    a = _assumed(c)
    return [a["block"] // a["steps"] + (i < a["block"] % a["steps"])
            for i in range(a["steps"])]


def passes_per_block(c: dict) -> int:
    """Forward passes a block costs: its denoising passes and the commit."""
    return _assumed(c)["steps"] + 1


def generate_reference(params, prompt, max_tokens: int, c, eos_id=None,
                       jit=lambda f: f, pad_to: int = 0):
    """The block loop of the docstring at temperature 0, recomputing the
    whole sequence every pass.  Returns (the tokens generated, at most
    `max_tokens`, ending with `eos_id` where a block held it; passes run).
    `pad_to`: run every forward at that many positions (zeros behind the
    block: later blocks are invisible to it), so that `jit=jax.jit`
    compiles one shape.

    Departure from the published procedure: which rows are open is carried
    as booleans (`still`), not found by comparing the block's tokens with
    the mask id, so a prompt that holds the mask id keeps it and a block
    that is filled with it is finished."""
    a = _assumed(c)
    b, mask = a["block"], a["mask"]
    seq = [int(t) for t in prompt]
    given = seq[len(seq) // b * b:]
    seq = seq[:len(seq) // b * b]
    out, passes, parts = [], 0, _parts(c, jit)

    def run(toks):
        row = seq + toks
        row = row + [0] * max(pad_to - len(row), 0)
        logits, _ = _forward(params, jnp.asarray(row, jnp.int32), c, parts,
                             None, rows=(len(seq), len(seq) + b))
        return np.asarray(logits)

    while True:
        toks = given + [mask] * (b - len(given))
        still = [False] * len(given) + [True] * (b - len(given))
        for n_fill in fills(c):
            logits = run(toks)
            passes += 1
            x0 = logits.argmax(axis=-1)
            z = logits - logits.max(axis=-1, keepdims=True)
            conf = np.exp(z[np.arange(b), x0]) / np.exp(z).sum(axis=-1)
            # the most confident open rows, of equal ones the lower first
            order = sorted((i for i in range(b) if still[i]),
                           key=lambda i: (-conf[i], i))
            for i in order[:n_fill]:
                toks[i], still[i] = int(x0[i]), False
        assert not any(still)
        run(toks)                  # the commit pass: see the docstring
        passes += 1
        seq += toks
        for tok in toks[len(given):]:
            out.append(tok)
            if len(out) >= max_tokens or tok == eos_id:
                return out, passes
        given = []


# ---------------------------------------------------------------------------
# the engine's own logits, its routing, and the open rows
# ---------------------------------------------------------------------------
def _open_rows(seed_tokens, n_blocks: int, b: int) -> np.ndarray:
    """(n_blocks, b) bool: which rows of each compared block stand open,
    seeded by the lane's tokens; every count from 1 to b appears among
    n_blocks >= b blocks."""
    rng = np.random.default_rng(int(np.asarray(seed_tokens[:8]).sum()))
    counts = [1 + (i % b) for i in range(n_blocks)]
    rng.shuffle(counts)
    out = np.zeros((n_blocks, b), bool)
    for i, n in enumerate(counts):
        out[i, rng.permutation(b)[:n]] = True
    return out


def score(e, config: dict, seqs, n_prompt: int):
    """The engine's scoring entry for this kind of model
    (`PagedLLMEngine.score` with `open_rows`): positions 0 .. n_prompt - 2
    prefilled through its own chunk program, then every block of the rest
    through the pass its burst scans, once with the drawn open rows
    holding the mask token (its logits are what is returned, per lane the
    rows of positions n_prompt - 1 ..) and once committed with the true
    tokens; both compiled to hand out the experts they took, which are
    kept for `forward` with the open rows under each lane's token ids."""
    b = _assumed(config)["block"]
    seqs = np.asarray(seqs)
    start = n_prompt - 1
    if start % b or (seqs.shape[1] - start) % b:
        raise SpecError(f"check: prompt_len - 1 = {start} and the "
                        f"{seqs.shape[1] - start} compared positions must "
                        f"be whole blocks of {b}")
    n_blocks = (seqs.shape[1] - start) // b
    open_rows = np.stack([_open_rows(s, n_blocks, b) for s in seqs])
    got, taken = e.score(seqs, start, routing=True, open_rows=open_rows)
    _HANDED.clear()
    for lane, route in enumerate(taken):
        _HANDED[_key(seqs[lane])] = {
            "start": start, "open": open_rows[lane],
            "clean": np.asarray(route["clean"]),
            "noised": np.asarray(route["noised"])}
    return got


# ---------------------------------------------------------------------------
# Operations and bytes a pass needs, from shapes alone: what the
# algorithm requires, not what the program happens to execute.
# ---------------------------------------------------------------------------
def _dims(c: dict) -> dict:
    hd = c["head_dim"]
    return {"d": c["hidden_size"], "v": c["vocab_size"],
            "q": c["num_attention_heads"] * hd,
            "kv": c["num_key_value_heads"] * hd,
            "f": c["moe_intermediate_size"], "e": c["num_experts"],
            "k": c["num_experts_per_tok"], "n": c["num_hidden_layers"],
            "b": _assumed(c)["block"], "t": _assumed(c)["steps"]}


def _itemsize(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]


def layer_params(c: dict, active_only: bool = False) -> int:
    """Matrix parameters of one layer; `active_only`: the experts one
    token takes, not all of them."""
    s = _dims(c)
    experts = (s["k"] if active_only else s["e"]) * 3 * s["d"] * s["f"]
    return 2 * s["d"] * s["q"] + 2 * s["d"] * s["kv"] + s["d"] * s["e"] \
        + experts


def total_params(c: dict, active_only: bool = False) -> int:
    s = _dims(c)
    emb = s["v"] * s["d"] * (1 if c.get("tie_word_embeddings") else 2)
    return s["n"] * layer_params(c, active_only) + emb


def expected_routed_experts(c: dict, rows: float) -> float:
    """Distinct experts that `rows` rows take in one layer under uniform
    routing: E (1 - (1 - k/E)^rows).  (8 for one row, 29.1 for four, 111.8
    for thirty-two of 128.)"""
    e, k = c["num_experts"], c["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def expert_bytes_per_step(c: dict, lanes: int, experts_read=None) -> float:
    """Bytes of expert weights one pass of `lanes` lanes needs: the
    distinct experts its B rows a lane take in every layer, each once.
    `experts_read`: that count a layer and pass as the program counted it
    (the tick log's `experts_read`), which the metrics hand over; None:
    the expectation under uniform, independent routing, an upper estimate
    here: the open rows of a block hold one token, the mask's, and differ
    by their position alone, so they route much alike (65 of 128 counted
    at 7-8 lanes where independence expects 110-112: my chip run, PR 52,
    call 2)."""
    s = _dims(c)
    if experts_read is None:
        experts_read = expected_routed_experts(c, lanes * s["b"])
    return s["n"] * experts_read * 3 * s["d"] * s["f"] \
        * _itemsize(c["param_dtype"])


def expert_operand(c: dict):
    """What an op that reads a layer's expert weights shows in its HLO
    text: an operand shaped [E,d,f] or [E,f,d] (after the layers' axis,
    where the stacks are whole), as a compiled pattern."""
    s = _dims(c)
    return re.compile(rf"\[(?:\d+,)?{s['e']},(?:{s['d']},{s['f']}|"
                      rf"{s['f']},{s['d']})\]")


def select_operand(c: dict):
    """What the ops of a denoising pass's selection show in their HLO
    text: an array whose last dimension is the vocabulary (the head's
    product and its weight, the soft-max over a row's logits, the argmax
    and the confidence read from them), as a compiled pattern.  The
    embedding's gather reads [V, d], which is not one."""
    return re.compile(rf"\[(?:\d+,)*{c['vocab_size']}\]")


def _kv_row_bytes(c: dict) -> int:
    return 2 * _dims(c)["kv"] * _itemsize(
        c.get("cache_dtype", c["compute_dtype"]))


def denoise_pass_bytes(c: dict, live_kv_tokens: float, lanes: int,
                       experts_read=None) -> float:
    """Bytes one pass of `lanes` lanes must move, the mean over a block's
    T + 1: every weight outside the experts once (attention, router; the
    embedding is a gather), of the experts those the lanes' B rows each
    take (`expert_bytes_per_step`: the program's count where it is handed
    over), the K / V of the live positions in every layer, and the head
    in T of the T + 1 passes (the commit takes no logits)."""
    s = _dims(c)
    dense = s["n"] * (layer_params(c) - s["e"] * 3 * s["d"] * s["f"])
    head = s["d"] * s["v"] * s["t"] / (s["t"] + 1)
    return (dense + head) * _itemsize(c["param_dtype"]) \
        + expert_bytes_per_step(c, lanes, experts_read) \
        + s["n"] * _kv_row_bytes(c) * live_kv_tokens


def prefill_flops(c: dict, tokens: float, context: float) -> float:
    """FLOPs that `tokens` prompt tokens need which, as a causal model's
    would, attend over `context` positions (a token at position p: p + 1).
    Under the block mask a row sees to the end of its block, on average
    (B - 1) / 2 positions more: the layers' matrices with the 8 experts a
    token takes, attention scores and values over that context.  The
    output head, which a prompt of this model never takes, is left out."""
    s = _dims(c)
    seen = context + tokens * (s["b"] - 1) / 2.0
    return 2.0 * s["n"] * layer_params(c, active_only=True) * tokens \
        + 4.0 * s["q"] * s["n"] * seen


# ---------------------------------------------------------------------------
# for bench/tools/memory_fit.py
# ---------------------------------------------------------------------------
def serve_programs(config: dict, place):
    """What a replica of `config` keeps resident, as shapes, and its
    largest programs lowered at the engine's sizes: the widest denoise
    burst and the widest prefill chunk."""
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

    w, b = eng["num_slots"], cfg.diffusion_block
    ch = min(512, eng["max_len"])       # the engine's widest chunk tier
    return {"params": params, "sequence_state": state}, [
        (f"paged_denoise_burst w={w}", burst_fn.lower(
            params, state, arr((w, b), jnp.int32), arr((w, b), jnp.bool_),
            arr((w, b_max), jnp.int32), arr((w,), jnp.int32),
            arr((w,), jnp.bool_), arr((w,), jnp.float32), rng,
            n_blocks=eng["max_burst"] // b)),
        (f"paged_prefill_chunk c={ch}", chunk_fn.lower(
            params, state, arr((ch,), jnp.int32), arr((b_max,), jnp.int32),
            arr((), jnp.int32), arr((), jnp.int32)))]
