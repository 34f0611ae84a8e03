"""Operations and bytes a step needs, from shapes alone.  `config` is a
configuration file's dict (the source's key names).  These count what
the algorithm requires, not what the program happens to execute: no
recomputation, no dense-over-experts waste, no f32 copies."""
from __future__ import annotations


def _dims(c: dict):
    d = c["hidden_size"]
    heads = c["num_attention_heads"]
    hd = c.get("head_dim", d // heads)
    kv = c["num_key_value_heads"] * hd
    return d, heads, hd, kv, c["intermediate_size"], c["vocab_size"]


def layer_params(c: dict, active_only: bool = False) -> int:
    """Matrix parameters of one block.  `active_only`: the experts one
    token is routed to (num_experts_per_tok), not all of them."""
    d, heads, hd, kv, f, _ = _dims(c)
    attn = 2 * d * heads * hd + 2 * d * kv
    e = c.get("num_local_experts", 0)
    if e:
        k = c["num_experts_per_tok"] if active_only else e
        return attn + k * 3 * d * f + d * e
    return attn + 3 * d * f


def expert_params_per_layer(c: dict) -> int:
    d, _, _, _, f, _ = _dims(c)
    return c.get("num_local_experts", 0) * 3 * d * f


def total_params(c: dict) -> int:
    d, *_, v = _dims(c)
    emb = v * d * (1 if c.get("tie_word_embeddings") else 2)
    return c["num_hidden_layers"] * layer_params(c) + emb


def forward_flops_per_token(c: dict, context: float) -> float:
    """Forward FLOPs for one token that attends over `context` positions
    (2 per multiply-add): matrices of the blocks (routed experts only),
    attention scores and values, and the output head.  The embedding
    lookup is a gather."""
    d, heads, hd, _, _, v = _dims(c)
    per_layer = 2 * layer_params(c, active_only=True) \
        + 4 * heads * hd * context
    return c["num_hidden_layers"] * per_layer + 2 * d * v


def prefill_flops(c: dict, prompt_len: int) -> float:
    """A causal prefill of `prompt_len` tokens: token i attends i+1
    positions; only the last token needs the output head."""
    d, heads, hd, _, _, v = _dims(c)
    n = prompt_len
    matrices = 2 * layer_params(c, active_only=True) * n
    attention = 4 * heads * hd * n * (n + 1) / 2
    return c["num_hidden_layers"] * (matrices + attention) + 2 * d * v


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward + backward = 3 x forward, causal attention at its mean
    context (seq_len + 1) / 2, head on every token.  Recomputation under
    remat does not count."""
    return 3 * forward_flops_per_token(c, (seq_len + 1) / 2)


def _itemsize(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]


def expected_routed_experts(c: dict, lanes: int) -> float:
    """Distinct experts that `lanes` tokens need in one layer, each
    routed to num_experts_per_tok of num_local_experts: the expectation
    under uniform routing, E x (1 - (1 - k/E)^lanes).  (2 for one lane,
    3.5 for two, 5.5 for four of Mixtral's 8.)  The program exposes no
    routing counts; with random routers and random inputs the routing is
    uniform but for chance."""
    e, k = c["num_local_experts"], c["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** lanes)


def expert_bytes_per_step(c: dict, lanes: int) -> float:
    """Bytes of expert weights one decode step of `lanes` tokens needs:
    the routed experts of every layer, each once."""
    d, _, _, _, f, _ = _dims(c)
    return c["num_hidden_layers"] * expected_routed_experts(c, lanes) \
        * 3 * d * f * _itemsize(c["param_dtype"])


def decode_step_bytes(c: dict, live_kv_tokens: float, lanes: int) -> float:
    """Bytes one decode step of `lanes` tokens must move: every dense
    weight once, of the experts only those the lanes are routed to, the
    output head, and the live KV of the lanes."""
    d, _, hd, kv, _, v = _dims(c)
    w = _itemsize(c["param_dtype"])
    dense = c["num_hidden_layers"] * (
        layer_params(c) - expert_params_per_layer(c)) + d * v
    experts = expert_bytes_per_step(c, lanes) \
        if c.get("num_local_experts") else 0.0
    cache = 2 * c["num_hidden_layers"] * kv * live_kv_tokens \
        * _itemsize(c.get("cache_dtype", c["compute_dtype"]))
    return dense * w + experts + cache
