"""Finds a cell's files by the names in BENCHMARK.json and reads them.

A cell (an entry of `workloads`) names a configuration and a traffic mix;
`configs/<config>.json`, `traffic/<mix>.json`, `cells/<cell>.json` (the
offered rate of an open loop) and `metrics/<metric>.json` are found by
those names.  A metric that BENCHMARK.json splits by the end-to-end metric
it moves (`x.serve`, `x.train`) and that has no file of its own takes
`x`'s.  Nothing here, or anywhere in the harness, branches on a
name: adding a cell, a configuration, a mix or a per-layer metric is
adding files and entries.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """A fault in a data file: the run stops before anything loads."""


def _read(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}") from None
    except ValueError as e:
        raise SpecError(f"{os.path.relpath(path, ROOT)}: {e}") from None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]       # configs/<config>.json
    traffic: Dict[str, Any]      # traffic/<mix>.json
    load: Dict[str, Any]         # cells/<cell>.json ({} where none)
    end_to_end: List[dict]       # this cell's entries of BENCHMARK.json
    per_layer: List[dict]        # each merged with metrics/<name>.json

    def programs(self) -> List[str]:
        """The jitted programs this cell's per-layer metrics read by name;
        a traced run that finds no execution of one fails and says which."""
        return sorted({m["args"]["program"] for m in self.per_layer
                       if "program" in m.get("args", {})})


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def metric_file(bench_dir: str, name: str, ext: str):
    """`metrics/<name><ext>`, else that of the name up to its last `.`;
    None where neither is there."""
    for stem in (name, name.rpartition(".")[0]):
        path = os.path.join(bench_dir, "metrics", stem + ext)
        if stem and os.path.exists(path):
            return path
    return None


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _read(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; it has "
                        f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise SpecError(f"workload {name!r} names configuration "
                        f"{entry['config']!r}, which BENCHMARK.json lacks")
    config = _read(os.path.join(root, cfg_entry["file"]))
    bdir = os.path.join(root, bench["paths"][0])
    traffic = _read(os.path.join(bdir, "traffic", entry["traffic"] + ".json"))
    load_path = os.path.join(bdir, "cells", name + ".json")
    load = _read(load_path) if os.path.exists(load_path) else {}
    per_layer = []
    for m in bench["per_layer"]:
        if _in_cell(m, name):
            how = _read(metric_file(bdir, m["name"], ".json") or
                        os.path.join(bdir, "metrics", m["name"] + ".json"))
            per_layer.append({**how, **m})
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic, load=load,
                end_to_end=[m for m in bench["end_to_end"]
                            if _in_cell(m, name)],
                per_layer=per_layer)


# ---------------------------------------------------------------------------
# configuration file -> the program's TransformerConfig
# ---------------------------------------------------------------------------
def transformer_config(config: Dict[str, Any]):
    """The configuration file's keys are the source's (`config.json` of
    the model); this is the one place they meet the program's names."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    heads = config["num_attention_heads"]
    if config.get("head_dim", config["hidden_size"] // heads) * heads \
            != config["hidden_size"]:
        raise SpecError("head_dim * num_attention_heads != hidden_size: "
                        "the program derives the head size")
    if config.get("sliding_window"):
        raise SpecError("the program has no sliding-window attention")
    return TransformerConfig(
        name=config["name"],
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        param_dtype=jnp.dtype(config["param_dtype"]),
        compute_dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(config.get("remat", False)),
        remat_policy=config.get("remat_policy", "full"),
        n_experts=int(config.get("num_local_experts", 0)),
        expert_top_k=int(config.get("num_experts_per_tok", 2)))


def request_limit(engine: Dict[str, Any]) -> int:
    """Largest prompt_len + max_tokens the engine serves in full.
    `PagedLLMEngine._maybe_finish` ends a request early once prompt + out
    reaches max_len - 1 - max(max_burst, speculation_k), and `generate*`
    refuses a prompt of max_len: one below both."""
    return engine["max_len"] - 2 - max(engine.get("max_burst", 8),
                                       engine.get("speculation_k", 0))


def check_requests(requests, engine: Dict[str, Any]) -> None:
    """Every request against the engine's own limits, before anything
    loads.  A violation is a fault in a data file, not a failed
    operation."""
    limit = request_limit(engine)
    for r in requests:
        if r.prompt_len < 1 or r.max_tokens < 1 \
                or r.prompt_len + r.max_tokens > limit:
            raise SpecError(
                f"request {r.index}: prompt {r.prompt_len} + "
                f"max_tokens {r.max_tokens} does not fit the engine "
                f"(limit {limit} = max_len {engine['max_len']} - 2 - "
                f"advance margin): the engine would refuse it or cut it "
                f"short")
