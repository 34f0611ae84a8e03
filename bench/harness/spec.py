"""Finds a cell's files by the names in BENCHMARK.json and reads them.

A cell (an entry of `workloads`) names a configuration and a traffic mix;
`configs/<config>.json`, `traffic/<mix>.json`, `cells/<cell>.json` (the
offered rate of an open loop) and `metrics/<metric>.json` are found by
those names.  A metric that BENCHMARK.json splits by the end-to-end metric
it moves (`x.serve`, `x.train`) and that has no file of its own takes
`x`'s.  A configuration file names its family (`"family"`), and
`families/<family>.py` holds everything the harness knows of that model
(`family` below).  Nothing here, or anywhere in the harness, branches on
a name or reads a key of a model's `config.json` but `vocab_size`:
adding a cell, a configuration, a family, a mix or a per-layer metric is
adding files and entries.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
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
    config["family_file"] = family_file(config, cfg_entry["file"], bdir)
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
# configuration file -> its family
# ---------------------------------------------------------------------------
def family_file(config: Dict[str, Any], where: str,
                bench_dir: str = BENCH_DIR) -> str:
    """The path of `families/<config["family"]>.py`: in the tree the
    configuration came from, else in the harness's own (a tree of data
    files alone, such as the tests', brings no family).  `where` names
    the configuration file in the fault."""
    name = config.get("family")
    if not isinstance(name, str) or not name:
        raise SpecError(f'{where} names no "family": the harness knows a '
                        f"model only by bench/families/<family>.py")
    for d in (bench_dir, BENCH_DIR):
        path = os.path.join(d, "families", name + ".py")
        if os.path.exists(path):
            return path
    raise SpecError(f"{where} names the family {name!r}, and there is no "
                    f"{os.path.join('families', name + '.py')} under "
                    f"{os.path.relpath(bench_dir, ROOT)}")


@functools.lru_cache(maxsize=None)
def load_file(path: str, prefix: str):
    """The module of a `.py` that a data file names (a family, a
    metric's reader), loaded by file: one module a file a process, as
    `sys.modules` keeps one a name."""
    stem = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        prefix + stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(config: Dict[str, Any]):
    """The module of the configuration's family: in the replica's and
    the train worker's process too, where `config` arrives with the `family_file`
    that `load_cell` found.  What a family file gives is listed at the
    top of families/mistral.py."""
    return load_file(config.get("family_file") or family_file(
        config, f"configuration {config.get('name')!r}"), "bench_family_")


def transformer_config(config: Dict[str, Any]):
    """The program's configuration object: the family's
    `program_config`.  Kept under this name for
    tests/test_tpu_compile.py, which a benchmark PR may not edit."""
    return family(config).program_config(config)


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
