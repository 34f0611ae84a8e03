"""All-to-all shuffle over the zero-copy transfer plane.

A shuffle that moves every mapper→reducer partition as its own pickled
object through point-to-point gets makes N² small transfers per round,
each paying the pickle codec and its own RPC slow-start. Here every
mapper of ``Dataset.random_shuffle`` emits ONE sealed *bundle* — all of
its reducer partitions packed back-to-back behind a fixed-size offset
header — and bundles move over the transfer plane:

- **relay-tree pre-staging** (multi-node): each bundle is broadcast to
  every node over the daemon relay tree (`plan_broadcast_tree` /
  `broadcast_object` — raw frames, pipelined chunks, log-N depth), so
  reducer tasks find their input node-local no matter where they
  schedule;
- **range serve**: because the bundle layout is offset-addressed, a
  reducer can also pull JUST its partition's byte range of a remote
  bundle (`transfer.fetch_object_range` → daemon `get_object_chunk`,
  which serves sealed and still-arriving objects alike) — same total
  bytes as point-to-point, but raw-framed and windowed.

Partitions are Arrow IPC streams, so a reducer deserializes its slice
without touching the rest of the bundle.
"""
from __future__ import annotations

import logging
import struct
import time
from typing import Any, List, Optional, Tuple

import numpy as np
import pyarrow as pa

import ray_tpu
from ray_tpu.data.block import concat

logger = logging.getLogger(__name__)

_MAGIC = b"RTSB"
_HEAD = struct.Struct("<4sI")      # magic, n_parts
_SLOT = struct.Struct("<QQ")       # offset, length


def header_size(n_parts: int) -> int:
    return _HEAD.size + n_parts * _SLOT.size


def table_to_ipc(table: pa.Table) -> bytes:
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, table.schema) as w:
        w.write_table(table)
    return sink.getvalue().to_pybytes()


def ipc_to_table(buf) -> pa.Table:
    return pa.ipc.open_stream(pa.BufferReader(pa.py_buffer(buf))).read_all()


def pack_bundle(parts: List[bytes]) -> bytes:
    """Offset-addressed bundle: header with (offset, length) per part,
    payloads concatenated — the layout range readers slice into."""
    n = len(parts)
    off = header_size(n)
    slots = []
    for p in parts:
        slots.append((off, len(p)))
        off += len(p)
    out = bytearray(off)
    _HEAD.pack_into(out, 0, _MAGIC, n)
    pos = _HEAD.size
    for s in slots:
        _SLOT.pack_into(out, pos, *s)
        pos += _SLOT.size
    w = header_size(n)
    for p in parts:
        out[w:w + len(p)] = p
        w += len(p)
    return bytes(out)


def parse_header(buf) -> List[Tuple[int, int]]:
    magic, n = _HEAD.unpack_from(buf, 0)
    if magic != _MAGIC:
        raise ValueError("not a shuffle bundle (bad magic)")
    return [_SLOT.unpack_from(buf, _HEAD.size + i * _SLOT.size)
            for i in range(n)]


def unpack_part(buf, j: int) -> memoryview:
    off, ln = parse_header(buf)[j]
    return memoryview(buf)[off:off + ln]


def part_table(bundle, j: int) -> pa.Table:
    return ipc_to_table(unpack_part(bundle, j))


# -- remote shuffle stages ------------------------------------------------

def _scatter_bundle(block, n: int, seed: int):
    """Mapper: permute rows, split into n partitions, pack ONE bundle.
    Second return is the bundle size — a tiny inline object, so the
    driver can account shuffle bytes without fetching a bundle."""
    rng = np.random.default_rng(seed)
    idx = rng.permutation(block.num_rows)
    parts = np.array_split(idx, n)
    bundle = pack_bundle([
        table_to_ipc(block.take(pa.array(p))) for p in parts])
    return bundle, len(bundle)


def _combine_part(seed: int, j: int, *bundles) -> pa.Table:
    """Reducer: partition j of every bundle, concatenated + permuted."""
    t = concat([part_table(b, j) for b in bundles])
    rng = np.random.default_rng(seed)
    return t.take(pa.array(rng.permutation(t.num_rows)))


def _prestage(bundle_refs: List[Any], fanout: int) -> int:
    """Broadcast each sealed bundle to every live node over the relay
    tree so reducers read node-locally. Best-effort: a failed prestage
    only costs the reducer a remote pull. Returns nodes staged."""
    try:
        from ray_tpu.api import _global_worker

        worker = _global_worker()
        node_ids = [n["node_id"] for n in worker.nodes()
                    if n.get("alive", True)]
        if len(node_ids) <= 1:
            return 0
        staged = 0
        for ref in bundle_refs:
            res = worker.broadcast_object(ref, node_ids)
            staged += int(res.get("nodes", 0)) if res.get("ok") else 0
        return staged
    except Exception:  # noqa: BLE001 — prestage is an optimization
        logger.debug("shuffle prestage skipped", exc_info=True)
        return 0


def streaming_shuffle_refs(refs: List[Any],
                           seed: Optional[int] = None,
                           dataset: str = "ds") -> List[Any]:
    """ref_fn body for the streaming RandomShuffle barrier: bundles out
    of mappers, relay-tree prestage, per-partition reducers."""
    from ray_tpu.core.config import get_config

    refs = list(refs)
    if not refs:
        return refs
    n_out = len(refs)
    cfg = get_config()
    fanout = (cfg.data_stream_shuffle_fanout
              or cfg.transfer_broadcast_fanout)

    scatter = ray_tpu.remote(_scatter_bundle).options(num_returns=2)
    combine = ray_tpu.remote(_combine_part)

    ss = np.random.SeedSequence(seed)
    seeds = ss.generate_state(len(refs) + n_out)
    t0 = time.monotonic()
    bundles, sizes = [], []
    for i, r in enumerate(refs):
        b, s = scatter.remote(r, n_out, int(seeds[i]))
        bundles.append(b)
        sizes.append(s)
    # Bundles must be sealed before they can relay; the wait doubles as
    # the mapper barrier every all-to-all has anyway.
    ray_tpu.wait(bundles, num_returns=len(bundles))
    _prestage(bundles, fanout)
    out = [combine.remote(int(seeds[len(refs) + j]), j, *bundles)
           for j in range(n_out)]
    ray_tpu.wait(out, num_returns=len(out))
    elapsed = time.monotonic() - t0
    try:
        from ray_tpu.data.streaming import metrics as dm

        dm.on_shuffle(dataset, sum(ray_tpu.get(sizes)), elapsed)
    except Exception:  # noqa: BLE001
        pass
    return out
