"""Dataset: lazy, immutable, distributed collection of Arrow blocks.

Reference surface being reproduced (ref: python/ray/data/dataset.py:137 —
map_batches :371, iter_batches :3640, materialize :4520; grouped_data.py;
_internal/split.py).  Execution is deferred: transforms append stages to a
logical plan; consumption streams block refs through the executor.
"""
from __future__ import annotations

import functools
import itertools
from typing import (Any, Callable, Dict, Iterable, Iterator, List, Optional,
                    Union)

import numpy as np
import pyarrow as pa

import ray_tpu
from ray_tpu.data import block as B
from ray_tpu.data.plan import AllToAllStage, MapStage, ReadTask

BatchUDF = Callable[..., Any]


def _batch_map_blockfn(fn, batch_size, batch_format, fn_kwargs):
    def block_fn(block: B.Block) -> Iterable[B.Block]:
        for piece in B.batches(block, batch_size):
            out = fn(B.to_batch(piece, batch_format), **fn_kwargs)
            yield B.from_batch(out)

    return block_fn


def _row_map_blockfn(kind: str, fn):
    def block_fn(block: B.Block) -> Iterable[B.Block]:
        rows = list(B.iter_rows(block))
        if kind == "map":
            out = [fn(r) for r in rows]
        elif kind == "filter":
            out = [r for r in rows if fn(r)]
        else:  # flat_map
            out = list(itertools.chain.from_iterable(fn(r) for r in rows))
        if not out:
            yield block.slice(0, 0)
            return
        yield B.from_rows(out)

    return block_fn


def _rebatch(block_iter: Iterable[B.Block], batch_size: int,
             batch_format: Optional[str], drop_last: bool) -> Iterator[Any]:
    """Re-slice a block stream into fixed-size batches."""
    carry: Optional[B.Block] = None
    for blk in block_iter:
        if carry is not None and carry.num_rows:
            blk = B.concat([carry, blk])
            carry = None
        start = 0
        while blk.num_rows - start >= batch_size:
            yield B.to_batch(blk.slice(start, batch_size), batch_format)
            start += batch_size
        carry = blk.slice(start)
    if carry is not None and carry.num_rows and not drop_last:
        yield B.to_batch(carry, batch_format)


def _jax_feed(batch_iter: Iterator[dict], sharding, dtypes,
              prefetch: Optional[int], name: str) -> Iterator[Any]:
    """Shared device feed for Dataset / streaming-split iterators:
    dtype cast + device_put behind a DevicePrefetcher of the configured
    depth (RAY_TPU_DATA_STREAM_PREFETCH_DEPTH when `prefetch` is None)."""
    import jax

    from ray_tpu.core.config import get_config
    from ray_tpu.data.streaming.prefetch import device_prefetching

    def to_device(np_batch):
        if dtypes:
            np_batch = {k: v.astype(dtypes[k]) if k in dtypes else v
                        for k, v in np_batch.items()}
        if sharding is not None:
            return {k: jax.device_put(v, sharding)
                    for k, v in np_batch.items()}
        return {k: jax.device_put(v) for k, v in np_batch.items()}

    depth = (get_config().data_stream_prefetch_depth
             if prefetch is None else prefetch)
    yield from device_prefetching(batch_iter, to_device, depth=depth,
                                  name=name)


def _torch_batches(batch_iter: Iterator[dict]) -> Iterator[dict]:
    """numpy batches → torch tensors (copying read-only shm views;
    torch needs writable memory for in-place training ops)."""
    import torch

    for batch in batch_iter:
        yield {k: torch.as_tensor(
                   v if getattr(v, "flags", None) is None
                   or v.flags.writeable else np.array(v))
               for k, v in batch.items()}


class Dataset:
    def __init__(self, read_tasks: List[ReadTask], stages: List[Any] = None):
        self._read_tasks = read_tasks
        self._stages = stages or []

    # ---------------- transforms (lazy) ----------------
    def _with(self, stage) -> "Dataset":
        return Dataset(self._read_tasks, self._stages + [stage])

    def map_batches(self, fn: BatchUDF, *, batch_size: Optional[int] = None,
                    batch_format: Optional[str] = None,
                    compute: Optional[Any] = None, concurrency: int = 0,
                    fn_constructor_args: tuple = (),
                    fn_kwargs: Optional[dict] = None, **_ignored) -> "Dataset":
        """Apply a UDF per batch.  Class UDFs run on an actor pool
        (`concurrency` actors); function UDFs fuse into producer tasks."""
        fn_kwargs = fn_kwargs or {}
        if isinstance(fn, type):
            n = concurrency or (compute if isinstance(compute, int) else 2)

            def maker(cls=fn, args=fn_constructor_args, kw=dict(fn_kwargs),
                      bs=batch_size, bf=batch_format):
                inst = cls(*args)
                return _batch_map_blockfn(inst, bs, bf, kw)

            return self._with(MapStage(
                name=f"MapBatches({fn.__name__})",
                block_fn=None, actor_fn_maker=maker, num_actors=n))
        return self._with(MapStage(
            name=f"MapBatches({getattr(fn, '__name__', 'fn')})",
            block_fn=_batch_map_blockfn(fn, batch_size, batch_format,
                                        fn_kwargs)))

    def map(self, fn) -> "Dataset":
        return self._with(MapStage("Map", _row_map_blockfn("map", fn)))

    def filter(self, fn) -> "Dataset":
        return self._with(MapStage("Filter", _row_map_blockfn("filter", fn)))

    def flat_map(self, fn) -> "Dataset":
        return self._with(MapStage("FlatMap",
                                   _row_map_blockfn("flat_map", fn)))

    def add_column(self, name: str, fn) -> "Dataset":
        def add(batch):
            batch[name] = fn(batch)
            return batch

        return self.map_batches(add, batch_format="numpy")

    def drop_columns(self, cols: List[str]) -> "Dataset":
        return self.map_batches(
            lambda t: t.drop_columns(cols), batch_format="pyarrow")

    def select_columns(self, cols: List[str]) -> "Dataset":
        return self.map_batches(
            lambda t: t.select(cols), batch_format="pyarrow")

    def rename_columns(self, mapping: Dict[str, str]) -> "Dataset":
        return self.map_batches(
            lambda t: t.rename_columns(
                [mapping.get(c, c) for c in t.column_names]),
            batch_format="pyarrow")

    def limit(self, n: int) -> "Dataset":
        # Streaming cutoff: pulls upstream refs only until n rows are seen,
        # so execution of the tail never happens.
        def ref_fn(ref_iter):
            def gen():
                left = n
                for ref in ref_iter:
                    if left <= 0:
                        break
                    blk = ray_tpu.get(ref)
                    take = min(left, blk.num_rows)
                    left -= take
                    yield (ref if take == blk.num_rows
                           else ray_tpu.put(blk.slice(0, take)))

            return gen()

        return self._with(AllToAllStage("Limit", ref_fn))

    # ---------------- all-to-all ----------------
    def repartition(self, num_blocks: int) -> "Dataset":
        def ref_fn(refs):
            refs = list(refs)
            if not refs:
                return refs
            blocks = ray_tpu.get(refs)
            whole = B.concat(blocks)
            n = whole.num_rows
            per = max(1, -(-n // num_blocks))
            return [ray_tpu.put(whole.slice(i * per, per))
                    for i in range(num_blocks) if i * per < n or n == 0]

        return self._with(AllToAllStage("Repartition", ref_fn))

    def random_shuffle(self, *, seed: Optional[int] = None) -> "Dataset":
        """Distributed map-reduce shuffle: each block scatters rows into
        num_blocks partitions; reducers concat+permute
        (ref: data/_internal shuffle — push-based variant not needed yet).

        Every mapper packs its partitions into ONE offset-addressed
        bundle that rides the broadcast/relay trees (prestaged
        node-local on multi-node clusters) instead of N² point-to-point
        pickled gets — see data/streaming/shuffle.py."""
        def ref_fn(refs):
            from ray_tpu.data.streaming.shuffle import streaming_shuffle_refs

            return streaming_shuffle_refs(refs, seed, self._name())

        return self._with(AllToAllStage("RandomShuffle", ref_fn))

    def sort(self, key: str, descending: bool = False) -> "Dataset":
        """Distributed range-partition sort (ref: _internal/planner/
        exchange/sort_task_spec.py:1 SortTaskSpec — sample → boundaries →
        per-block partition map → per-range merge). No task ever holds
        more than ~1/num_blocks of the data, so datasets larger than any
        single worker's memory sort fine (the previous one-task
        `sort_all` funneled everything through one worker)."""
        order = "descending" if descending else "ascending"

        def ref_fn(refs):
            refs = list(refs)
            if not refs:
                return refs
            n_out = len(refs)

            @ray_tpu.remote
            def sort_block(b):
                return b.sort_by([(key, order)])

            if n_out == 1:
                return [sort_block.remote(refs[0])]

            # 1) Sample boundary candidates from every block.
            @ray_tpu.remote
            def sample_keys(block, k=64):
                if block.num_rows == 0:
                    return None
                idx = np.linspace(0, block.num_rows - 1,
                                  min(k, block.num_rows)).astype(np.int64)
                return (block.column(key).take(pa.array(idx))
                        .to_numpy(zero_copy_only=False))

            samples = [s for s in ray_tpu.get(
                [sample_keys.remote(r) for r in refs]) if s is not None]
            if not samples:
                return refs
            allsamp = np.sort(np.concatenate(samples))
            cut_idx = np.linspace(0, allsamp.size - 1,
                                  n_out + 1).astype(np.int64)[1:-1]
            bounds = allsamp[cut_idx]

            # 2) Partition map: each block splits into n_out key ranges
            # (always ascending; descending flips the range order below).
            @ray_tpu.remote
            def partition(block, bnds, n):
                sb = block.sort_by([(key, "ascending")])
                keys = sb.column(key).to_numpy(zero_copy_only=False)
                cuts = np.searchsorted(keys, bnds, side="left")
                edges = [0, *cuts.tolist(), sb.num_rows]
                parts = tuple(sb.slice(edges[i], edges[i + 1] - edges[i])
                              for i in range(n))
                return parts[0] if n == 1 else parts

            # 3) Per-range merge: concat this range's shards + local sort.
            @ray_tpu.remote
            def merge(*parts):
                return B.concat(list(parts)).sort_by([(key, order)])

            parted = [partition.options(num_returns=n_out)
                      .remote(r, bounds, n_out) for r in refs]
            out = [merge.remote(*[parted[i][j] for i in range(len(refs))])
                   for j in range(n_out)]
            return out[::-1] if descending else out

        return self._with(AllToAllStage("Sort", ref_fn))

    def groupby(self, key) -> "GroupedData":
        """Group by one column or a LIST of columns (ref:
        python/ray/data/grouped_data.py multi-key groupby)."""
        return GroupedData(self, key)

    def union(self, other: "Dataset") -> "Dataset":
        if self._stages or other._stages:
            left = self.materialize()
            right = other.materialize()
            return Dataset(left._read_tasks + right._read_tasks)
        return Dataset(self._read_tasks + other._read_tasks)

    def zip(self, other: "Dataset") -> "Dataset":
        def ref_fn(refs):
            mine = B.concat(ray_tpu.get(list(refs)))
            theirs = B.concat(ray_tpu.get(list(other.to_block_refs())))
            n = min(mine.num_rows, theirs.num_rows)
            mine, theirs = mine.slice(0, n), theirs.slice(0, n)
            cols = {c: mine.column(c) for c in mine.column_names}
            for c in theirs.column_names:
                cols[c if c not in cols else f"{c}_1"] = theirs.column(c)
            return [ray_tpu.put(pa.table(cols))]

        return self._with(AllToAllStage("Zip", ref_fn))

    def random_sample(self, fraction: float,
                      seed: Optional[int] = None) -> "Dataset":
        # Per-call entropy when unseeded; per-block entropy from a content
        # digest so equal-sized blocks don't draw identical masks.
        import secrets
        import zlib

        call_entropy = seed if seed is not None else secrets.randbits(63)

        def block_fn(block):
            digest = 0
            if block.num_columns and block.num_rows:
                for buf in block.column(0).combine_chunks().chunk(0).buffers():
                    if buf is not None:
                        digest = zlib.crc32(bytes(buf)[:4096], digest)
            rng = np.random.default_rng((call_entropy, digest,
                                         block.num_rows))
            mask = rng.random(block.num_rows) < fraction
            yield block.filter(pa.array(mask))

        return self._with(MapStage("RandomSample", block_fn))

    # ---------------- split ----------------
    def split(self, n: int, *, equal: bool = False) -> List["Dataset"]:
        """Materialize and split into n datasets (ref: dataset.py split;
        used for per-host train shards)."""
        refs = list(self.to_block_refs())
        blocks = ray_tpu.get(refs)
        whole = B.concat(blocks)
        total = whole.num_rows
        per = total // n if equal else -(-total // n)
        out = []
        for i in range(n):
            start = min(i * per, total)
            end = min((i + 1) * per, total) if i < n - 1 or equal else total
            t = whole.slice(start, max(0, end - start))
            out.append(from_block_list([t]))
        return out

    def streaming_split(self, n: int, *, equal: bool = False
                        ) -> List["StreamingSplitIterator"]:
        """N per-consumer iterators over ONE streaming execution of this
        dataset (ref: _internal/execution/operators/output_splitter.py:1
        OutputSplitter + Dataset.streaming_split — the multi-worker Train
        ingest path). Blocks are handed out first-come-first-served by a
        coordinator actor, so fast consumers take more and slow ones
        never stall the pipeline; `equal=True` instead enforces
        round-robin handout (consumers advance in lockstep).

        The coordinator is the ack-based StreamSplitCoordinator
        (data/streaming/split.py): it tracks one outstanding block per
        consumer and supports live resplit() on elastic world-size
        change — no epoch restart, no lost or duplicated samples."""
        from ray_tpu.data.streaming.split import StreamSplitCoordinator

        coord = StreamSplitCoordinator.remote(self, n, equal)
        return [StreamingSplitIterator(coord, i) for i in range(n)]

    def split_at_indices(self, indices: List[int]) -> List["Dataset"]:
        whole = B.concat(ray_tpu.get(list(self.to_block_refs())))
        bounds = [0] + list(indices) + [whole.num_rows]
        return [from_block_list([whole.slice(a, b - a)])
                for a, b in zip(bounds[:-1], bounds[1:])]

    def train_test_split(self, test_size: float, *, shuffle: bool = False,
                         seed: Optional[int] = None):
        ds = self.random_shuffle(seed=seed) if shuffle else self
        whole = B.concat(ray_tpu.get(list(ds.to_block_refs())))
        cut = int(whole.num_rows * (1 - test_size))
        return (from_block_list([whole.slice(0, cut)]),
                from_block_list([whole.slice(cut)]))

    # ---------------- execution / consumption ----------------
    def to_block_refs(self) -> Iterator[Any]:
        from ray_tpu.data.stats import DatasetStats
        from ray_tpu.data.streaming import streaming_execute

        self._last_stats = DatasetStats()
        try:
            yield from streaming_execute(self._read_tasks, self._stages,
                                         stats=self._last_stats)
        finally:
            from ray_tpu.data.streaming import metrics as _dm

            _dm.on_execution(self._name(), self._last_stats)

    def _name(self) -> str:
        return getattr(self, "_label", "ds")

    def iter_blocks(self) -> Iterator[B.Block]:
        for ref in self.to_block_refs():
            blk = ray_tpu.get(ref)
            stats = getattr(self, "_last_stats", None)
            if stats is not None:
                stats.consumed_rows += blk.num_rows
                stats.consumed_bytes += blk.nbytes
            yield blk

    def stats(self) -> str:
        """Execution stats of the most recent consumption (ref:
        Dataset.stats(), data/_internal/stats.py)."""
        stats = getattr(self, "_last_stats", None)
        if stats is None:
            return "Dataset has not been executed yet."
        return stats.summary()

    def materialize(self) -> "Dataset":
        refs = list(self.to_block_refs())
        return _materialized(refs)

    def iter_batches(self, *, batch_size: int = 256,
                     batch_format: Optional[str] = None,
                     prefetch_batches: int = 1,
                     drop_last: bool = False) -> Iterator[Any]:
        yield from _rebatch(self.iter_blocks(), batch_size, batch_format,
                            drop_last)

    def iter_torch_batches(self, *, batch_size: int = 256,
                           drop_last: bool = False) -> Iterator[dict]:
        """Batches as torch tensors (ref: Dataset.iter_torch_batches)."""
        yield from _torch_batches(self.iter_batches(
            batch_size=batch_size, batch_format="numpy",
            drop_last=drop_last))

    def iter_rows(self) -> Iterator[Any]:
        for blk in self.iter_blocks():
            yield from B.iter_rows(blk)

    def take(self, n: int = 20) -> List[Any]:
        out = []
        for row in self.iter_rows():
            out.append(row)
            if len(out) >= n:
                break
        return out

    def take_all(self) -> List[Any]:
        return list(self.iter_rows())

    def count(self) -> int:
        return sum(b.num_rows for b in self.iter_blocks())

    def sum(self, col: str):
        import pyarrow.compute as pc

        return sum(pc.sum(b.column(col)).as_py() or 0
                   for b in self.iter_blocks())

    def min(self, col: str):
        import pyarrow.compute as pc

        return min(pc.min(b.column(col)).as_py() for b in self.iter_blocks())

    def max(self, col: str):
        import pyarrow.compute as pc

        return max(pc.max(b.column(col)).as_py() for b in self.iter_blocks())

    def mean(self, col: str):
        total, cnt = 0.0, 0
        for b in self.iter_blocks():
            import pyarrow.compute as pc

            s = pc.sum(b.column(col)).as_py()
            total += s or 0
            cnt += b.num_rows
        return total / cnt if cnt else float("nan")

    def std(self, col: str, ddof: int = 1):
        """Streaming standard deviation (Chan parallel-variance merge
        across blocks — no global materialization)."""
        import pyarrow.compute as pc

        count, mean, m2 = 0, 0.0, 0.0
        for b in self.iter_blocks():
            # Weight by VALID values — nulls carry no mass (an all-null
            # block contributes nothing; pc.mean would return None).
            n = pc.count(b.column(col), mode="only_valid").as_py()
            if not n:
                continue
            bm = pc.mean(b.column(col)).as_py()
            bv = pc.variance(b.column(col), ddof=0).as_py() or 0.0
            delta = bm - mean
            total = count + n
            m2 += bv * n + delta * delta * count * n / total
            mean += delta * n / total
            count = total
        if count <= ddof:
            return float("nan")
        return float(np.sqrt(m2 / (count - ddof)))

    def quantile(self, col: str, q: float = 0.5):
        """Exact quantile; pulls only the ONE column to the driver."""
        import pyarrow.compute as pc

        chunks = [b.column(col) for b in self.iter_blocks()
                  if b.num_rows]
        if not chunks:
            return float("nan")
        combined = pa.chunked_array(chunks)
        return pc.quantile(combined, q=q).to_pylist()[0]

    def unique(self, col: str) -> List[Any]:
        """Distinct values of a column, streamed block by block."""
        import pyarrow.compute as pc

        seen: set = set()
        for b in self.iter_blocks():
            seen.update(pc.unique(b.column(col)).to_pylist())
        return sorted(seen, key=lambda v: (v is None, v))

    def schema(self) -> Optional[pa.Schema]:
        for b in self.iter_blocks():
            return b.schema
        return None

    def columns(self) -> List[str]:
        s = self.schema()
        return list(s.names) if s else []

    def num_blocks(self) -> int:
        return len(list(self.to_block_refs()))

    def size_bytes(self) -> int:
        return sum(b.nbytes for b in self.iter_blocks())

    def to_pandas(self):
        return B.concat(list(self.iter_blocks())).to_pandas()

    def to_arrow(self) -> pa.Table:
        return B.concat(list(self.iter_blocks()))

    def to_numpy(self) -> Dict[str, np.ndarray]:
        return B.to_numpy(self.to_arrow())

    # ---------------- writes ----------------
    def write_parquet(self, path: str) -> None:
        self._write(path, "parquet")

    def write_csv(self, path: str) -> None:
        self._write(path, "csv")

    def write_json(self, path: str) -> None:
        self._write(path, "json")

    def write_tfrecords(self, path: str) -> None:
        """One .tfrecords file per block, rows encoded as
        tf.train.Example via the built-in codec (ref: Dataset.
        write_tfrecords)."""
        import os

        from ray_tpu.data import tfrecord

        os.makedirs(path, exist_ok=True)
        for i, blk in enumerate(self.iter_blocks()):
            f = os.path.join(path, f"part-{i:05d}.tfrecords")
            tfrecord.write_records(
                f, (tfrecord.encode_example(row)
                    for row in B.iter_rows(blk)))

    def write_mongo(self, *, database: str, collection: str,
                    uri: Optional[str] = None,
                    client_factory=None) -> None:
        """Insert every row into a MongoDB collection (ref: datasource/
        mongo_datasource.py write path). `client_factory` is the same
        injectable seam as `read_mongo`. Blocks stream through the
        DRIVER sequentially — sink writes are correctness-first here;
        distribute by mapping a write over shards yourself if the sink
        is the bottleneck."""
        if client_factory is None:
            def client_factory():  # pragma: no cover - needs a mongod
                import pymongo

                return pymongo.MongoClient(uri)

        client = client_factory()
        try:
            coll = client[database][collection]
            for blk in self.iter_blocks():
                rows = [dict(r) for r in B.iter_rows(blk)]
                if rows:
                    coll.insert_many(rows)
        finally:
            try:
                client.close()
            except Exception:  # noqa: BLE001
                pass

    def write_bigquery(self, *, dataset: str,
                       project_id: Optional[str] = None,
                       client_factory=None) -> None:
        """Load every block into a BigQuery table (ref: datasource/
        bigquery_datasource.py write path); `dataset` is
        "dataset.table"."""
        if client_factory is None:
            def client_factory():  # pragma: no cover - needs GCP creds
                from google.cloud import bigquery

                return bigquery.Client(project=project_id)

        client = client_factory()
        try:
            for blk in self.iter_blocks():
                job = client.load_table_from_dataframe(blk.to_pandas(),
                                                       dataset)
                job.result()
        finally:
            try:
                client.close()
            except Exception:  # noqa: BLE001 fakes without close()
                pass

    def _write(self, path: str, fmt: str) -> None:
        import os

        os.makedirs(path, exist_ok=True)
        for i, blk in enumerate(self.iter_blocks()):
            f = os.path.join(path, f"part-{i:05d}.{fmt}")
            if fmt == "parquet":
                import pyarrow.parquet as pq

                pq.write_table(blk, f)
            elif fmt == "csv":
                import pyarrow.csv as pcsv

                pcsv.write_csv(blk, f)
            else:
                blk.to_pandas().to_json(f, orient="records", lines=True)

    # ---------------- device feeding (TPU-specific) ----------------
    def iter_jax_batches(self, *, batch_size: int, sharding=None,
                         dtypes: Optional[dict] = None, drop_last: bool = True,
                         prefetch: Optional[int] = None) -> Iterator[Any]:
        """Pipeline-resident host→HBM feed: a background thread owns
        batch formation + `jax.device_put` and keeps up to `prefetch`
        device-resident batches parked, so the transfer of batch k+1
        overlaps compute on batch k (double buffering at the default
        depth; see data/streaming/prefetch.py)."""
        yield from _jax_feed(
            self.iter_batches(batch_size=batch_size, batch_format="numpy",
                              drop_last=drop_last),
            sharding, dtypes, prefetch, self._name())

    def __repr__(self):
        names = [getattr(s, "name", "?") for s in self._stages]
        return (f"Dataset(blocks~{len(self._read_tasks)}, "
                f"stages={names})")


class StreamingSplitIterator:
    """One consumer's shard of a streaming_split (ref: DataIterator,
    python/ray/data/iterator.py — the object handed to each Train
    worker). Pickles cleanly (actor handle + index), single pass.

    `block_timeout_s` bounds each next_block wait (None = wait forever,
    the default: the FIRST block legitimately waits on the whole
    upstream pipeline — an AllToAll barrier, autoscaler provisioning)."""

    def __init__(self, coord, idx: int,
                 block_timeout_s: Optional[float] = None):
        self._coord = coord
        self._idx = idx
        self._block_timeout_s = block_timeout_s

    def iter_blocks(self) -> Iterator[B.Block]:
        while True:
            ref = ray_tpu.get(self._coord.next_block.remote(self._idx),
                              timeout=self._block_timeout_s)
            if ref is None:
                return
            yield ray_tpu.get(ref)

    def iter_batches(self, *, batch_size: int = 256,
                     batch_format: Optional[str] = None,
                     drop_last: bool = False) -> Iterator[Any]:
        yield from _rebatch(self.iter_blocks(), batch_size, batch_format,
                            drop_last)

    def iter_torch_batches(self, *, batch_size: int = 256,
                           drop_last: bool = False) -> Iterator[dict]:
        yield from _torch_batches(self.iter_batches(
            batch_size=batch_size, batch_format="numpy",
            drop_last=drop_last))

    def iter_jax_batches(self, *, batch_size: int, sharding=None,
                         dtypes: Optional[dict] = None,
                         drop_last: bool = True,
                         prefetch: Optional[int] = None) -> Iterator[Any]:
        """Device-prefetched shard feed: the train-worker counterpart of
        Dataset.iter_jax_batches, so each elastic shard keeps device_put
        of batch k+1 overlapping compute on batch k."""
        yield from _jax_feed(
            self.iter_batches(batch_size=batch_size, batch_format="numpy",
                              drop_last=drop_last),
            sharding, dtypes, prefetch, f"split-{self._idx}")

    def iter_rows(self) -> Iterator[Any]:
        for blk in self.iter_blocks():
            yield from B.iter_rows(blk)


class GroupedData:
    """Groupby-aggregate over one or many key columns (ref:
    python/ray/data/grouped_data.py — multi-key groupby, named
    aggregations, map_groups)."""

    def __init__(self, ds: Dataset, key):
        self._ds = ds
        self._keys: List[str] = [key] if isinstance(key, str) else \
            list(key)
        if not self._keys:
            raise ValueError("groupby needs at least one key column")

    def _agg(self, aggs: List[tuple]) -> Dataset:
        keys = self._keys

        def ref_fn(refs):
            refs = list(refs)

            @ray_tpu.remote
            def agg_all(*blocks):
                import pyarrow.compute as pc

                # Options ride as ("OptionsClassName", kwargs) specs —
                # pyarrow FunctionOptions instances don't pickle.
                real = [
                    (a[0], a[1], getattr(pc, a[2][0])(**a[2][1]))
                    if len(a) == 3 and isinstance(a[2], tuple) else a
                    for a in aggs
                ]
                t = B.concat(list(blocks))
                tbl = t.group_by(keys).aggregate(real)
                # pyarrow names output "<col>_<fn>"; keep as-is
                return tbl.sort_by([(k, "ascending") for k in keys])

            return [agg_all.remote(*refs)]

        return self._ds._with(AllToAllStage("GroupByAgg", ref_fn))

    def aggregate(self, *aggs: tuple) -> Dataset:
        """Named aggregations: (col, fn) pairs with any pyarrow
        group-by function — 'sum', 'mean', 'min', 'max', 'count',
        'stddev', 'variance', 'count_distinct', ... — or
        (col, fn, ("OptionsClassName", kwargs)) triples for pyarrow
        FunctionOptions, e.g. ("v", "stddev", ("VarianceOptions",
        {"ddof": 1})) — specs, because FunctionOptions instances don't
        pickle across workers. Multiple at once produce one row per
        group with a column per aggregate."""
        if not aggs:
            raise ValueError("aggregate() needs (col, fn) pairs")
        return self._agg(list(aggs))

    def count(self) -> Dataset:
        return self._agg([(self._keys[0], "count")])

    def sum(self, col: str) -> Dataset:
        return self._agg([(col, "sum")])

    def mean(self, col: str) -> Dataset:
        return self._agg([(col, "mean")])

    def min(self, col: str) -> Dataset:
        return self._agg([(col, "min")])

    def max(self, col: str) -> Dataset:
        return self._agg([(col, "max")])

    def std(self, col: str, ddof: int = 1) -> Dataset:
        # pyarrow's grouped stddev defaults to ddof=0; match
        # Dataset.std's sample-std default explicitly.
        return self._agg([(col, "stddev",
                           ("VarianceOptions", {"ddof": ddof}))])

    def map_groups(self, fn, *, batch_format: Optional[str] = None) -> Dataset:
        keys = self._keys

        def ref_fn(refs):
            refs = list(refs)

            @ray_tpu.remote
            def apply(*blocks):
                import pyarrow.compute as pc

                t = B.concat(list(blocks))
                # Distinct key combos via an empty aggregation, then
                # one conjunctive filter per group.
                combos = t.group_by(keys).aggregate([])
                outs = []
                for i in range(combos.num_rows):
                    mask = None
                    for k in keys:
                        m = pc.equal(t.column(k), combos.column(k)[i])
                        mask = m if mask is None else pc.and_(mask, m)
                    grp = t.filter(mask)
                    res = fn(B.to_batch(grp, batch_format))
                    outs.append(B.from_batch(res))
                return B.concat(outs)

            return [apply.remote(*refs)]

        return self._ds._with(AllToAllStage("MapGroups", ref_fn))


def _materialized(refs: List[Any]) -> Dataset:
    tasks = [ReadTask(fn=functools.partial(ray_tpu.get, r), name="cached")
             for r in refs]
    return Dataset(tasks)


def from_block_list(blocks: List[B.Block]) -> Dataset:
    refs = [ray_tpu.put(b) for b in blocks]
    return _materialized(refs)
