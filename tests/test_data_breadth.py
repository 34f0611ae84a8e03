"""Data breadth: tfrecords/webdataset/sql readers, write_tfrecords,
ds.stats(), backpressure window (ref: python/ray/data/tests/
test_tfrecords.py, test_webdataset.py, test_sql.py, test_stats.py)."""
import io
import json
import os
import sqlite3
import tarfile

import numpy as np
import pytest


@pytest.fixture(scope="module")
def data_cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# tfrecord codec (pure)
# ---------------------------------------------------------------------------

def test_tfrecord_example_roundtrip(tmp_path):
    from ray_tpu.data import tfrecord

    rows = [
        {"name": b"alpha", "score": 1.5, "count": 7},
        {"name": b"beta", "score": -2.25, "count": -3,
         "vec": [1.0, 2.0, 3.0], "ids": [1, 2, 3]},
    ]
    path = str(tmp_path / "t.tfrecords")
    tfrecord.write_records(
        path, (tfrecord.encode_example(r) for r in rows))
    out = [tfrecord.decode_example(p)
           for p in tfrecord.read_records(path)]
    assert out[0]["name"] == b"alpha"
    assert out[0]["score"] == pytest.approx(1.5)
    assert out[0]["count"] == 7
    assert out[1]["count"] == -3
    assert out[1]["vec"] == pytest.approx([1.0, 2.0, 3.0])
    assert out[1]["ids"] == [1, 2, 3]


def test_tfrecord_crc_detects_corruption(tmp_path):
    from ray_tpu.data import tfrecord

    path = str(tmp_path / "c.tfrecords")
    tfrecord.write_records(
        path, iter([tfrecord.encode_example({"a": 1})]))
    raw = bytearray(open(path, "rb").read())
    raw[-5] ^= 0xFF  # flip a payload byte
    open(path, "wb").write(bytes(raw))
    with pytest.raises(ValueError, match="crc"):
        list(tfrecord.read_records(path))


# ---------------------------------------------------------------------------
# readers on a live cluster
# ---------------------------------------------------------------------------

def test_read_write_tfrecords(data_cluster, tmp_path):
    from ray_tpu import data

    ds = data.from_items([{"x": i, "y": float(i) * 0.5}
                          for i in range(20)], parallelism=3)
    out_dir = str(tmp_path / "tfr")
    ds.write_tfrecords(out_dir)
    back = data.read_tfrecords(out_dir)
    rows = sorted(back.take_all(), key=lambda r: r["x"])
    assert [r["x"] for r in rows] == list(range(20))
    assert rows[4]["y"] == pytest.approx(2.0)


def test_read_webdataset(data_cluster, tmp_path):
    from ray_tpu import data

    shard = str(tmp_path / "shard-000.tar")
    with tarfile.open(shard, "w") as tar:
        for i in range(5):
            for ext, payload in (
                ("json", json.dumps({"i": i}).encode()),
                ("txt", f"caption {i}".encode()),
                ("cls", str(i % 2).encode()),
            ):
                data_bytes = payload
                info = tarfile.TarInfo(name=f"sample{i:04d}.{ext}")
                info.size = len(data_bytes)
                tar.addfile(info, io.BytesIO(data_bytes))
    ds = data.read_webdataset(shard)
    rows = sorted(ds.take_all(), key=lambda r: r["__key__"])
    assert len(rows) == 5
    assert rows[2]["json"] == {"i": 2}
    assert rows[2]["txt"] == "caption 2"
    assert rows[3]["cls"] == 1


def test_read_sql(data_cluster, tmp_path):
    from ray_tpu import data

    db = str(tmp_path / "t.db")
    conn = sqlite3.connect(db)
    conn.execute("CREATE TABLE pts (x INTEGER, label TEXT)")
    conn.executemany("INSERT INTO pts VALUES (?, ?)",
                     [(i, f"l{i}") for i in range(10)])
    conn.commit()
    conn.close()
    ds = data.read_sql("SELECT x, label FROM pts WHERE x < 5",
                       lambda: sqlite3.connect(db))
    rows = sorted(ds.take_all(), key=lambda r: r["x"])
    assert [r["x"] for r in rows] == [0, 1, 2, 3, 4]
    assert rows[1]["label"] == "l1"


def test_gated_sources_raise_helpfully(data_cluster):
    """Without an injected client and without the optional driver
    package, the failure names the missing dependency (and the
    client_factory escape hatch) at read-task execution time."""
    from ray_tpu import data

    with pytest.raises(Exception, match="pymongo"):
        data.read_mongo("mongodb://x", database="db",
                        collection="coll").take_all()
    with pytest.raises(Exception, match="bigquery"):
        data.read_bigquery(dataset="project.table").take_all()


# ---------------------------------------------------------------------------
# stats + backpressure
# ---------------------------------------------------------------------------

def test_dataset_stats(data_cluster):
    from ray_tpu import data

    ds = data.range(1000, parallelism=4).map_batches(
        lambda b: {"id": b["id"] * 2}, batch_format="numpy")
    assert "not been executed" in ds.stats()
    total = ds.count()
    assert total == 1000
    s = ds.stats()
    assert "tasks" in s and "consumed: 1000 rows" in s
    # The fused read+map stage ran one task per read block.
    assert "4 tasks" in s


def test_backpressure_window_shrinks_under_store_pressure(monkeypatch):
    from ray_tpu.data.streaming.executor import _effective_window

    class FakeStore:
        capacity = 100
        used = 90

    class FakeWorker:
        store = FakeStore()

    import ray_tpu.api as api

    monkeypatch.setattr(api, "_worker", FakeWorker())
    assert _effective_window(32) == 8
    FakeStore.used = 10
    assert _effective_window(32) == 32


def test_aggregate_depth_std_quantile_unique():
    """Streaming std (Chan merge), exact quantile, distinct values
    (ref: python/ray/data/aggregate.py Std/AbsMax et al.)."""
    import numpy as np

    from ray_tpu import data as rd

    vals = np.arange(100, dtype=np.float64)
    ds = rd.from_items([{"v": float(v), "g": int(v) % 3}
                        for v in vals], parallelism=7)
    assert abs(ds.std("v") - np.std(vals, ddof=1)) < 1e-9
    # Nulls carry no mass (an all-null block must not crash or skew).
    withnulls = rd.from_items(
        [{"v": None}] * 10 + [{"v": float(v)} for v in vals],
        parallelism=6)
    assert abs(withnulls.std("v") - np.std(vals, ddof=1)) < 1e-9
    assert ds.quantile("v", 0.5) == np.quantile(vals, 0.5)
    assert ds.unique("g") == [0, 1, 2]


def test_multi_key_groupby_and_named_aggregates():
    from ray_tpu import data as rd

    rows = [{"a": i % 2, "b": i % 3, "v": float(i)} for i in range(60)]
    ds = rd.from_items(rows, parallelism=5)
    out = ds.groupby(["a", "b"]).aggregate(
        ("v", "sum"), ("v", "mean"), ("v", "stddev")).take_all()
    assert len(out) == 6                      # 2 x 3 key combos
    import numpy as np

    for r in out:
        grp = [x["v"] for x in rows
               if x["a"] == r["a"] and x["b"] == r["b"]]
        assert abs(r["v_sum"] - sum(grp)) < 1e-9
        assert abs(r["v_mean"] - np.mean(grp)) < 1e-9

    # grouped std matches numpy's sample std per group (ddof=1)
    s = ds.groupby("a").std("v").take_all()
    assert len(s) == 2
    for r in s:
        grp = [x["v"] for x in rows if x["a"] == r["a"]]
        assert abs(r["v_stddev"] - np.std(grp, ddof=1)) < 1e-9

    # multi-key map_groups applies per key-combo
    out = ds.groupby(["a", "b"]).map_groups(
        lambda batch: {"a": batch["a"][:1], "b": batch["b"][:1],
                       "n": np.array([len(batch["v"])])},
        batch_format="numpy").take_all()
    assert sorted(r["n"] for r in out) == [10] * 6


def test_read_write_mongo_with_injected_client(data_cluster):
    from ray_tpu import data as rdata

    # Defined in-function: cloudpickle ships nested classes by VALUE,
    # so worker processes don't need to import this test module.
    class _FakeMongoCollection:
        def __init__(self, docs):
            self.docs = docs
            self.inserted = []

        def find(self):
            return iter(self.docs)

        def aggregate(self, pipeline):
            out = self.docs
            for stage in pipeline:
                if "$match" in stage:
                    out = [d for d in out
                           if all(d.get(k) == v
                                  for k, v in stage["$match"].items())]
                if "$limit" in stage:
                    out = out[: stage["$limit"]]
            return iter(out)

        def insert_many(self, rows):
            self.inserted.extend(rows)


    class _FakeMongoClient:
        def __init__(self, docs):
            self.coll = _FakeMongoCollection(docs)

        def __getitem__(self, _db):
            return {"c": self.coll}

        def close(self):
            pass

    docs = [{"_id": i, "x": i, "tag": "a" if i % 2 == 0 else "b"}
            for i in range(10)]
    client = _FakeMongoClient(docs)
    ds = rdata.read_mongo(database="db", collection="c",
                          client_factory=lambda: client)
    rows = ds.take_all()
    assert len(rows) == 10 and "_id" not in rows[0]

    # sharded read: one task per aggregation pipeline
    ds2 = rdata.read_mongo(
        database="db", collection="c",
        pipelines=[[{"$match": {"tag": "a"}}],
                   [{"$match": {"tag": "b"}}]],
        client_factory=lambda: client)
    assert len(ds2.take_all()) == 10

    # write path round-trips through the same seam
    out_client = _FakeMongoClient([])
    rdata.from_items([{"y": i} for i in range(5)]).write_mongo(
        database="db", collection="c",
        client_factory=lambda: out_client)
    assert len(out_client.coll.inserted) == 5


def test_read_write_bigquery_with_injected_client(data_cluster):
    from ray_tpu import data as rdata

    class _FakeBQResult:
        def __init__(self, rows):
            self._rows = rows

        def __iter__(self):
            return iter(self._rows)


    class _FakeBQJob:
        def __init__(self, rows):
            self.rows = rows

        def result(self):
            return _FakeBQResult(self.rows)


    class _FakeBQClient:
        def __init__(self, rows):
            self.rows = rows
            self.queries = []
            self.loaded = []

        def query(self, q):
            self.queries.append(q)
            return _FakeBQJob(self.rows)

        def load_table_from_dataframe(self, df, dataset):
            self.loaded.append((dataset, len(df)))
            return _FakeBQJob([])

    rows = [{"a": i, "b": f"s{i}"} for i in range(7)]
    client = _FakeBQClient(rows)
    ds = rdata.read_bigquery(dataset="d.t",
                             client_factory=lambda: client)
    got = ds.take_all()
    # (the client is pickled into the read task, so the local object's
    # call log stays empty — assert on the data instead)
    assert sorted(r["a"] for r in got) == list(range(7))

    rdata.from_items([{"z": 1}, {"z": 2}]).write_bigquery(
        dataset="d.out", client_factory=lambda: client)
    assert client.loaded and client.loaded[0][0] == "d.out"
