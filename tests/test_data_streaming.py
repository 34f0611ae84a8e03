"""Streaming data plane (data/streaming): byte-budgeted execution,
backpressure accounting, spill fallback, bundle shuffle, device
prefetch, and the per-operator stats/metrics surface."""
import pickle

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rd
from ray_tpu.core.config import get_config
from ray_tpu.exceptions import BackpressureTimeout, DataPlaneError


@pytest.fixture(scope="module", autouse=True)
def ray_cluster():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _restore_stream_knobs():
    cfg = get_config()
    keep = {k: getattr(cfg, k) for k in (
        "data_stream_window_bytes", "data_stream_op_inflight_bytes",
        "data_stream_spill_threshold", "data_stream_stall_timeout_s",
        "data_stream_prefetch_depth")}
    yield
    for k, v in keep.items():
        setattr(cfg, k, v)


def test_streaming_is_default_and_correct():
    ds = (rd.range(64, parallelism=4)
          .map_batches(lambda b: {"x": b["id"] * 2}, batch_format="numpy"))
    out = ds.to_numpy()["x"]
    np.testing.assert_array_equal(np.sort(out), np.arange(64) * 2)


def test_per_operator_byte_stats_populated():
    ds = rd.range(200, parallelism=4).map_batches(
        lambda b: {"x": b["id"].astype(np.float64)}, batch_format="numpy")
    ds.to_numpy()
    stats = ds._last_stats
    produced = [st for st in stats.stages if st.bytes_out]
    assert produced, "streaming stages must account produced bytes"
    assert sum(st.rows_out for st in stats.stages) >= 200
    assert all(st.peak_inflight_bytes >= 0 for st in stats.stages)
    # The human summary surfaces the new breakdowns.
    s = ds.stats()
    assert "MB out" in s and "stalled" in s


def test_tiny_op_cap_backpressures_but_completes():
    cfg = get_config()
    cfg.data_stream_op_inflight_bytes = 1   # every block overruns the cap
    ds = (rd.range(128, parallelism=8)
          .map_batches(lambda b: {"x": b["id"] * 3}, batch_format="numpy"))
    out = ds.to_numpy()["x"]
    np.testing.assert_array_equal(np.sort(out), np.arange(128) * 3)
    stats = ds._last_stats
    assert max(st.peak_inflight_bytes for st in stats.stages) >= 1


def _add_seven_udf():
    """Class UDF → actor operator, so the graph has TWO operators (the
    read stage can't fuse past an actor pool) and the global byte
    window actually has an inter-operator hop to squeeze. Defined in a
    function so it pickles by value into the actor worker."""

    class AddSeven:
        def __call__(self, batch):
            return {"x": batch["id"] + 7}

    return AddSeven


def test_spill_fallback_keeps_graph_live():
    cfg = get_config()
    cfg.data_stream_window_bytes = 1        # global window always exceeded
    cfg.data_stream_spill_threshold = 1.0   # store never "too full" to spill
    ds = (rd.range(64, parallelism=4)
          .map_batches(_add_seven_udf(), batch_format="numpy",
                       concurrency=1))
    out = ds.to_numpy()["x"]
    np.testing.assert_array_equal(np.sort(out), np.arange(64) + 7)
    stats = ds._last_stats
    assert sum(st.spilled_tasks for st in stats.stages) >= 1
    assert sum(st.stall_s for st in stats.stages) >= 0.0


def test_backpressure_timeout_when_spill_disallowed():
    cfg = get_config()
    cfg.data_stream_window_bytes = 1
    cfg.data_stream_spill_threshold = 0.0   # no spill headroom, ever
    cfg.data_stream_stall_timeout_s = 0.4
    ds = (rd.range(64, parallelism=4)
          .map_batches(_add_seven_udf(), batch_format="numpy",
                       concurrency=1))
    with pytest.raises(BackpressureTimeout) as ei:
        ds.to_numpy()
    e = ei.value
    assert isinstance(e, DataPlaneError) and isinstance(e, TimeoutError)
    assert e.operator
    e2 = pickle.loads(pickle.dumps(e))
    assert e2.operator == e.operator and e2.waited_s == e.waited_s


def test_streaming_shuffle_preserves_rows():
    ds = rd.range(300, parallelism=6).random_shuffle(seed=7)
    out = sorted(r["id"] for r in ds.take_all())
    assert out == list(range(300))


def test_shuffle_bundle_roundtrip_and_range_layout():
    import pyarrow as pa

    from ray_tpu.data.streaming import shuffle as sh

    tables = [pa.table({"v": list(range(i * 10, i * 10 + 5))})
              for i in range(3)]
    bundle = sh.pack_bundle([sh.table_to_ipc(t) for t in tables])
    slots = sh.parse_header(bundle)
    assert len(slots) == 3
    assert slots[0][0] == sh.header_size(3)
    # Slots tile the payload back-to-back — the property range pulls
    # rely on to fetch exactly one partition.
    for (o1, l1), (o2, _) in zip(slots, slots[1:]):
        assert o1 + l1 == o2
    assert slots[-1][0] + slots[-1][1] == len(bundle)
    for j, t in enumerate(tables):
        assert sh.part_table(bundle, j).equals(t)


def test_streaming_split_ack_requeues_on_death():
    ds = rd.range(40, parallelism=4)
    it0, it1 = ds.streaming_split(2)
    coord = it0._coord
    seen = []
    # Consumer 0 takes one block and dies without asking for the next:
    # its outstanding block must be requeued for the survivor.
    first = ray_tpu.get(coord.next_block.remote(0))
    assert first is not None
    ray_tpu.get(coord.mark_dead.remote(0))
    for blk in it1.iter_blocks():
        seen.extend(blk.column("id").to_pylist())
    assert sorted(seen) == list(range(40))
    prog = ray_tpu.get(coord.progress.remote())
    assert prog["exhausted"] and prog["outstanding"] == 0


def test_device_prefetcher_overlap_and_order():
    from ray_tpu.data.streaming.prefetch import DevicePrefetcher

    src = iter(range(20))
    pf = DevicePrefetcher(src, lambda x: x * 2, depth=2, name="t")
    got = list(pf)
    assert got == [x * 2 for x in range(20)]
    assert pf.hits + pf.misses == 21   # 20 items + the StopIteration pull


def test_device_prefetcher_propagates_errors_and_closes():
    from ray_tpu.data.streaming.prefetch import DevicePrefetcher

    def bad():
        yield 1
        raise ValueError("upstream exploded")

    pf = DevicePrefetcher(bad(), lambda x: x, depth=2, name="t")
    with pytest.raises(ValueError, match="upstream exploded"):
        list(pf)
    # Early close stops the producer without hanging.
    pf2 = DevicePrefetcher(iter(range(1000)), lambda x: x, depth=2,
                           name="t")
    assert next(pf2) == 0
    pf2.close()


def test_data_plane_gauges_registered_after_execution():
    from ray_tpu.util.metrics import registry_dump

    ds = rd.range(100, parallelism=4).map_batches(
        lambda b: {"x": b["id"]}, batch_format="numpy")
    ds.to_numpy()
    names = {m["name"] for m in registry_dump()}
    assert "data_op_bytes_in_flight" in names
    assert "data_op_stall_seconds" in names


def test_iter_jax_batches_streaming_feed():
    jax = pytest.importorskip("jax")

    ds = rd.range(64, parallelism=4)
    batches = list(ds.iter_jax_batches(batch_size=16))
    assert len(batches) == 4
    total = np.sort(np.concatenate([np.asarray(b["id"]) for b in batches]))
    np.testing.assert_array_equal(total, np.arange(64))
    assert all(isinstance(b["id"], jax.Array) for b in batches)
