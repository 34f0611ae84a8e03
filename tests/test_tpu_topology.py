"""TPU slice topology → scheduler integration (VERDICT r1 item 3).

Fakes a 4-host v5e-16 slice with env-seeded node daemons (the reference
fakes slices the same way around _private/accelerators/tpu.py:75-230:
GKE env vars TPU_ACCELERATOR_TYPE / TPU_NAME / TPU_WORKER_ID).
"""
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.core.distributed import accelerators


# ---------------------------------------------------------------------------
# unit: accelerator manager resource derivation
# ---------------------------------------------------------------------------

def test_extra_resources_head_vs_worker(monkeypatch):
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5e-16")
    monkeypatch.setenv("TPU_NAME", "my-slice")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    res = accelerators.tpu_extra_resources(4)
    assert res["my-slice"] == 1.0
    assert res["TPU-v5e-16-head"] == 1.0
    assert res["accelerator_type:TPU-V5E"] == 1.0

    monkeypatch.setenv("TPU_WORKER_ID", "2")
    res = accelerators.tpu_extra_resources(4)
    assert res["my-slice"] == 1.0
    assert "TPU-v5e-16-head" not in res


# ---------------------------------------------------------------------------
# unit: chip detection counts device nodes and never opens a chip
# ---------------------------------------------------------------------------

def _fake_dev(monkeypatch, accel=(), vfio=None):
    import glob
    import os

    def listdir(path):
        if vfio is None:
            raise FileNotFoundError(path)
        if isinstance(vfio, Exception):
            raise vfio
        return list(vfio)

    monkeypatch.setattr(glob, "glob", lambda pat: list(accel))
    monkeypatch.setattr(os, "listdir", listdir)
    for var in ("RAY_TPU_NUM_TPUS", "RAY_TPU_DISABLE_TPU_DETECTION"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")


@pytest.mark.parametrize("accel,vfio,want", [
    ((), None, 0),                                  # no chip on the host
    ((), ["vfio", "1"], 1),                         # the one-chip v5e host
    ((), ["0", "1", "2", "3", "vfio"], 4),          # a four-chip host
    (["/dev/accel0", "/dev/accel1"], ["vfio"], 2),  # accel nodes win
])
def test_chip_count_from_device_nodes(monkeypatch, accel, vfio, want):
    from ray_tpu.core.distributed import resources

    _fake_dev(monkeypatch, accel, vfio)
    assert resources.probe_tpu_count() == want
    total = resources.detect_node_resources(num_cpus=1)
    assert total.get("TPU", 0) == want


def test_chip_detection_overrides_and_logged_failure(monkeypatch, caplog):
    from ray_tpu.core.distributed import resources

    _fake_dev(monkeypatch, vfio=PermissionError("/dev/vfio"))
    with caplog.at_level("ERROR"):
        assert resources.probe_tpu_count() == 0
    assert "TPU detection failed" in caplog.text
    _fake_dev(monkeypatch, vfio=["1", "vfio"])
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")      # workers could not use it
    assert resources.probe_tpu_count() == 0
    monkeypatch.setenv("RAY_TPU_NUM_TPUS", "4")     # the operator knows
    assert resources.probe_tpu_count() == 4


def test_num_hosts_in_pod():
    assert accelerators.num_hosts_in_pod("v5e-16") == 4
    assert accelerators.num_hosts_in_pod("v4-16") == 2  # cores, 8/host
    assert accelerators.num_hosts_in_pod("v5e-4") == 1
    assert accelerators.num_hosts_in_pod("v5p-8") == 2


def test_visible_chip_env_fractional():
    env = accelerators.visible_chip_env([1])
    assert env["TPU_VISIBLE_CHIPS"] == "1"
    assert env["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
    env = accelerators.visible_chip_env([0, 1])
    assert env["TPU_VISIBLE_CHIPS"] == "0,1"
    assert env["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"
    assert accelerators.visible_chip_env([0, 1, 2, 3]) == {}


# ---------------------------------------------------------------------------
# integration: fake v5e-16 slice in a multi-daemon cluster
# ---------------------------------------------------------------------------

def _slice_env(name: str, worker_id: int) -> dict:
    return {
        "TPU_ACCELERATOR_TYPE": "v5e-16",
        "TPU_NAME": name,
        "TPU_WORKER_ID": str(worker_id),
        # Make sure the daemon never probes for real chips.
        "RAY_TPU_DISABLE_TPU_DETECTION": "1",
    }


@pytest.fixture(scope="module")
def slice_cluster():
    cluster = Cluster(head_node_args={"num_cpus": 2})
    for wid in range(4):
        cluster.add_node(num_cpus=1, num_tpus=4,
                         env=_slice_env("slice-a", wid))
    cluster.connect()
    cluster.wait_for_nodes(5)
    yield cluster
    cluster.shutdown()


def test_slice_resources_visible(slice_cluster):
    res = ray_tpu.cluster_resources()
    assert res["TPU"] == 16.0
    assert res["slice-a"] == 4.0          # one per host
    assert res["TPU-v5e-16-head"] == 1.0  # worker 0 only


def test_gang_lands_on_one_slice_and_excludes_second(slice_cluster):
    from ray_tpu.util import tpu as tpu_util
    from ray_tpu.util.scheduling_strategies import (
        PlacementGroupSchedulingStrategy,
    )

    slices = tpu_util.list_slices("v5e-16")
    assert len(slices) == 1
    assert slices[0].num_hosts == 4
    assert slices[0].chips_per_host == 4.0

    gang = tpu_util.reserve_slice("v5e-16", timeout=60)

    @ray_tpu.remote(num_cpus=0, resources={"TPU": 4})
    def host_rank():
        import os

        return (ray_tpu.get_runtime_context().get_node_id(),
                os.environ.get("TPU_NAME"))

    outs = ray_tpu.get([
        host_rank.options(
            scheduling_strategy=PlacementGroupSchedulingStrategy(
                placement_group=gang.pg, placement_group_bundle_index=i)
        ).remote()
        for i in range(4)
    ], timeout=120)
    nodes = {o[0] for o in outs}
    assert len(nodes) == 4            # one task per host, all distinct
    assert nodes == set(slices[0].node_ids)

    # The slice is fully held: a second gang cannot reserve it.
    with pytest.raises(TimeoutError):
        tpu_util.reserve_slice("v5e-16", timeout=6)

    # Release → the second gang immediately succeeds.
    gang.release()
    gang2 = tpu_util.reserve_slice("v5e-16", timeout=60)
    gang2.release()
