"""`ray_tpu.get(ref)` with no timeout, on a cluster a fixture started."""
import psutil
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    ray_tpu.init(num_cpus=1)
    pids = [p.pid for p in psutil.Process().children(recursive=True)]
    (tmp_path_factory.getbasetemp() / "cluster_pids").write_text(
        " ".join(map(str, pids)))
    yield ray_tpu
    ray_tpu.shutdown()


def test_get_of_a_task_that_never_ends(cluster):
    @ray_tpu.remote
    def never():
        import time
        time.sleep(10 ** 6)

    ray_tpu.get(never.remote())
