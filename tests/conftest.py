import os
import sys

# Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
# exercised without TPU hardware (the driver separately dry-runs the
# multi-chip path). Must be set before jax is imported anywhere.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Subprocesses (GCS server, node daemons, workers) inherit both variables.

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture
def local_ray():
    import ray_tpu

    ray_tpu.init(local_mode=True, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def cluster_ray():
    """A real multi-process cluster (head + node daemon + workers)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()
