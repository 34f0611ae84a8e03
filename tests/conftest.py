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

# The run's own compile cache: a directory made for this run and handed to
# JAX's persistent cache before jax is imported, with its floors at zero,
# so that a program compiled once (by any of xdist's workers, or by a
# process a test starts) is loaded wherever it is built again: the suite
# builds the same engines' chunk and burst programs hundreds of times.
# Nobody sets it from outside (a directory that outlives the run would be
# a second result), and whoever made it removes it at the run's end.
_RUN_CACHE = None
if not os.environ.get("PYTEST_XDIST_WORKER"):     # xdist's workers inherit
    import tempfile

    _RUN_CACHE = tempfile.mkdtemp(prefix="ray_tpu_tests_jax_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _RUN_CACHE
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

import case_limit  # noqa: E402

# The limit's own fixture modules run in inner runs, under a conftest of
# their own (tests/test_case_limit.py); tier-1 does not come by.
collect_ignore = ["case_limit_fixtures"]


def pytest_configure(config):
    config.pluginmanager.register(case_limit, "case_limit")


# `--dist loadfile` hands files to its workers in collection order, and a
# file of minutes that starts last is the tail of the run's wall: the files
# over ~120 CPU-seconds (junit of the driver's command, PR 65) start first,
# longest first, and after them the one file of a minute that the alphabet
# would start last; every other file keeps its place.
_LONG_FIRST = (
    "test_moe", "test_tpu_compile", "test_dsa_moe_serving",
    "test_gated_delta_serving", "test_short_conv_serving",
    "test_mamba2_moe_serving",
    "test_mla_moe_serving", "test_paged_attention",
    "test_window_moe_serving", "test_hybrid_serving",
    "test_gated_moe_serving", "test_mhc_mla_serving",
    "test_group_moe_serving", "test_looped_serving", "test_tune_breadth")


def pytest_collection_modifyitems(items):
    rank = {name: at for at, name in enumerate(_LONG_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.stem, len(rank)))


def pytest_unconfigure(config):
    if _RUN_CACHE is not None:
        import shutil

        shutil.rmtree(_RUN_CACHE, ignore_errors=True)


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
