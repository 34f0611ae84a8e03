"""Starting and stopping the program's runtime from the process that
parses the command line.  That process never initialises a JAX backend
(chip_smoke.py's rule): a chip belongs to one process, and the model
lives in the worker the runtime starts."""
from __future__ import annotations

import os
import time

from bench.harness.spec import ROOT


class NoChips(Exception):
    pass


def worker_environment() -> None:
    """What every worker inherits.  The compile cache at
    JAX_COMPILATION_CACHE_DIR if set, else the program's fixed path in
    the checkout (`compile_cache.configure()`); its floors lowered to
    zero, so that programs that compile in under JAX's one-second floor
    are cached too and a warm run compiles nothing."""
    from ray_tpu.util import compile_cache

    compile_cache.configure()
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in os.environ.get("PYTHONPATH", "").split(
            os.pathsep) if p])
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or libtpu logs to /tmp
    # A replica's constructor here loads, compiles every tier and checks
    # logits; on a cold cache that can pass the program's 120 s limit on
    # an actor's construction.
    os.environ.setdefault("RAY_TPU_ACTOR_CREATION_TIMEOUT_S", "900")


def start(chips: int, rehearse: bool) -> None:
    """`ray_tpu.init()`: detection must find the chips itself.  A
    rehearsal (CPU) declares them instead, and fails the device check at
    the end."""
    import ray_tpu

    worker_environment()
    ray_tpu.init(num_tpus=chips if rehearse else None)
    found = ray_tpu.cluster_resources().get("TPU", 0)
    if found < chips:
        stop()
        raise NoChips(f"this host advertises TPU: {found:g}, the cell "
                      f"needs {chips}")


def stop(*pids: int) -> None:
    """Stops everything this run started and waits until each worker
    that held a chip has ended."""
    import ray_tpu
    from ray_tpu import serve

    try:
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    deadline = time.monotonic() + 60
    for pid in pids:
        while os.path.exists(f"/proc/{pid}") and time.monotonic() < deadline:
            time.sleep(0.05)


def parent_is_off_jax() -> bool:
    import sys

    bridge = sys.modules.get("jax._src.xla_bridge")
    return bridge is None or not bridge.backends_are_initialized()
