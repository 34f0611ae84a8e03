"""Resource sets and node resource accounting.

Analogue of the reference's scheduling resources (ref: src/ray/common/
scheduling/resource_set.h, cluster_resource_data.h). Resources are
name→float maps ("CPU", "TPU", "memory", custom labels, and gang resources
like "TPU-v5e-16-head" per the reference's slice-head pattern,
_private/accelerators/tpu.py:382).
"""
from __future__ import annotations

from typing import Dict, Optional

ResourceSet = Dict[str, float]

EPS = 1e-9


def fits(available: ResourceSet, demand: ResourceSet) -> bool:
    for k, v in demand.items():
        if v > EPS and available.get(k, 0.0) + EPS < v:
            return False
    return True


def feasible(total: ResourceSet, demand: ResourceSet) -> bool:
    """Could the demand EVER fit on a node with these total resources?"""
    return fits(total, demand)


def subtract(avail: ResourceSet, demand: ResourceSet) -> None:
    for k, v in demand.items():
        if v > EPS:
            avail[k] = avail.get(k, 0.0) - v


def add(avail: ResourceSet, demand: ResourceSet) -> None:
    for k, v in demand.items():
        if v > EPS:
            avail[k] = avail.get(k, 0.0) + v


def utilization(total: ResourceSet, available: ResourceSet,
                demand: Optional[ResourceSet] = None) -> float:
    """Critical-resource utilization in [0,1]: the max over resource types
    the demand cares about (all types if demand is None). Matches the
    reference's best-node scoring input (ref: policy/scheduling_options.h)."""
    worst = 0.0
    keys = demand.keys() if demand else total.keys()
    for k in keys:
        t = total.get(k, 0.0)
        if t <= EPS:
            continue
        used = t - available.get(k, 0.0)
        worst = max(worst, used / t)
    return worst


def count_tpu_device_nodes() -> int:
    """Chips this host exposes, counted from their device nodes.

    The reference counts `/dev/accel*` or, where chips are passed through
    VFIO, the numbered group nodes under `/dev/vfio`
    (ref: _private/accelerators/tpu.py `_get_current_node_num_accelerators`).
    A v5e host reached through VFIO shows one `/dev/vfio/<n>` per chip it
    was given.  Nothing is opened: a chip belongs to one process at a
    time, so detection that initialised a backend would take the chip from
    the first worker (and could not get it from a caller that holds it).
    """
    import glob
    import os

    accel = glob.glob("/dev/accel*")
    if accel:
        return len(accel)
    try:
        return sum(1 for e in os.listdir("/dev/vfio") if e.isdigit())
    except FileNotFoundError:
        return 0


def probe_tpu_count() -> int:
    """Count local TPU chips without touching them.

    Overrides (checked in order):
      - RAY_TPU_NUM_TPUS: trust the operator, skip detection.
      - RAY_TPU_DISABLE_TPU_DETECTION=1: always 0.
      - JAX_PLATFORMS=cpu in our env: always 0 (workers could not use a
        chip anyway; test/CI mode).
    A detection that fails is logged as an error: the node then
    advertises no TPU and `ScalingConfig(use_tpu=True)` cannot be placed.
    """
    import logging
    import os

    # lint: allow-knob -- detection override monkeypatched by tests mid-process; must stay dynamic
    forced = os.environ.get("RAY_TPU_NUM_TPUS")
    if forced is not None:
        return int(float(forced))
    # lint: allow-knob -- the autoscaler exports this into child envs; must stay dynamic
    if os.environ.get("RAY_TPU_DISABLE_TPU_DETECTION", "").lower() in (
            "1", "true", "yes"):
        return 0
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return 0
    try:
        return count_tpu_device_nodes()
    except OSError as e:
        logging.getLogger(__name__).error(
            "TPU detection failed (%r): this node advertises no TPU", e)
        return 0


def detect_node_resources(num_cpus: Optional[float] = None,
                          num_tpus: Optional[float] = None,
                          memory: Optional[int] = None,
                          custom: Optional[ResourceSet] = None) -> ResourceSet:
    """Autodetect this host's resources (TPU chips from their device
    nodes — the analogue of the reference's TPUAcceleratorManager
    autodetection, ref: _private/accelerators/tpu.py:52-230)."""
    import os

    res: ResourceSet = {}
    res["CPU"] = float(num_cpus if num_cpus is not None
                       else (os.cpu_count() or 1))
    n = float(num_tpus) if num_tpus is not None else float(probe_tpu_count())
    if n > 0:
        res["TPU"] = n
        # Slice-gang resources (TPU-{pod_type}-head etc.) attach whenever
        # the node has chips — explicit counts included, so operators who
        # pass --num-tpus on a GKE slice still get gang scheduling.
        try:
            from ray_tpu.core.distributed.accelerators import (
                tpu_extra_resources)

            res.update(tpu_extra_resources(int(n)))
        except Exception:
            pass
    if memory is None:
        try:
            import psutil

            memory = int(psutil.virtual_memory().total * 0.7)
        except Exception:
            memory = 8 << 30
    res["memory"] = float(memory)
    if custom:
        res.update(custom)
    return res
