"""TPU slice helpers: worker-side introspection + driver-side slice gangs.

Worker-side mirrors `ray.util.accelerators.tpu` (ref: python/ray/util/
accelerators/tpu.py:7,19 — get_current_pod_name / get_current_pod_worker_count).
Driver-side adds what the reference leaves to user code: discovering slices
from the cluster resource view (every host of a slice carries `{tpu_name: 1}`
and worker 0 carries `TPU-{pod_type}-head: 1`, ref: _private/accelerators/
tpu.py:336-397) and reserving one slice atomically as a placement group so a
pjit gang lands inside a single ICI domain.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from ray_tpu.core.distributed import accelerators
from ray_tpu.util.placement_group import (
    PlacementGroup,
    placement_group,
    remove_placement_group,
)

HEAD_PREFIX = "TPU-"
HEAD_SUFFIX = "-head"


# ---------------------------------------------------------------------------
# worker-side introspection (runs inside a task/actor on a TPU host)
# ---------------------------------------------------------------------------

def get_current_pod_name() -> Optional[str]:
    """Name of the TPU slice this host belongs to (ref: tpu.py:7)."""
    return accelerators.get_tpu_name()


def get_current_pod_worker_count() -> Optional[int]:
    """Number of hosts in this host's slice (ref: tpu.py:19)."""
    return accelerators.num_hosts_in_pod()


def get_num_tpu_chips_on_node() -> int:
    """TPU chips on THIS host (ref: tpu.py get_current_node_tpu_chips)."""
    import ray_tpu

    try:
        node_id = ray_tpu.get_runtime_context().get_node_id()
        for n in ray_tpu.nodes():
            if n["NodeID"] == node_id:
                return int(n["Resources"].get("TPU", 0))
    except Exception:  # noqa: BLE001 — not connected; probe locally
        pass
    try:
        from ray_tpu.core.distributed.resources import probe_tpu_count

        return int(probe_tpu_count())
    except Exception:  # noqa: BLE001
        return 0


def device_report() -> Dict[str, object]:
    """What THIS process computes on, as JAX reports it — for the process
    that owns the chip to say (a caller that asked JAX itself would take
    the chip).  Initialises the backend; memory fields are None where the
    backend reports none (the CPU)."""
    import jax

    devices = jax.devices()
    memory = devices[0].memory_stats() or {}
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices),
            "peak_bytes_in_use": memory.get("peak_bytes_in_use"),
            "bytes_limit": memory.get("bytes_limit")}


# ---------------------------------------------------------------------------
# driver-side slice discovery + atomic reservation
# ---------------------------------------------------------------------------

class TpuSlice:
    """One discovered slice: its name resource, pod type, and host nodes."""

    def __init__(self, name: str, pod_type: str, node_ids: List[str],
                 chips_per_host: float):
        self.name = name
        self.pod_type = pod_type
        self.node_ids = node_ids
        self.chips_per_host = chips_per_host

    @property
    def num_hosts(self) -> int:
        return len(self.node_ids)

    def __repr__(self) -> str:
        return (f"TpuSlice({self.name!r}, {self.pod_type}, "
                f"{self.num_hosts} hosts)")


def list_slices(pod_type: Optional[str] = None) -> List[TpuSlice]:
    """Discover slices from node resources: a node carrying
    `TPU-{pod_type}-head` names its slice via the co-resident custom
    resource that other hosts of the slice share."""
    import ray_tpu

    nodes = ray_tpu.nodes()
    slices: List[TpuSlice] = []
    for n in nodes:
        if not n["Alive"]:
            continue
        head_keys = [k for k in n["Resources"]
                     if k.startswith(HEAD_PREFIX) and k.endswith(HEAD_SUFFIX)]
        for hk in head_keys:
            pt = hk[len(HEAD_PREFIX):-len(HEAD_SUFFIX)]
            if pod_type is not None and pt != pod_type:
                continue
            # The slice-name resource is the custom resource the head node
            # shares with its sibling hosts. Disambiguate from arbitrary
            # custom resources by membership count: prefer the key carried
            # by exactly the pod's host count, else the widest-shared key.
            expected = accelerators.num_hosts_in_pod(pt)
            best = None  # (score, name, members)
            for k in n["Resources"]:
                if k in ("CPU", "TPU", "memory") or k == hk:
                    continue
                if (k.startswith("accelerator_type:")
                        or (k.startswith(HEAD_PREFIX)
                            and k.endswith(HEAD_SUFFIX))):
                    continue
                peers = [m for m in nodes
                         if m["Alive"] and k in m["Resources"]]
                score = (2 if expected and len(peers) == expected else 1,
                         len(peers))
                if best is None or score > best[0]:
                    best = (score, k, peers)
            if best is None:
                continue
            name, members = best[1], best[2]
            chips = float(n["Resources"].get("TPU", 0))
            slices.append(TpuSlice(name, pt,
                                   [m["NodeID"] for m in members], chips))
    return slices


def reserve_slice(pod_type: str, timeout: float = 60.0,
                  cpus_per_host: float = 0.0) -> "SliceReservation":
    """Reserve ONE whole slice of `pod_type` atomically.

    The gang placement group puts one bundle on every host of a single
    slice ({slice_name: 1, TPU: chips} per host, STRICT_SPREAD), so two
    concurrent gangs can never interleave on the same slice — the second
    reservation waits until a slice is free (ref slice-gang pattern:
    _private/accelerators/tpu.py:382).
    """
    import time as _time

    from ray_tpu.core.distributed import accelerators as _acc

    expected_hosts = _acc.num_hosts_in_pod(pod_type)
    deadline = _time.monotonic() + timeout
    last_err = "no slices found"
    while _time.monotonic() < deadline:
        for sl in list_slices(pod_type):
            if expected_hosts and sl.num_hosts < expected_hosts:
                # Slice still booting (autoscaler launched it seconds
                # ago; some hosts haven't registered): reserving a
                # partial gang would hand out a PG with missing bundles.
                last_err = (f"slice {sl.name} has {sl.num_hosts}/"
                            f"{expected_hosts} hosts up")
                continue
            bundle = {sl.name: 1.0, "TPU": sl.chips_per_host}
            if cpus_per_host:
                bundle["CPU"] = cpus_per_host
            pg = placement_group([dict(bundle) for _ in range(sl.num_hosts)],
                                 strategy="STRICT_SPREAD")
            remaining = max(0.5, deadline - _time.monotonic())
            if pg.ready(timeout=min(5.0, remaining)):
                return SliceReservation(sl, pg)
            # Slice busy (another gang holds it): drop the pending PG and
            # try the next slice / retry.
            remove_placement_group(pg)
            last_err = f"slice {sl.name} busy"
        _time.sleep(0.2)
    raise TimeoutError(f"could not reserve a {pod_type} slice in "
                       f"{timeout}s: {last_err}")


class SliceReservation:
    """Holds a reserved slice; schedule gang members into `pg` bundles."""

    def __init__(self, tpu_slice: TpuSlice, pg: PlacementGroup):
        self.slice = tpu_slice
        self.pg = pg

    def release(self) -> None:
        remove_placement_group(self.pg)
