"""Atomic gang scheduling: two-phase reserve/commit, rollback, repair.

The contract under test (ISSUE 8 tentpole layer 1): a STRICT_* bundle
set is reserved all-or-nothing — a half-placed gang must never leak
bundles or prestart zygote workers — and a gang that loses a node is
repaired bundle-granularly (survivor bundles stay reserved; only the
holes are re-placed).
"""
import threading
import time

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util.placement_group import (placement_group,
                                          placement_group_table,
                                          remove_placement_group)


def _metric(text: str, name: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name + "{") or line.startswith(name + " "):
            total += float(line.rsplit(" ", 1)[1])
    return total


def _daemons():
    from ray_tpu.api import _global_worker
    from ray_tpu.core.distributed.rpc import SyncRpcClient

    w = _global_worker()
    return [SyncRpcClient(n["Address"], w.loop_thread)
            for n in ray_tpu.nodes() if n["Alive"]]


def _pg_info(pg) -> dict:
    from ray_tpu.api import _global_worker

    return _global_worker().get_placement_group(pg.id)


@pytest.fixture(scope="module")
def gang_cluster():
    cluster = Cluster(head_node_args={"num_cpus": 2})
    cluster.add_node(num_cpus=2)
    cluster.connect()
    cluster.wait_for_nodes(2)
    yield cluster
    cluster.shutdown()


def test_strict_spread_insufficient_capacity_no_leaks(gang_cluster):
    """3 exclusive bundles on 2 nodes can never place: the gang must
    stay PENDING with ZERO bundles reserved anywhere and ZERO workers
    prewarmed for it — a half-placed gang is the bug."""
    pg = placement_group([{"CPU": 1}] * 3, strategy="STRICT_SPREAD")
    assert not pg.ready(timeout=2)
    try:
        clients = _daemons()
        try:
            for c in clients:
                state = c.call("NodeDaemon", "debug_state", timeout=15)
                assert state["pg_bundles"] == 0, state
                assert state["pg_bundles_uncommitted"] == 0, state
                text = c.call("NodeDaemon", "get_metrics", timeout=15)
                assert _metric(text,
                               "raytpu_pg_prewarmed_workers_total") == 0
        finally:
            for c in clients:
                c.close()
        info = _pg_info(pg)
        assert info["state"] == "PENDING"
        assert info["placed"] == 0
    finally:
        remove_placement_group(pg)


def test_prepare_ttl_expiry_returns_resources(gang_cluster):
    """PREPARE without COMMIT (a GCS that died mid-reserve) must be
    swept by the daemon's TTL backstop: resources come back, the
    phantom bundle disappears."""
    clients = _daemons()
    c = clients[0]
    try:
        before = c.call("NodeDaemon", "debug_state", timeout=15)
        reply = c.call("NodeDaemon", "reserve_pg_bundle",
                       pg_id="ttl-test", bundle_idx=0,
                       resources={"CPU": 1}, ttl_s=1.0, timeout=15)
        assert reply["ok"], reply
        mid = c.call("NodeDaemon", "debug_state", timeout=15)
        assert mid["pg_bundles_uncommitted"] >= 1
        assert mid["available"]["CPU"] == before["available"]["CPU"] - 1
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            after = c.call("NodeDaemon", "debug_state", timeout=15)
            if (after["pg_bundles"] == before["pg_bundles"]
                    and after["available"]["CPU"]
                    == before["available"]["CPU"]):
                return
            time.sleep(0.2)
        pytest.fail(f"prepared bundle never expired: {after}")
    finally:
        for cl in clients:
            cl.close()


def test_commit_marks_bundle_usable_and_prewarms(gang_cluster):
    """COMMIT flips the bundle usable and (prestart enabled) prewarms
    one worker for it; release returns the resources."""
    clients = _daemons()
    c = clients[0]
    try:
        # Clear the idle pool so the cap check cannot mask the prewarm.
        c.call("NodeDaemon", "flush_idle_workers", timeout=15)
        before = c.call("NodeDaemon", "debug_state", timeout=15)
        text = c.call("NodeDaemon", "get_metrics", timeout=15)
        warm_before = _metric(text, "raytpu_pg_prewarmed_workers_total")
        assert c.call("NodeDaemon", "reserve_pg_bundle",
                      pg_id="commit-test", bundle_idx=0,
                      resources={"CPU": 1}, timeout=15)["ok"]
        assert c.call("NodeDaemon", "commit_pg_bundle",
                      pg_id="commit-test", bundle_idx=0, timeout=15)["ok"]
        state = c.call("NodeDaemon", "debug_state", timeout=15)
        assert state["pg_bundles_uncommitted"] == 0
        assert state["pg_bundles"] == before["pg_bundles"] + 1
        deadline = time.monotonic() + 20
        warm_after = warm_before
        while time.monotonic() < deadline:
            text = c.call("NodeDaemon", "get_metrics", timeout=15)
            warm_after = _metric(text, "raytpu_pg_prewarmed_workers_total")
            if warm_after > warm_before:
                break
            time.sleep(0.2)
        assert warm_after > warm_before, "commit never prewarmed a worker"
        # Committed bundles survive the TTL sweep.
        time.sleep(1.5)
        state = c.call("NodeDaemon", "debug_state", timeout=15)
        assert state["pg_bundles"] == before["pg_bundles"] + 1
        c.call("NodeDaemon", "return_pg_bundle", pg_id="commit-test",
               bundle_idx=0, timeout=15)
        state = c.call("NodeDaemon", "debug_state", timeout=15)
        assert state["available"]["CPU"] == before["available"]["CPU"]
    finally:
        for cl in clients:
            cl.close()


@pytest.mark.parametrize("first", ["bundle", "lease"])
def test_bundle_return_and_holder_release_credit_once(gang_cluster, first):
    """A bundle gives back only what no live lease holds; the held share
    comes back with its holder.  Whichever goes first, the node ends at
    exactly what it started with: a double credit would oversubscribe a
    chip, a missed one would leak it for good."""
    clients = _daemons()
    c = clients[0]
    pg_id = f"credit-{first}"
    try:
        before = c.call("NodeDaemon", "debug_state", timeout=15)
        cpu = before["available"]["CPU"]
        assert c.call("NodeDaemon", "reserve_pg_bundle", pg_id=pg_id,
                      bundle_idx=0, resources={"CPU": 2}, timeout=15)["ok"]
        assert c.call("NodeDaemon", "commit_pg_bundle", pg_id=pg_id,
                      bundle_idx=0, timeout=15)["ok"]
        lease = c.call("NodeDaemon", "request_lease", demand={"CPU": 1},
                       placement=(pg_id, 0), timeout=30)
        assert lease.get("granted"), lease
        state = c.call("NodeDaemon", "debug_state", timeout=15)
        assert state["available"]["CPU"] == cpu - 2

        def return_bundle():
            c.call("NodeDaemon", "return_pg_bundle", pg_id=pg_id,
                   bundle_idx=0, timeout=15)

        def return_lease():
            c.call("NodeDaemon", "return_lease",
                   lease_id=lease["lease_id"], timeout=15)

        if first == "bundle":
            return_bundle()
            # The unheld CPU is back; the holder's is still out.
            state = c.call("NodeDaemon", "debug_state", timeout=15)
            assert state["available"]["CPU"] == cpu - 1
            return_lease()
        else:
            return_lease()
            # Released into the bundle, which still reserves both.
            state = c.call("NodeDaemon", "debug_state", timeout=15)
            assert state["available"]["CPU"] == cpu - 2
            return_bundle()
        state = c.call("NodeDaemon", "debug_state", timeout=15)
        assert state["available"]["CPU"] == cpu
        assert state["pg_bundles"] == before["pg_bundles"]
        assert state["leases"] == before["leases"]
        # Idempotent: a second return of either credits nothing.
        return_bundle()
        return_lease()
        state = c.call("NodeDaemon", "debug_state", timeout=15)
        assert state["available"]["CPU"] == cpu
    finally:
        for cl in clients:
            cl.close()


def test_ready_long_polls_and_wakes_on_capacity(gang_cluster):
    """PlacementGroup.ready() parks in the GCS long-poll (no driver
    sleep loop) and wakes promptly when the missing capacity joins."""
    pg = placement_group([{"gang_res": 1}], strategy="PACK")
    woke_after = {}

    def waiter():
        t0 = time.monotonic()
        woke_after["ok"] = pg.ready(timeout=60)
        woke_after["s"] = time.monotonic() - t0

    th = threading.Thread(target=waiter, daemon=True)
    th.start()
    time.sleep(1.0)
    assert not woke_after  # still parked — capacity absent
    gang_cluster.add_node(num_cpus=1, resources={"gang_res": 1})
    th.join(timeout=30)
    assert woke_after.get("ok"), woke_after
    # Parked wake + one reserve round, not a 60s timeout burn.
    assert woke_after["s"] < 30, woke_after
    remove_placement_group(pg)


@pytest.mark.slow
def test_node_death_punches_hole_and_repairs():
    """Losing one node of a CREATED gang demotes it to PENDING with the
    survivor bundle still placed (bundle-granular repair), and a
    replacement node restores CREATED without touching the survivor."""
    ray_tpu.shutdown()
    cluster = Cluster(head_node_args={"num_cpus": 1})
    second = cluster.add_node(num_cpus=1)
    cluster.connect()
    cluster.wait_for_nodes(2)
    try:
        pg = placement_group([{"CPU": 1}] * 2, strategy="STRICT_SPREAD")
        assert pg.ready(timeout=60)
        survivor_nid = [n for n in _pg_info(pg)["nodes"]
                        if n != second.node_id]
        cluster.remove_node(second)  # SIGKILL
        deadline = time.monotonic() + 60
        info = None
        while time.monotonic() < deadline:
            info = _pg_info(pg)
            if info["state"] == "PENDING":
                break
            time.sleep(0.25)
        assert info and info["state"] == "PENDING", info
        # Hole punched for the dead node only; survivor keeps its spot.
        assert info["placed"] == 1, info
        assert [n for n in info["nodes"] if n is not None] == survivor_nid
        cluster.add_node(num_cpus=1)
        assert pg.ready(timeout=60)
        info = _pg_info(pg)
        assert info["placed"] == 2
        assert survivor_nid[0] in info["nodes"]
        remove_placement_group(pg)
    finally:
        cluster.shutdown()
