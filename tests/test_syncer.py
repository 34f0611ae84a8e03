"""Cluster-state syncer semantics (syncer.py; ref: ray_syncer.proto:62 —
versioned delta sync with sequence-numbered idempotent apply).

Three layers:
  * ClusterSyncer apply rules driven directly (no RPC): ordering,
    duplicates, gaps, stale-node verdicts.
  * NodeSyncer report logic against a fake transport: first-contact full
    snapshot, suppression, burst coalescing, resync handshake.
  * End-to-end over the real RPC stack: deltas land in the GCS view,
    the fan-out stream feeds a subscriber's spillback view, and a
    virtual cluster sustains the delta-dominant ratio.
"""
import asyncio

import pytest


def make_gcs():
    from ray_tpu.core.distributed.gcs_server import GcsServer

    return GcsServer()


def register(gcs, node_id="n1", cpus=4.0):
    gcs.nodes.register_node(node_id, f"virtual:{node_id}",
                            {"CPU": cpus}, "")


# ---------------------------------------------------------------------------
# ClusterSyncer: idempotent versioned apply
# ---------------------------------------------------------------------------

def test_delta_ordering_and_idempotent_apply():
    gcs = make_gcs()
    register(gcs)
    syn = gcs.syncer

    # First contact must be a full snapshot: a delta against an unknown
    # base gets a resync verdict, never a partial apply.
    r = syn.push_update("n1", version=1, base_version=0,
                        state={"available": {"CPU": 3.0}})
    assert r.get("resync") and not r["ok"]

    r = syn.push_update("n1", version=1, base_version=0, full=True,
                        state={"available": {"CPU": 3.0}, "workers": 2})
    assert r["ok"] and r["applied"] == 1
    view = gcs.nodes.view.nodes["n1"]
    assert view.available == {"CPU": 3.0} and view.workers == 2

    r = syn.push_update("n1", version=2, base_version=1,
                        state={"available": {"CPU": 1.0}})
    assert r["ok"] and r["applied"] == 2
    assert view.available == {"CPU": 1.0}

    # Duplicate replay (at-least-once retry): ignored, view untouched.
    r = syn.push_update("n1", version=2, base_version=1,
                        state={"available": {"CPU": 9.0}})
    assert r["ok"] and r["applied"] == 2
    assert view.available == {"CPU": 1.0}

    # Reordered old delta: ignored the same way.
    r = syn.push_update("n1", version=1, base_version=0,
                        state={"available": {"CPU": 8.0}})
    assert r["ok"] and r["applied"] == 2
    assert view.available == {"CPU": 1.0}

    # Version gap (lost delta): resync verdict, then the full snapshot
    # re-establishes the sequence.
    r = syn.push_update("n1", version=5, base_version=4,
                        state={"available": {"CPU": 0.5}})
    assert r.get("resync")
    assert view.available == {"CPU": 1.0}
    r = syn.push_update("n1", version=5, base_version=4, full=True,
                        state={"available": {"CPU": 0.5}, "workers": 7})
    assert r["ok"] and r["applied"] == 5
    assert view.available == {"CPU": 0.5} and view.workers == 7

    s = syn.stats()
    assert s["applied_deltas"] == 1
    assert s["applied_full"] == 2
    assert s["stale_ignored"] == 2
    assert s["resync_requests"] == 2


def test_unknown_and_dead_node_verdicts():
    gcs = make_gcs()
    syn = gcs.syncer

    r = syn.push_update("ghost", version=1, base_version=0, full=True,
                        state={})
    assert r["registered"] is False and not r.get("stale")

    register(gcs)
    syn.push_update("n1", version=1, base_version=0, full=True,
                    state={"available": {"CPU": 4.0}})
    gcs.nodes.mark_dead("n1", reason="test")
    # Pushes from a dead node must not resurrect it silently.
    r = syn.push_update("n1", version=2, base_version=1,
                        state={"available": {"CPU": 4.0}})
    assert r["registered"] is False and r["stale"] is True
    assert gcs.nodes.view.nodes["n1"].alive is False
    # ... and its version was dropped, so a deliberate re-registration
    # starts from a full snapshot again.
    register(gcs)
    r = syn.push_update("n1", version=3, base_version=2,
                        state={"available": {"CPU": 4.0}})
    assert r.get("resync")


def test_heartbeat_stale_node_verdict_and_reregister_event():
    gcs = make_gcs()
    register(gcs)
    assert gcs.nodes.heartbeat("n1", {"CPU": 2.0})["registered"]
    gcs.nodes.mark_dead("n1", reason="test")

    r = gcs.nodes.heartbeat("n1", {"CPU": 2.0})
    assert r["registered"] is False and r["stale"] is True
    # The rejected update must not have refreshed the dead entry.
    assert gcs.nodes.view.nodes["n1"].alive is False

    register(gcs)  # the daemon's explicit response to the verdict
    assert gcs.nodes.heartbeat("n1", {"CPU": 2.0})["registered"]
    events = gcs.event_log.list_events(source="node")
    assert any("re-registered" in e["message"] for e in events)


def test_keepalive_refreshes_liveness_without_state():
    import time

    gcs = make_gcs()
    register(gcs)
    syn = gcs.syncer
    syn.push_update("n1", version=1, base_version=0, full=True,
                    state={"available": {"CPU": 4.0}})
    n = gcs.nodes.view.nodes["n1"]
    n.last_heartbeat -= 100.0  # simulate silence
    stale_hb = n.last_heartbeat
    r = syn.push_update("n1", version=1, keepalive=True)
    assert r["ok"] and r["applied"] == 1
    assert n.last_heartbeat > stale_hb
    assert time.monotonic() - n.last_heartbeat < 5.0


# ---------------------------------------------------------------------------
# NodeSyncer: report-side diffing against a fake transport
# ---------------------------------------------------------------------------

class FakeGcs:
    def __init__(self):
        self.calls = []
        self.scripted = []      # FIFO of replies; default acks otherwise

    async def call(self, service, method, timeout=None, **kw):
        self.calls.append((service, method, kw))
        if self.scripted:
            return self.scripted.pop(0)
        return {"ok": True, "applied": kw.get("version")}


def _node_syncer(state, fake, **kw):
    from ray_tpu.core.distributed.syncer import NodeSyncer

    return NodeSyncer(
        gcs=fake, node_id="n1",
        collect=lambda: {k: (dict(v) if isinstance(v, dict) else v)
                         for k, v in state.items()},
        report_interval_s=0.01, keepalive_s=60.0, **kw)


def test_first_full_then_delta_then_suppression():
    async def run():
        state = {"available": {"CPU": 4.0}, "workers": 0}
        fake = FakeGcs()
        syn = _node_syncer(state, fake)

        assert await syn.sync_once() == "full"
        kw = fake.calls[-1][2]
        assert kw["full"] and kw["version"] == 1
        assert kw["state"] == {"available": {"CPU": 4.0}, "workers": 0}

        # Nothing changed: the tick is suppressed, no wire traffic.
        before = len(fake.calls)
        assert await syn.sync_once() == "suppressed"
        assert len(fake.calls) == before
        assert syn.stats["suppressed"] == 1

        # One field changed: the push carries ONLY the changed key.
        state["available"] = {"CPU": 1.0}
        assert await syn.sync_once() == "delta"
        kw = fake.calls[-1][2]
        assert kw["state"] == {"available": {"CPU": 1.0}}
        assert kw["base_version"] == 1 and kw["version"] == 2

    asyncio.run(run())


def test_burst_coalesces_into_one_delta():
    async def run():
        state = {"available": {"CPU": 4.0}, "workers": 0, "store_used": 0}
        fake = FakeGcs()
        syn = _node_syncer(state, fake)
        await syn.sync_once()

        # A burst of local changes between ticks rides ONE delta.
        state["available"] = {"CPU": 3.0}
        state["workers"] = 5
        state["available"] = {"CPU": 2.0}
        state["store_used"] = 1 << 20
        assert await syn.sync_once() == "delta"
        kw = fake.calls[-1][2]
        assert kw["state"] == {"available": {"CPU": 2.0}, "workers": 5,
                               "store_used": 1 << 20}
        assert syn.version == 2  # one version bump for the whole burst

    asyncio.run(run())


def test_resync_verdict_forces_full_snapshot():
    async def run():
        state = {"available": {"CPU": 4.0}}
        fake = FakeGcs()
        syn = _node_syncer(state, fake)
        await syn.sync_once()

        state["available"] = {"CPU": 1.0}
        fake.scripted.append({"ok": False, "resync": True})
        assert await syn.sync_once() == "resync"
        # Next cycle re-establishes with a full snapshot.
        assert await syn.sync_once() == "full"
        kw = fake.calls[-1][2]
        assert kw["full"] and kw["state"] == {"available": {"CPU": 1.0}}

    asyncio.run(run())


def test_stale_verdict_triggers_reregister_then_full():
    async def run():
        state = {"available": {"CPU": 4.0}}
        fake = FakeGcs()
        reregistered = []

        async def on_rereg():
            reregistered.append(True)

        syn = _node_syncer(state, fake, on_reregister=on_rereg)
        await syn.sync_once()

        state["available"] = {"CPU": 1.0}
        fake.scripted.append({"registered": False, "stale": True})
        assert await syn.sync_once() == "stale"
        assert reregistered == [True]
        assert await syn.sync_once() == "full"

    asyncio.run(run())


def test_keepalive_when_idle_past_deadline():
    async def run():
        state = {"available": {"CPU": 4.0}}
        fake = FakeGcs()
        syn = _node_syncer(state, fake)
        syn.keepalive_s = 0.0       # every idle tick must keepalive
        await syn.sync_once()
        assert await syn.sync_once() == "keepalive"
        service, method, kw = fake.calls[-1]
        assert kw.get("keepalive") and "state" not in kw

    asyncio.run(run())


# ---------------------------------------------------------------------------
# End-to-end over the real RPC stack
# ---------------------------------------------------------------------------

async def _wait_for(predicate, timeout=15.0, interval=0.05):
    deadline = asyncio.get_running_loop().time() + timeout
    while True:
        if predicate():
            return
        if asyncio.get_running_loop().time() > deadline:
            raise AssertionError("condition not reached in time")
        await asyncio.sleep(interval)


def test_end_to_end_delta_sync_and_fanout():
    from ray_tpu.core.distributed.gcs_server import GcsServer
    from ray_tpu.core.distributed.rpc import AsyncRpcClient
    from ray_tpu.core.distributed.virtual_node import VirtualNode

    async def run():
        gcs = GcsServer()
        port = await gcs.start()
        client = AsyncRpcClient(f"127.0.0.1:{port}")
        node = VirtualNode(client=client, node_id="e2e" + "0" * 13,
                           num_cpus=4.0, report_interval_s=0.05,
                           subscribe=True)
        await node.start()
        nid = node.node_id
        # First contact: the initial full snapshot must have landed
        # (register_node alone also shows CPU=4, so wait on the stat).
        await _wait_for(
            lambda: gcs.syncer.stats()["applied_full"] >= 1)
        assert gcs.nodes.view.nodes[nid].available == {"CPU": 4.0}

        # A local change ships as a delta and lands in the GCS view...
        node.state["available"] = {"CPU": 1.0}
        node.state["idle_workers"] = 3
        node.syncer.mark_dirty()
        await _wait_for(lambda: gcs.nodes.view.nodes[nid].available
                        == {"CPU": 1.0}
                        and gcs.nodes.view.nodes[nid].idle_workers == 3)

        # ... and fans back out into the subscriber's spillback view.
        await _wait_for(lambda: nid in node.view.nodes
                        and node.view.nodes[nid].available
                        == {"CPU": 1.0})

        stats = gcs.syncer.stats()
        assert stats["applied_full"] >= 1
        assert stats["applied_deltas"] >= 1
        assert stats["broadcasts"] >= 1
        assert node.syncer.stats["view_payloads"] >= 1
        await node.stop()
        await client.close()
        await gcs.stop()

    asyncio.run(run())


def test_virtual_cluster_delta_dominant_ratio():
    """A 30-node virtual cluster under churn keeps the sync path
    delta-dominant: full snapshots happen once per connect, steady state
    is deltas + suppressed ticks (the 1000-node envelope of
    tests/test_scale_smoke.py, tier-1 sized)."""
    from ray_tpu.core.distributed.gcs_server import GcsServer
    from ray_tpu.core.distributed.virtual_node import VirtualCluster

    async def run():
        gcs = GcsServer()
        port = await gcs.start()
        vc = VirtualCluster(f"127.0.0.1:{port}", n_nodes=30,
                            num_clients=4, report_interval_s=0.05,
                            keepalive_s=1.0, subscribers=2, seed=3)
        await vc.start()
        for _ in range(4):
            vc.churn(0.5)
            await asyncio.sleep(0.1)
        await _wait_for(
            lambda: gcs.syncer.stats()["applied_deltas"] >= 4)
        await asyncio.sleep(0.3)

        alive = sum(1 for n in gcs.nodes.view.nodes.values() if n.alive)
        assert alive == 30
        stats = gcs.syncer.stats()
        agg = vc.aggregate_stats()
        assert agg["errors"] == 0
        delta_like = stats["applied_deltas"] + agg["suppressed"]
        assert delta_like >= 2 * stats["applied_full"], (stats, agg)
        # Subscribers assembled the whole cluster from the fan-out.
        assert len(vc.nodes[0].view.nodes) == 30
        await vc.stop()
        await gcs.stop()

    asyncio.run(run())


def test_syncer_disabled_falls_back_to_heartbeats(monkeypatch):
    """RAY_TPU_SYNCER_ENABLED=0: the legacy heartbeat path alone keeps a
    cluster alive and schedulable (the syncer is an optimization, not a
    correctness dependency)."""
    import os

    import ray_tpu

    monkeypatch.setenv("RAY_TPU_SYNCER_ENABLED", "0")
    from ray_tpu.core.config import reset_config

    reset_config()
    try:
        ray_tpu.init(num_cpus=2, ignore_reinit_error=True)

        @ray_tpu.remote
        def f(x):
            return x * 2

        assert ray_tpu.get([f.remote(i) for i in range(8)],
                           timeout=60) == [i * 2 for i in range(8)]
        w = ray_tpu.api._global_worker()
        stats = w.gcs.call("Syncer", "stats", timeout=10)
        assert stats["applied_deltas"] == 0  # nothing rode the syncer
        assert any(n["alive"] for n in w.gcs.call(
            "NodeInfo", "list_nodes", timeout=10))
    finally:
        ray_tpu.shutdown()
        monkeypatch.delenv("RAY_TPU_SYNCER_ENABLED", raising=False)
        reset_config()
