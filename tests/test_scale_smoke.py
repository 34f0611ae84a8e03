"""CI-sized scale smoke (ref: release/benchmarks/distributed/
test_many_tasks.py, test_many_actors.py scaled to a shared-CPU test
box; the full-size envelopes below are marked slow)."""
import time

import pytest


def test_task_flood_and_queue_drain(cluster_ray):
    """A queued burst (all CPUs blocked) drains completely and in full
    once released — the many_tasks/queued-flood shape."""
    ray_tpu = cluster_ray

    import os
    import tempfile

    @ray_tpu.remote(num_cpus=4)
    def blocker(path):
        import pathlib
        import time as _t

        while not pathlib.Path(path).exists():
            _t.sleep(0.02)
        return "released"

    @ray_tpu.remote
    def tick(i):
        return i

    release = os.path.join(tempfile.mkdtemp(), "go")
    b = blocker.remote(release)
    time.sleep(0.3)
    refs = [tick.remote(i) for i in range(2000)]
    open(release, "w").close()
    assert ray_tpu.get(b, timeout=60) == "released"
    out = ray_tpu.get(refs, timeout=300)
    assert out == list(range(2000))


def _actor_churn(ray_tpu, total: int, wave: int,
                 timeout: float = 1800.0) -> float:
    """Create+ping+kill `total` actors in waves; returns actors/s."""

    @ray_tpu.remote(num_cpus=0)
    class Tiny:
        def ping(self):
            return 1

    t0 = time.perf_counter()
    for i in range(0, total, wave):
        batch = [Tiny.remote() for _ in range(min(wave, total - i))]
        assert ray_tpu.get([a.ping.remote() for a in batch],
                           timeout=timeout) == [1] * len(batch)
        for a in batch:
            ray_tpu.kill(a)
    rate = total / (time.perf_counter() - t0)
    time.sleep(1.0)
    alive = [a for a in ray_tpu.api._global_worker().gcs.call(
        "ActorManager", "list_actors", timeout=30)
        if a["state"] == "ALIVE" and a["cls_name"] == "Tiny"]
    assert not alive, alive
    return rate


def test_actor_wave_create_ping_kill(cluster_ray):
    """Sustained actor churn: waves of create+ping+kill leave no stuck
    actors behind (the many_actors shape, tier-1 sized)."""
    _actor_churn(cluster_ray, total=12, wave=6)


@pytest.mark.slow
def test_many_actors_1000(cluster_ray):
    """Full-size many_actors envelope: 1,000 actors through the zygote
    fork path. The asserted floor is low enough that a loaded CI box
    doesn't flake, but far above what cold spawning reaches — a
    regression to cold spawning fails this."""
    rate = _actor_churn(cluster_ray, total=1000, wave=50)
    assert rate >= 5.0, f"actor churn regressed to {rate:.2f}/s"


def _virtual_node_envelope(n_nodes: int, churn_rounds: int,
                           report_interval_s: float) -> tuple:
    """Stand up `n_nodes` virtual daemons (virtual_node.py) against an
    in-process GCS, churn load, and return (alive, gcs_stats, agg)."""
    import asyncio

    from ray_tpu.core.distributed.gcs_server import GcsServer
    from ray_tpu.core.distributed.virtual_node import VirtualCluster

    async def run():
        gcs = GcsServer()
        port = await gcs.start()
        vc = VirtualCluster(f"127.0.0.1:{port}", n_nodes=n_nodes,
                            report_interval_s=report_interval_s,
                            keepalive_s=2.0, subscribers=3, seed=11)
        await vc.start()
        for _ in range(churn_rounds):
            vc.churn(0.25)
            await asyncio.sleep(report_interval_s + 0.1)
        await asyncio.sleep(1.5)
        alive = sum(1 for nv in gcs.nodes.view.nodes.values() if nv.alive)
        stats = gcs.syncer.stats()
        agg = vc.aggregate_stats()
        sub_view = len(vc.nodes[0].view.nodes)
        await vc.stop()
        await gcs.stop()
        return alive, stats, agg, sub_view

    return asyncio.run(run())


def test_virtual_nodes_100_sync_deltas():
    """CI-sized many_nodes shape: 100 virtual daemons register, sync
    deltas (not full-state posts), and stay alive through churn."""
    alive, stats, agg, sub_view = _virtual_node_envelope(
        100, churn_rounds=3, report_interval_s=0.1)
    assert alive == 100
    assert agg["errors"] == 0
    assert stats["applied_deltas"] >= 1
    delta_like = stats["applied_deltas"] + agg["suppressed"]
    assert delta_like >= 2 * stats["applied_full"], (stats, agg)
    assert sub_view == 100


@pytest.mark.slow
def test_many_virtual_nodes_1000():
    """Full-size scale envelope: 1000 virtual daemons sustained on one
    GCS, with the sync path provably delta-dominant — a regression to
    full-state reporting (or nodes flapping dead under load) fails
    this."""
    alive, stats, agg, sub_view = _virtual_node_envelope(
        1000, churn_rounds=8, report_interval_s=0.5)
    assert alive >= 1000, f"only {alive}/1000 virtual daemons alive"
    assert agg["errors"] == 0, agg
    assert stats["applied_deltas"] >= 100
    ratio = ((stats["applied_deltas"] + agg["suppressed"])
             / max(1, stats["applied_full"]))
    assert ratio >= 3.0, (stats, agg)
    assert sub_view >= 1000


def test_many_args_many_returns_many_gets(cluster_ray):
    """Single-node scalability shapes: wide arg lists, wide returns,
    bulk get (ref: single_node/test_single_node.py)."""
    ray_tpu = cluster_ray

    arg_refs = [ray_tpu.put(i) for i in range(200)]

    @ray_tpu.remote
    def sink(*xs):
        return sum(xs)

    assert ray_tpu.get(sink.remote(*arg_refs),
                       timeout=120) == sum(range(200))

    n = 64

    @ray_tpu.remote(num_returns=n)
    def fan():
        return list(range(n))

    assert ray_tpu.get(list(fan.remote()), timeout=120) == list(range(n))

    refs = [ray_tpu.put(i) for i in range(1500)]
    assert ray_tpu.get(refs, timeout=120) == list(range(1500))
