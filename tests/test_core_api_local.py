"""API-semantics tests against the in-process engine.

Modeled on the reference's core API suites (ref: python/ray/tests/
test_basic.py, test_actor.py style coverage).
"""
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import exceptions as rexc


@pytest.fixture(autouse=True)
def _local():
    ray_tpu.init(local_mode=True, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_put_get_roundtrip():
    obj = {"a": np.arange(10), "b": [1, 2, 3], "c": "hello"}
    ref = ray_tpu.put(obj)
    out = ray_tpu.get(ref)
    assert out["b"] == [1, 2, 3]
    np.testing.assert_array_equal(out["a"], np.arange(10))


def test_task_submit_and_get():
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(1, 2)) == 3


def test_task_with_object_ref_args():
    @ray_tpu.remote
    def add(a, b):
        return a + b

    x = ray_tpu.put(10)
    y = add.remote(x, 5)
    z = add.remote(y, y)
    assert ray_tpu.get(z) == 30


def test_nested_tasks():
    @ray_tpu.remote
    def inner(x):
        return x * 2

    @ray_tpu.remote
    def outer(x):
        return ray_tpu.get(inner.remote(x)) + 1

    assert ray_tpu.get(outer.remote(5)) == 11


def test_num_returns():
    @ray_tpu.remote(num_returns=3)
    def three():
        return 1, 2, 3

    a, b, c = three.remote()
    assert ray_tpu.get([a, b, c]) == [1, 2, 3]


def test_task_error_propagates():
    @ray_tpu.remote
    def boom():
        raise ValueError("bad")

    with pytest.raises(rexc.TaskError) as ei:
        ray_tpu.get(boom.remote())
    assert "bad" in str(ei.value)


def test_get_timeout():
    @ray_tpu.remote
    def slow():
        time.sleep(5)
        return 1

    with pytest.raises(rexc.GetTimeoutError):
        ray_tpu.get(slow.remote(), timeout=0.1)


def test_wait():
    @ray_tpu.remote
    def sleepy(t):
        time.sleep(t)
        return t

    fast = sleepy.remote(0.01)
    slow = sleepy.remote(2.0)
    ready, pending = ray_tpu.wait([fast, slow], num_returns=1, timeout=1.0)
    assert ready == [fast]
    assert pending == [slow]


def test_actor_basic():
    @ray_tpu.remote
    class Counter:
        def __init__(self, start=0):
            self.n = start

        def incr(self, by=1):
            self.n += by
            return self.n

    c = Counter.remote(10)
    assert ray_tpu.get(c.incr.remote()) == 11
    assert ray_tpu.get(c.incr.remote(5)) == 16


def test_actor_ordering():
    @ray_tpu.remote
    class Appender:
        def __init__(self):
            self.items = []

        def add(self, x):
            self.items.append(x)
            return None

        def get_items(self):
            return self.items

    a = Appender.remote()
    for i in range(50):
        a.add.remote(i)
    assert ray_tpu.get(a.get_items.remote()) == list(range(50))


def test_named_actor():
    @ray_tpu.remote
    class Svc:
        def ping(self):
            return "pong"

    Svc.options(name="svc1").remote()
    h = ray_tpu.get_actor("svc1")
    assert ray_tpu.get(h.ping.remote()) == "pong"


def test_actor_method_error():
    @ray_tpu.remote
    class Bad:
        def boom(self):
            raise RuntimeError("actor bad")

    b = Bad.remote()
    with pytest.raises(rexc.TaskError):
        ray_tpu.get(b.boom.remote())


def test_kill_actor():
    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    a = A.remote()
    assert ray_tpu.get(a.ping.remote()) == 1
    ray_tpu.kill(a)
    with pytest.raises((rexc.ActorDiedError, rexc.TaskError)):
        ray_tpu.get(a.ping.remote())


def test_async_actor():
    import asyncio

    @ray_tpu.remote
    class AsyncActor:
        async def work(self, x):
            await asyncio.sleep(0.01)
            return x * 2

    a = AsyncActor.remote()
    refs = [a.work.remote(i) for i in range(10)]
    assert ray_tpu.get(refs) == [i * 2 for i in range(10)]


def test_actor_handle_in_task():
    @ray_tpu.remote
    class Store:
        def __init__(self):
            self.v = 0

        def set(self, v):
            self.v = v

        def get_v(self):
            return self.v

    @ray_tpu.remote
    def use(handle):
        ray_tpu.get(handle.set.remote(42))
        return ray_tpu.get(handle.get_v.remote())

    s = Store.remote()
    assert ray_tpu.get(use.remote(s)) == 42


def test_options_override():
    @ray_tpu.remote
    def f():
        return 1

    assert ray_tpu.get(f.options(num_cpus=2).remote()) == 1


def test_large_numpy_roundtrip():
    x = np.random.rand(1000, 1000)
    ref = ray_tpu.put(x)
    np.testing.assert_array_equal(ray_tpu.get(ref), x)


def test_cluster_resources():
    res = ray_tpu.cluster_resources()
    assert res.get("CPU", 0) > 0


def test_a_ref_collected_under_the_store_s_lock_does_not_wait_for_it():
    """The collector runs `ObjectRef.__del__` at whatever allocation it
    likes, and `_Store._event` allocates under the store's lock: the
    `delete` that `__del__` ends in then took the lock a second time, in
    the same thread, and waited for ever (PR 65's first whole run:
    `test_workflow.py::test_per_step_retry_with_backoff`, once; ROADMAP
    D12 (k)).  Here the store's `_events` collects on its `get`, which
    `_event` calls under the lock."""
    import threading

    from ray_tpu.core.local_engine import _Store

    store = _Store()

    class CollectsARef(dict):
        def get(self, key, default=None):
            store.delete(b"a ref nobody holds")     # `_ref_removed`
            return super().get(key, default)

    store._events = CollectsARef()
    waiter = threading.Thread(target=store.wait, args=(b"an object", 0.01),
                              daemon=True)
    waiter.start()
    waiter.join(timeout=10)
    assert not waiter.is_alive()
