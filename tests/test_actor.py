"""Actor semantics (reference: python/ray/tests/test_actor.py role)."""

import asyncio
import threading
import time

import pytest

import ray_tpu
from ray_tpu.exceptions import ActorDiedError, RayActorError


@ray_tpu.remote
class Counter:
    def __init__(self, start=0):
        self.n = start

    def inc(self, by=1):
        self.n += by
        return self.n

    def read(self):
        return self.n


def test_actor_basic(ray_start_regular):
    c = Counter.remote()
    assert ray_tpu.get(c.inc.remote()) == 1
    assert ray_tpu.get(c.inc.remote(5)) == 6
    assert ray_tpu.get(c.read.remote()) == 6


def test_actor_init_args(ray_start_regular):
    c = Counter.remote(start=100)
    assert ray_tpu.get(c.read.remote()) == 100


def test_actor_method_ordering(ray_start_regular):
    c = Counter.remote()
    refs = [c.inc.remote() for _ in range(100)]
    assert ray_tpu.get(refs[-1]) == 100
    assert ray_tpu.get(refs) == list(range(1, 101))


def test_actor_method_error_does_not_kill(ray_start_regular):
    @ray_tpu.remote
    class Fragile:
        def bad(self):
            raise ValueError("oops")

        def good(self):
            return "fine"

    a = Fragile.remote()
    with pytest.raises(ValueError):
        ray_tpu.get(a.bad.remote())
    assert ray_tpu.get(a.good.remote()) == "fine"


def test_actor_init_error(ray_start_regular):
    @ray_tpu.remote
    class Broken:
        def __init__(self):
            raise RuntimeError("cannot construct")

        def m(self):
            return 1

    a = Broken.remote()
    with pytest.raises(RayActorError):
        ray_tpu.get(a.m.remote(), timeout=10)


def test_kill_actor(ray_start_regular):
    c = Counter.remote()
    assert ray_tpu.get(c.inc.remote()) == 1
    ray_tpu.kill(c)
    time.sleep(0.1)
    with pytest.raises(ActorDiedError):
        ray_tpu.get(c.inc.remote(), timeout=10)


def test_actor_restart(ray_start_regular):
    @ray_tpu.remote(max_restarts=1)
    class Restartable:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    a = Restartable.remote()
    assert ray_tpu.get(a.inc.remote()) == 1
    assert ray_tpu.get(a.inc.remote()) == 2
    ray_tpu.kill(a, no_restart=False)
    # Restarted with fresh state.
    assert ray_tpu.get(a.inc.remote(), timeout=10) == 1


def test_named_actor(ray_start_regular):
    Counter.options(name="global_counter").remote(start=7)
    h = ray_tpu.get_actor("global_counter")
    assert ray_tpu.get(h.read.remote()) == 7
    with pytest.raises(ValueError):
        Counter.options(name="global_counter").remote()
    h2 = Counter.options(name="global_counter", get_if_exists=True).remote()
    assert ray_tpu.get(h2.read.remote()) == 7


def test_actor_handle_pass_to_task(ray_start_regular):
    @ray_tpu.remote
    def use(counter):
        return ray_tpu.get(counter.inc.remote())

    c = Counter.remote()
    assert ray_tpu.get(use.remote(c)) == 1
    assert ray_tpu.get(c.read.remote()) == 1


def test_async_actor(ray_start_regular):
    @ray_tpu.remote
    class AsyncActor:
        def __init__(self):
            self.hits = 0

        async def work(self, delay):
            await asyncio.sleep(delay)
            self.hits += 1
            return self.hits

    a = AsyncActor.remote()
    # Submit overlapping calls; they interleave on the actor's event loop.
    refs = [a.work.remote(0.05) for _ in range(10)]
    results = ray_tpu.get(refs, timeout=30)
    assert sorted(results) == list(range(1, 11))


def test_threaded_actor(ray_start_regular):
    @ray_tpu.remote(max_concurrency=4)
    class Concurrent:
        def __init__(self):
            self.lock = threading.Lock()
            self.in_flight = self.most_in_flight = 0

        def slow(self):
            with self.lock:
                self.in_flight += 1
                self.most_in_flight = max(self.most_in_flight,
                                          self.in_flight)
            time.sleep(0.2)
            with self.lock:
                self.in_flight -= 1
            return 1

        def most(self):
            return self.most_in_flight

    a = Concurrent.remote()
    refs = [a.slow.remote() for _ in range(4)]
    assert sum(ray_tpu.get(refs, timeout=30)) == 4
    # The calls overlapped: sequential execution never has two in flight.
    assert ray_tpu.get(a.most.remote(), timeout=30) >= 2


def test_method_num_returns(ray_start_regular):
    @ray_tpu.remote
    class Multi:
        @ray_tpu.method(num_returns=2)
        def pair(self):
            return "a", "b"

    m = Multi.remote()
    a, b = m.pair.remote()
    assert ray_tpu.get([a, b]) == ["a", "b"]
