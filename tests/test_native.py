"""Native-layer tests: shm object store, mutable-object channels (including
cross-process), and the C++ ready queue (reference test model:
src/ray/object_manager/plasma tests + cluster_task_manager_test.cc)."""

import functools
import multiprocessing as mp
import os
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu._native import (
    NativeMutableChannel,
    NativeObjectStore,
    NativeTaskQueue,
    native_available,
)

pytestmark = pytest.mark.skipif(
    not native_available(), reason="no native toolchain")


@pytest.fixture
def store():
    s = NativeObjectStore.create(capacity=4 << 20, max_objects=256)
    yield s
    s.close()


def test_put_get_roundtrip(store):
    store.put(1, b"hello world")
    assert store.get(1) == b"hello world"
    assert store.contains(1)
    assert not store.contains(99)
    stats = store.stats()
    assert stats["num_objects"] == 1
    assert stats["used"] >= 11


def test_put_duplicate_and_delete(store):
    store.put(7, b"x")
    with pytest.raises(Exception):
        store.put(7, b"y")
    store.delete(7)
    assert not store.contains(7)
    store.put(7, b"z")  # tombstone slot reusable
    assert store.get(7) == b"z"


def test_zero_copy_view(store):
    store.put(3, bytes(range(10)))
    view = store.get_view(3)
    assert bytes(view) == bytes(range(10))


def test_mutable_object_versioning(store):
    store.mo_create(10, max_size=1024, num_readers=1)
    store.mo_write(10, b"v1")
    data, ver = store.mo_read(10, last_seen=0, max_size=1024)
    assert data == b"v1" and ver == 1
    # Same reader blocks for a new version.
    with pytest.raises(Exception):
        store.mo_read(10, last_seen=1, max_size=1024, timeout_s=0.05)
    store.mo_write(10, b"v2")
    data, ver = store.mo_read(10, last_seen=1, max_size=1024)
    assert data == b"v2" and ver == 2


def test_mutable_write_blocks_until_consumed(store):
    store.mo_create(11, max_size=64, num_readers=1)
    store.mo_write(11, b"a")
    # Second write must block until the reader consumes version 1.
    t0 = time.monotonic()
    results = {}

    def writer():
        store.mo_write(11, b"b", timeout_s=5)
        results["done"] = time.monotonic() - t0

    t = threading.Thread(target=writer)
    t.start()
    time.sleep(0.2)
    store.mo_read(11, last_seen=0, max_size=64)
    t.join(timeout=5)
    assert results["done"] >= 0.15


def test_native_channel_protocol(store):
    ch = NativeMutableChannel(store, max_size=4096, num_readers=2)
    ch.write({"x": 1})
    assert ch.read(0) == {"x": 1}
    assert ch.read(1) == {"x": 1}
    ch.write([1, 2, 3])
    assert ch.read(0) == [1, 2, 3]
    ch.close()
    from ray_tpu.exceptions import ChannelError

    # Close drains: reader 1 still gets the committed v2, then errors.
    assert ch.read(1, timeout=1) == [1, 2, 3]
    with pytest.raises(ChannelError):
        ch.read(1, timeout=1)


def _child_proc(name, result_q):
    s = NativeObjectStore.open(name)
    try:
        assert s.get(42) == b"from parent"
        data, ver = s.mo_read(50, last_seen=0, max_size=256, timeout_s=10)
        s.put(43, b"from child:" + data)
        result_q.put("ok")
    except Exception as e:  # noqa: BLE001
        result_q.put(f"err: {e!r}")
    finally:
        s.close()


def test_cross_process_store_and_mutable():
    s = NativeObjectStore.create(
        name=f"/rtn_test_{mp.current_process().pid}",
        capacity=1 << 20, max_objects=64)
    try:
        s.put(42, b"from parent")
        s.mo_create(50, max_size=256, num_readers=1)
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        p = ctx.Process(target=_child_proc, args=(s.name, q))
        p.start()
        time.sleep(0.3)
        s.mo_write(50, b"hello")
        assert q.get(timeout=30) == "ok"
        p.join(timeout=10)
        assert s.get(43) == b"from child:hello"
    finally:
        s.close()


def test_task_queue_topological_waves():
    # Diamond: 0 -> {1, 2} -> 3
    q = NativeTaskQueue(max_tasks=4, max_edges=4)
    for t in range(4):
        q.add_task(t)
    q.add_edge(0, 1)
    q.add_edge(0, 2)
    q.add_edge(1, 3)
    q.add_edge(2, 3)
    q.seal()
    w1 = q.pop_wave()
    assert w1 == [0]
    q.complete(w1)
    w2 = sorted(q.pop_wave())
    assert w2 == [1, 2]
    q.complete(w2)
    w3 = q.pop_wave()
    assert w3 == [3]
    q.complete(w3)
    assert q.num_done == 4
    assert q.pop_wave(timeout_s=0.05) == []


def test_task_queue_wide_graph_throughput():
    n = 5000
    q = NativeTaskQueue(max_tasks=n, max_edges=n)
    for t in range(n):
        q.add_task(t)
    for t in range(1, n):
        q.add_edge(0, t)  # star: one producer, n-1 consumers
    q.seal()
    assert q.pop_wave(max_tasks=10) == [0]
    q.complete([0])
    total = 0
    t0 = time.perf_counter()
    while total < n - 1:
        wave = q.pop_wave(max_tasks=4096, timeout_s=1.0)
        if not wave:
            break
        q.complete(wave)
        total += len(wave)
    dt = time.perf_counter() - t0
    assert total == n - 1
    assert dt < 2.0  # native propagation is micro-seconds per task


def test_arena_reclaims_deleted_objects(store):
    """Delete must return arena space for reuse (free-list allocator) —
    a bump-only arena would exhaust under staged-arg churn."""
    baseline = store.stats()["used"]
    for i in range(50):
        store.put(0xBEEF_0000 + i, b"z" * 1_000_000)
        store.delete(0xBEEF_0000 + i)
    assert store.stats()["used"] <= baseline + 1024
    # Differently-sized churn exercises split/coalesce paths.
    for i in range(50):
        store.put(0xBEEF_1000 + i, b"z" * (10_000 + 7 * i))
    for i in range(50):
        store.delete(0xBEEF_1000 + i)
    assert store.stats()["used"] <= baseline + 1024


def test_dyn_queue_direct():
    """DynQueue (the live scheduler's C++ ready-ring) exercised directly:
    alloc/commit/pop, dependency gating, completion, abort recycling."""
    from ray_tpu._native.store import NativeDynQueue

    dq = NativeDynQueue(max_tasks=64, max_edges=128)
    a = dq.alloc()
    b = dq.alloc()
    dq.add_dep(b, a)  # b waits on a
    dq.commit(a)
    dq.commit(b)
    popped = dq.pop(16, timeout_s=1.0)
    assert popped == [a]  # b is gated
    dq.complete(a)
    assert dq.pop(16, timeout_s=1.0) == [b]
    dq.complete(b)
    # Dep on an already-completed producer is satisfied immediately.
    c = dq.alloc()
    dq.add_dep(c, a)
    dq.commit(c)
    assert dq.pop(16, timeout_s=1.0) == [c]
    dq.complete(c)


def test_dyn_queue_abort_recycles_slot():
    from ray_tpu._native.store import NativeDynQueue

    dq = NativeDynQueue(max_tasks=4, max_edges=16)
    handles = [dq.alloc() for _ in range(4)]
    with pytest.raises(MemoryError):
        dq.alloc()  # table full
    dq.abort(handles[0])
    h = dq.alloc()  # the aborted slot is reusable
    # A stale edge against the aborted generation is satisfied (no hang).
    dq.add_dep(h, handles[0])
    dq.commit(h)
    assert dq.pop(8, timeout_s=1.0) == [h]
    dq.complete(h)
    for stale in handles[1:]:
        dq.abort(stale)


def test_dyn_queue_edge_capacity_overflow():
    from ray_tpu._native.store import NativeDynQueue

    dq = NativeDynQueue(max_tasks=32, max_edges=2)
    producers = [dq.alloc() for _ in range(3)]
    consumer = dq.alloc()
    dq.add_dep(consumer, producers[0])
    dq.add_dep(consumer, producers[1])
    with pytest.raises(MemoryError):
        dq.add_dep(consumer, producers[2])  # edge table full


_CLOSE_RACE = r"""
import sys, threading, time
from ray_tpu._native import NativeMutableChannel, NativeObjectStore
from ray_tpu.exceptions import ChannelError

store = NativeObjectStore.create(capacity=1 << 20, max_objects=64)
ch = NativeMutableChannel(store, max_size=1024)
if sys.argv[1] == "after":
    store.close()
    ch.destroy()  # a worker retired after its runtime shut down
    for call in (lambda: ch.read(timeout=0.1), lambda: store.put(1, b"x")):
        try:
            call()
        except Exception as exc:
            print(type(exc).__name__, exc)
else:
    def read():
        try:
            ch.read(timeout=0.5)
        except ChannelError as exc:
            print(type(exc).__name__)

    t = threading.Thread(target=read)
    t.start()
    while store._calls == 0:
        time.sleep(0.001)
    store.close()
    assert store._h is not None, "unmapped under a blocked reader"
    # The next runtime's store, under the same name, outlives the unmap.
    again = NativeObjectStore.create(name=store.name, capacity=1 << 20,
                                     max_objects=64)
    t.join()
    assert store._h is None, "never unmapped"
    NativeObjectStore.open(store.name).close()
    again.close()
"""


@pytest.mark.parametrize("when, expect", [
    ("after", "ChannelError channel destroyed\n"
              "NativeError native put failed: closed"),
    ("during", "ChannelTimeoutError"),
], ids=["after", "during"])
def test_store_close_races_calls_on_other_threads(when, expect):
    """A call after ``close`` raises (it used to hand the library a
    freed handle and crash the process: a chip worker retired by its
    task's thread after the fixture shut the runtime down), and a
    reader blocked in ``mo_read`` keeps the segment mapped until it
    returns, without unlinking a store created since under its name."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", _CLOSE_RACE, when],
                         cwd=root, capture_output=True, text=True,
                         timeout=60)
    assert out.returncode == 0, (out.returncode, out.stderr[-2000:])
    assert out.stdout.strip() == expect


def _run_chain(sched, n, step):
    """Submit ``n`` tasks, each taking the one before's result, and
    return the last one's value."""
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.scheduler import TaskSpec
    from ray_tpu._private.worker import ObjectRef, global_worker

    w = global_worker()
    prev_ref = None
    for i in range(n):
        task_id = w.next_task_id()
        rid = ObjectID.for_task_return(task_id, 0)
        sched.submit(TaskSpec(
            task_id=task_id, function=functools.partial(step, i),
            args=(prev_ref,) if prev_ref is not None else (0,),
            kwargs={}, num_returns=1, return_ids=[rid],
            name=f"chain{i}", resources={"CPU": 1.0}))
        prev_ref = ObjectRef(rid)
    value = w.store.get(prev_ref.object_id, timeout=30)
    return w.serialization_context.deserialize(value)


def test_scheduler_without_native_queue_runs_python_tracker(
        ray_start_regular, monkeypatch):
    """Where the native layer cannot be imported, the scheduler tracks
    dependencies in Python: a dependent chain still runs in order."""
    import ray_tpu._native.store as native_store
    from ray_tpu._private.scheduler import LocalScheduler, ResourcePool
    from ray_tpu._private.worker import global_worker

    def _no_native(*a, **kw):
        raise ImportError("native layer unavailable")

    monkeypatch.setattr(native_store, "NativeDynQueue", _no_native)
    sched = LocalScheduler(global_worker().store,
                           ResourcePool({"CPU": 2.0}), num_workers=2,
                           lineage={})
    order = []

    def step(i, x):
        order.append(i)
        return x + 1

    try:
        assert sched._dq is None
        assert _run_chain(sched, 40, step) == 40
        assert order == list(range(40))
    finally:
        sched.shutdown()


def test_scheduler_native_queue_full_falls_back(ray_start_regular):
    """A full native ring degrades to the python dependency path: chains
    still execute correctly past the ring capacity."""
    from ray_tpu._native.store import NativeDynQueue
    from ray_tpu._private.scheduler import LocalScheduler, ResourcePool
    from ray_tpu._private.worker import global_worker

    sched = LocalScheduler(global_worker().store,
                           ResourcePool({"CPU": 2.0}), num_workers=2,
                           lineage={})
    try:
        # Tiny ring to force MemoryError fallbacks mid-traffic.
        sched._dq = NativeDynQueue(max_tasks=8, max_edges=8)
        # 5x the ring capacity.
        assert _run_chain(sched, 40, lambda i, x: x + 1) == 40
    finally:
        sched.shutdown()
