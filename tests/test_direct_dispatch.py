"""Direct-dispatch cross-node task plane tests.

Fast unit tests cover the router's locality scoring, the node daemon's
function-digest (``need_fn``) admission protocol, the event-driven
dependency wait, and the completion reporter's direct-or-relay routing
— no cluster processes. The slow suite spins a real head + two node daemons
and proves the wire behavior: steady-state dispatch never relays
through the head, a dead direct dial falls back (or reroutes) and the
task still completes, locality places consumers on the node already
holding their argument bytes, functions ship once per (node, digest),
async-shipped pipelines overlap, and remote task errors arrive typed.
"""

import os
import pickle
import subprocess
import sys
import threading
import time
from collections import OrderedDict

import pytest

import ray_tpu
from ray_tpu._private.ids import ObjectID, TaskID
from ray_tpu._private.scheduler import TaskSpec
from ray_tpu.exceptions import GetTimeoutError


# --------------------------------------------------------------- fast units
def _bare_router():
    """A RemoteRouter skeleton with just the state _choose_node and
    _await_dep touch — no worker, no threads, no sockets."""
    from ray_tpu._private.remote_router import RemoteRouter

    r = RemoteRouter.__new__(RemoteRouter)
    r._lock = threading.Lock()
    r._inflight = {}
    r._assigned = {}
    r._draining_nodes = {}
    r._oid_owner = {}
    r._oid_sizes = {}
    r._task_node = {}
    r._task_target = {}
    r._done = {}
    r._done_cbs = {}
    r._failed = {}
    r._completed = set()
    r._dep_children = {}
    r.lineage = {}
    r.external = {}

    class _NoopDirectory:
        @staticmethod
        def publish_many(oid_bins):
            pass

    r.owner_directory = _NoopDirectory()
    return r


def _spec(args=()):
    tid = TaskID.from_random()
    return TaskSpec(task_id=tid, function=lambda: None, args=tuple(args),
                    kwargs={}, num_returns=1,
                    return_ids=[ObjectID(tid.binary() + (0).to_bytes(
                        4, "little"))], name="t", resources={"CPU": 1.0})


def _node(cid, backlog=0):
    return {"client_id": cid, "node_id": cid, "alive": True,
            "resources": {"CPU": 1.0}, "status": {"backlog": backlog},
            "peer_addr": None}


def _ref_owned_by(router, owner_cid, size):
    from ray_tpu._private.worker import ObjectRef

    oid = ObjectID.from_random()
    router._oid_owner[oid.binary()] = owner_cid
    router._oid_sizes[oid.binary()] = size
    return ObjectRef(oid, _add_ref=False)


def test_locality_prefers_node_holding_arg_bytes():
    """A task consuming a 10 MB node-resident block places on the owning
    node even when another node is (slightly) less loaded."""
    r = _bare_router()
    nodes = [_node("a", backlog=2), _node("b", backlog=0)]
    r.nodes = lambda refresh=False: nodes
    ref = _ref_owned_by(r, "a", 10 << 20)
    chosen = r._choose_node(_spec(args=(ref,)))
    assert chosen["client_id"] == "a"
    # Without the resident bytes, least-loaded wins.
    assert r._choose_node(_spec())["client_id"] == "b"


def test_locality_yields_to_load_past_slack():
    """Locality must not hotspot: past the load slack the least-loaded
    feasible node wins over the bytes-resident one."""
    from ray_tpu._private.config import GlobalConfig

    r = _bare_router()
    slack = GlobalConfig.locality_load_slack
    nodes = [_node("a", backlog=int(slack) + 5), _node("b", backlog=0)]
    r.nodes = lambda refresh=False: nodes
    ref = _ref_owned_by(r, "a", 10 << 20)
    assert r._choose_node(_spec(args=(ref,)))["client_id"] == "b"


def test_locality_pending_dep_colocates_chain():
    """A dep whose producer is still in flight counts as presence at the
    producer's (prospective) node, so pipelines colocate."""
    r = _bare_router()
    nodes = [_node("a"), _node("b")]
    r.nodes = lambda refresh=False: nodes
    from ray_tpu._private.worker import ObjectRef

    oid = ObjectID.from_random()
    r._task_target[oid.task_id()] = "b"  # producer assigned, not done
    ref = ObjectRef(oid, _add_ref=False)
    assert r._choose_node(_spec(args=(ref,)))["client_id"] == "b"


class _FakeStore:
    def __init__(self):
        self._ready = {}
        self._cbs = {}

    def on_ready(self, oid, cb):
        if oid in self._ready:
            cb()
        else:
            self._cbs.setdefault(oid, []).append(cb)

    def put_value(self, oid):
        self._ready[oid] = True
        for cb in self._cbs.pop(oid, []):
            cb()

    def is_ready(self, oid):
        return oid in self._ready

    def peek_error(self, oid):
        return None


def test_await_dep_event_driven_and_typed_timeout():
    """_await_dep wakes on the store's ready callback (no poll loop) and
    raises the typed GetTimeoutError on expiry."""
    r = _bare_router()

    class _W:
        pass

    r.worker = _W()
    r.worker.store = _FakeStore()
    oid = ObjectID.from_random()
    with pytest.raises(GetTimeoutError):
        r._await_dep(oid, timeout=0.15)
    # Produced from another thread: the wait returns promptly.
    t = threading.Timer(0.05, r.worker.store.put_value, args=(oid,))
    start = time.monotonic()
    t.start()
    r._await_dep(oid, timeout=5.0)
    assert time.monotonic() - start < 1.0, "wait was not event-driven"


def test_await_dep_raises_producer_failure():
    r = _bare_router()

    class _W:
        pass

    r.worker = _W()
    r.worker.store = _FakeStore()
    oid = ObjectID.from_random()
    tid = oid.task_id()
    boom = ValueError("producer failed")
    r._failed[tid] = boom
    ev = threading.Event()
    ev.set()
    r._done[tid] = ev
    r.lineage[tid] = object()
    with pytest.raises(ValueError, match="producer failed"):
        r._await_dep(oid, timeout=1.0)


def test_failure_cascade_is_iterative_not_recursive():
    """Failing the root of a deep async-shipped chain must fail every
    dependent without recursion (a 2000-link cascade would blow the
    stack if _fail recursed through _fail_downstream)."""
    r = _bare_router()

    class _W:
        pass

    errs = {}

    class _Store:
        @staticmethod
        def put_error(oid, exc):
            errs[oid.binary()] = exc

    r.worker = _W()
    r.worker.store = _Store()
    specs = [_spec() for _ in range(2000)]
    for s in specs:
        r.lineage[s.task_id] = s
    for up, down in zip(specs, specs[1:]):
        r._dep_children[up.task_id] = {down.task_id}
    r._fail(specs[0], ValueError("root failure"))
    assert len(r._failed) == 2000
    assert len(errs) == 2000


def _bare_daemon():
    """A NodeDaemon skeleton exposing only the fn-cache admission."""
    from collections import deque

    from ray_tpu._private.node_daemon import NodeDaemon

    d = NodeDaemon.__new__(NodeDaemon)
    d._draining = False
    d.drain_refusals = 0
    d._fn_cache = OrderedDict()
    d._fn_cache_bytes = 0
    d._fn_cache_cap = 64 << 20
    d._fn_lock = threading.Lock()
    d.fn_bytes_received = 0
    d._seen_tasks = set()
    d._seen_order = deque()
    d._seen_lock = threading.Lock()

    class _Intake:
        def __init__(self):
            self.submitted = []

        def submit(self, fn, *a):
            self.submitted.append(a)

    class _W:
        pass

    d.worker = _W()
    d.worker.store = _FakeStore()
    d._intake = _Intake()
    d._gated = _Intake()
    return d


def test_need_fn_protocol_round_trip():
    """Digest-only pushes are refused with ``need_fn`` until the bytes
    ship once; after that, digest-only pushes are accepted and the
    function bytes never cross again."""
    import hashlib

    import cloudpickle

    d = _bare_daemon()
    fn_bytes = cloudpickle.dumps(lambda x: x + 1)
    digest = hashlib.sha256(fn_bytes).digest()

    def payload(tid, **kw):
        return pickle.dumps(dict(
            {"task_id": tid, "return_ids": [], "num_returns": 0,
             "name": "t", "resources": {}, "max_retries": 0,
             "retry_exceptions": False, "args": [], "kwargs": {},
             "driver_id": "d"}, **kw))

    cold = payload(b"t" * 24, fn_digest=digest)
    assert d._accept_payload(cold) == "need_fn"
    assert not d._intake.submitted
    warm = payload(b"u" * 24, fn_digest=digest, fn=fn_bytes)
    assert d._accept_payload(warm) == "accepted"
    assert d.fn_bytes_received == len(fn_bytes)
    assert d._accept_payload(cold) == "accepted"
    assert d.fn_bytes_received == len(fn_bytes)  # shipped exactly once
    assert len(d._intake.submitted) == 2
    # Exactly-once admission: an ambiguous push retry (same task id)
    # is acknowledged without re-submitting the task.
    assert d._accept_payload(cold) == "accepted"
    assert len(d._intake.submitted) == 2
    assert d._load_fn(digest)(41) == 42


@pytest.mark.parametrize("route", ["dialable", "unreachable",
                                   "handler_error", "no_address"])
def test_report_loop_announces_only_relayed_items(route):
    """Completions reach a dialable driver direct, with no head call at
    all: the ownership directory's invariant (the head's object plane
    is flat in the number of objects). Completions that cannot go
    direct (driver not dialable, no address, or a per-message error in
    the driver's handler) take the head relay, and the only oids the
    relay announces are those of its streamed items too big to inline:
    the task_done relay records its own locations."""
    import collections
    import types

    from ray_tpu._private.node_daemon import NodeDaemon
    from ray_tpu._private.object_server import PeerUnreachableError

    calls = []

    def call_many(addr, msgs):
        if route == "unreachable":
            raise PeerUnreachableError(f"undialable {addr}")
        err = RuntimeError("handler") if route == "handler_error" else None
        return [err for _ in msgs]

    class _Head:
        _peers = types.SimpleNamespace(call_many=call_many)

        def object_announce_many(self, oids):
            calls.append(("announce", list(oids)))

        def task_done_many(self, driver_id, dones):
            calls.append(("task_done", [oids for oids, _ in dones]))

        def publish(self, channel, msg):
            calls.append(("publish", msg[1]))

    # What _report_loop touches, already stopped: one call drains the
    # queue and returns. The builders skip the store; an even yield is
    # too big to inline, so it carries its oid to announce.
    d = NodeDaemon.__new__(NodeDaemon)
    d._report_cv = threading.Condition()
    d._report_q = collections.deque()
    d._stop = threading.Event()
    d._stop.set()
    d.direct_report_batches = d.announce_fallback_oids = 0
    d.head = _Head()
    addr = None if route == "no_address" else ("127.0.0.1", 1)
    d._build_done = lambda payload, rids: (
        b"done", [o.binary() for o in rids], addr, "drv")
    d._build_item = lambda payload, idx, oid: (
        b"item:" + oid.binary(), None if idx % 2 else oid.binary(), addr,
        "drv")
    done_oids = [ObjectID.from_random() for _ in range(3)]
    item_oids = [ObjectID.from_random() for _ in range(4)]
    d._report_q.extend(("done", {}, [o]) for o in done_oids)
    d._report_q.extend(("item", {}, i, o) for i, o in enumerate(item_oids))
    d._report_loop()

    if route == "dialable":
        assert calls == [] and d.direct_report_batches == 1
        return
    large = [item_oids[0].binary(), item_oids[2].binary()]
    assert calls == [("announce", large),
                     ("task_done", [[o.binary()] for o in done_oids])] + [
        ("publish", b"item:" + o.binary()) for o in item_oids]
    assert d.announce_fallback_oids == 2 and d.direct_report_batches == 0


# ------------------------------------------------------------ slow cluster
pytestmark_slow = pytest.mark.slow


def _spawn_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_HEAD_CLIENT_TIMEOUT_S"] = "2.0"
    return env


def _spawn_head(tmp_path):
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.head_service",
         "--port", "0", "--state", str(tmp_path / "head_state.log")],
        stdout=subprocess.PIPE, text=True, env=_spawn_env())
    line = proc.stdout.readline()
    address = line.strip().rsplit(" ", 1)[-1]
    return proc, address


def _spawn_node(address, num_cpus, resources):
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.node_daemon",
         "--address", address, "--num-cpus", str(num_cpus),
         "--resources", resources, "--worker-mode", "thread"],
        stdout=subprocess.PIPE, text=True, env=_spawn_env())
    assert "joined" in proc.stdout.readline()
    return proc


def _wait_peer_addrs(worker, n, timeout=10.0):
    """Steady state begins once every node's direct server address has
    ridden a heartbeat into the directory."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        nodes = worker.head_client.node_list()
        if len(nodes) >= n and all(x.get("peer_addr") for x in nodes):
            return nodes
        time.sleep(0.1)
    pytest.fail("node peer addresses never published")


@pytest.fixture
def cluster(tmp_path):
    os.environ["RAY_TPU_HEAD_CLIENT_TIMEOUT_S"] = "2.0"
    ray_tpu.shutdown()
    head, address = _spawn_head(tmp_path)
    node1 = node2 = None
    try:
        node1 = _spawn_node(address, 1, '{"n1": 1}')
        node2 = _spawn_node(address, 1, '{"n2": 1}')
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=address)
        w = ray_tpu._private.worker.global_worker()
        _wait_peer_addrs(w, 2)
        yield {"address": address, "head": head, "node1": node1,
               "node2": node2, "worker": w}
    finally:
        ray_tpu.shutdown()
        for p in (node1, node2, head):
            if p is not None:
                p.kill()
                p.wait(timeout=5)
        os.environ.pop("RAY_TPU_HEAD_CLIENT_TIMEOUT_S", None)


@pytest.mark.slow
def test_steady_state_dispatch_never_relays(cluster):
    """Fan-out rides the direct plane end to end: zero head-relayed
    pushes, zero head-relayed completions, function bytes shipped at
    most once per node, small results inline (zero pulls)."""
    w = cluster["worker"]
    r = w.remote_router

    @ray_tpu.remote
    def noop(x):
        return x

    out = ray_tpu.get([noop.remote(i) for i in range(60)], timeout=120)
    assert out == list(range(60))
    assert r.direct_pushes >= 60
    assert r.relayed_pushes == 0
    assert r.direct_done_reports >= 60
    assert r.relayed_done_reports == 0
    assert r.inline_results >= 60
    # One function: its bytes ship once per node, digests thereafter.
    assert r.fn_payloads_with_bytes <= 2
    assert r.fn_payloads_digest_only >= 58


@pytest.mark.slow
def test_direct_dial_failure_falls_back_to_relay(cluster):
    """Poisoned direct plane (every peer dial fails): tasks fall back to
    head-relayed pushes and still complete."""
    from ray_tpu._private.object_server import PeerUnreachableError

    w = cluster["worker"]
    r = w.remote_router

    @ray_tpu.remote
    def noop(x):
        return x

    peers = w.head_client._peers

    def _dead(addr, msgs):
        raise PeerUnreachableError(f"poisoned {addr}")

    orig = peers.call_many
    peers.call_many = _dead
    try:
        out = ray_tpu.get([noop.remote(i) for i in range(10)], timeout=60)
        assert out == list(range(10))
        assert r.relayed_pushes >= 10
    finally:
        peers.call_many = orig


@pytest.mark.slow
def test_node_killed_between_accept_and_push_reroutes(cluster):
    """SIGKILL the target node after routing accepted the task but
    before its batch hits the wire: the push fails, the router excludes
    the dead node, and the task completes on the survivor."""
    w = cluster["worker"]
    r = w.remote_router
    nodes = w.head_client.node_list()
    node2_rec = next(n for n in nodes if "n2" in (n["resources"] or {}))

    @ray_tpu.remote
    def noop(x):
        return x

    # Stall the dispatcher's drain for node2 so the kill lands inside
    # the _accept -> push window deterministically.
    orig_push_group = r._push_group
    release = threading.Event()

    def _stalled(node, entries):
        if node["client_id"] == node2_rec["client_id"]:
            release.wait(10.0)
        orig_push_group(node, entries)

    r._push_group = _stalled
    try:
        from ray_tpu.util.scheduling_strategies import (
            NodeAffinitySchedulingStrategy,
        )

        ref = ray_tpu.remote(lambda: "survived").options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node2_rec["node_id"], soft=True)).remote()
        cluster["node2"].kill()
        cluster["node2"].wait(timeout=5)
        release.set()
        assert ray_tpu.get(ref, timeout=60) == "survived"
    finally:
        r._push_group = orig_push_group
        release.set()


@pytest.mark.slow
def test_locality_places_consumer_on_owning_node(cluster):
    """A task consuming a large node-resident arg runs ON the owning
    node (zero cross-node chunk pulls: the arg never leaves it, and the
    driver performs zero pull RPCs)."""
    w = cluster["worker"]
    hc = w.head_client

    @ray_tpu.remote(resources={"n1": 0.1})
    def produce():
        return b"x" * (8 << 20)  # 8 MB: far above the inline cap

    @ray_tpu.remote
    def consume(blob):
        from ray_tpu._private.worker import global_worker

        return (global_worker().node_id.hex(), len(blob))

    big = produce.remote()
    # Let the producer finish so the owner + size are in the directory.
    deadline = time.monotonic() + 30
    tid = big.object_id.task_id()
    while time.monotonic() < deadline:
        ev = w.remote_router._done.get(tid)
        if ev is not None and ev.is_set():
            break
        time.sleep(0.05)
    # Record every object the driver pulls from here on: the big arg
    # must never be among them (zero chunk-pull RPCs for it).
    pulled = []
    orig_pull = hc._peers.pull

    def _spy(addr, oid_bin):
        pulled.append(bytes(oid_bin))
        return orig_pull(addr, oid_bin)

    hc._peers.pull = _spy
    try:
        node_hex, nbytes = ray_tpu.get(consume.remote(big), timeout=60)
    finally:
        hc._peers.pull = orig_pull
    assert nbytes == 8 << 20
    owner = next(n for n in hc.node_list()
                 if "n1" in (n["resources"] or {}))
    assert node_hex == owner["node_id"], \
        "consumer was not placed on the node holding its argument"
    assert big.object_id.binary() not in pulled, \
        "driver chunk-pulled a node-resident argument"
    assert not w.store.is_ready(big.object_id), \
        "the 8 MB argument leaked onto the driver"


@pytest.mark.slow
def test_async_dependency_shipping_overlaps(cluster):
    """A dependent task ships to its node WHILE the producer is still
    running — the driver-side dependency barrier is gone."""
    w = cluster["worker"]
    r = w.remote_router

    @ray_tpu.remote(resources={"n1": 0.1})
    def slow_produce():
        import time as _t

        _t.sleep(1.5)
        return 7

    @ray_tpu.remote(resources={"n2": 0.1})
    def consume(x):
        return x * 6

    a = slow_produce.remote()
    b = consume.remote(a)
    b_tid = b.object_id.task_id()
    a_tid = a.object_id.task_id()
    deadline = time.monotonic() + 1.2  # well inside the producer's sleep
    shipped_early = False
    while time.monotonic() < deadline:
        with r._lock:
            shipped = b_tid in r._task_node
            a_done = r._done[a_tid].is_set() if a_tid in r._done else False
        if shipped and not a_done:
            shipped_early = True
            break
        time.sleep(0.02)
    assert shipped_early, \
        "consumer did not ship while its producer was still running"
    assert ray_tpu.get(b, timeout=60) == 42


@pytest.mark.slow
def test_remote_error_propagates_typed_and_fast(cluster):
    """A remote task error arrives with the task_done event as a typed
    exception — no pull-retry stall, and async-shipped dependents fail
    with the same root cause."""

    @ray_tpu.remote
    def boom():
        raise ValueError("remote kaboom")

    @ray_tpu.remote
    def after(x):
        return x

    ref = boom.remote()
    child = after.remote(ref)
    t0 = time.monotonic()
    with pytest.raises(ValueError, match="remote kaboom"):
        ray_tpu.get(ref, timeout=30)
    assert time.monotonic() - t0 < 5.0, "error propagation stalled"
    with pytest.raises(ValueError, match="remote kaboom"):
        ray_tpu.get(child, timeout=30)
