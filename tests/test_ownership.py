"""Ownership-based object directory tests: owner-direct resolve of
borrowed refs (no head directory entry anywhere), the
locate/subscribe/notify protocol, lease handoff via ``object_transfer``,
owner-death typed errors, and the head's steady-state observability
surface (reference model: ownership in the survey §2.2 — the submitting
worker owns its refs and answers location queries for them)."""

import os
import pickle
import subprocess
import sys
import threading
import time

import pytest

import ray_tpu
from ray_tpu.exceptions import GetTimeoutError, OwnerDiedError


@pytest.fixture
def head_proc():
    env = dict(os.environ)
    env["RAY_TPU_HEAD_CLIENT_TIMEOUT_S"] = "2.0"
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.head_service",
         "--port", "0"],
        stdout=subprocess.PIPE, text=True, env=env)
    line = proc.stdout.readline()
    address = line.strip().rsplit(" ", 1)[-1]
    yield address
    proc.kill()
    proc.wait(timeout=5)


@pytest.fixture
def attached(head_proc):
    ray_tpu.shutdown()
    worker = ray_tpu.init(num_cpus=2, worker_mode="thread",
                          address=head_proc, ignore_reinit_error=True)
    yield worker
    ray_tpu.shutdown()


_PEER = r"""
import sys, time
import cloudpickle
import ray_tpu

address = sys.argv[1]
ray_tpu.init(num_cpus=1, worker_mode="thread", address=address)
w = ray_tpu._private.worker.global_worker()

ref = ray_tpu.put({"secret": list(range(1000))})
# Deliberately NOT announced: the head's directory never sees this
# object — the pickled ref carries the owner's identity + address and
# consumers must resolve owner-direct.
w.kv_put(b"own/ref", cloudpickle.dumps(ref))
w.kv_put(b"own/oid", ref.object_id.hex().encode())
w.kv_put(b"own/client", w.head_client.client_id.encode())

late = ray_tpu.put("late-bird")
w.kv_put(b"own/late_oid", late.object_id.hex().encode())
w.kv_put(b"own/ready", b"1")

deadline = time.time() + 120
while time.time() < deadline:
    if w.kv_get(b"own/want_late") is not None:
        time.sleep(1.0)  # consumer is already inside its wait
        ray_tpu.announce_object(late)
        w.kv_put(b"own/late_announced", b"1")
        w.kv_del(b"own/want_late")
    if w.kv_get(b"own/done") is not None:
        break
    time.sleep(0.05)
ray_tpu.shutdown()
"""


@pytest.fixture
def peer_driver(head_proc):
    env = dict(os.environ)
    env["RAY_TPU_HEAD_CLIENT_TIMEOUT_S"] = "2.0"
    proc = subprocess.Popen([sys.executable, "-c", _PEER, head_proc],
                            env=env)
    yield head_proc, proc
    proc.kill()
    proc.wait(timeout=5)


def _wait_kv(worker, key, timeout=30):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = worker.kv_get(key)
        if v is not None:
            return v
        time.sleep(0.05)
    raise AssertionError(f"kv key {key} never appeared")


# ---------------------------------------------------------- owner-direct
def test_borrowed_ref_resolves_owner_direct(peer_driver, attached):
    """A pickled ref carries its owner; the borrower resolves through
    the OWNER's object server — the head's directory holds no entry for
    the object at any point."""
    _wait_kv(attached, b"own/ready")
    ref = pickle.loads(_wait_kv(attached, b"own/ref"))
    ob = ref.object_id.binary()
    owner = attached.borrowed_owner(ob)
    assert owner is not None, "deserialized ref carried no owner"
    assert owner[0] == _wait_kv(attached, b"own/client").decode()
    before = attached.head_client.head_stats()
    value = ray_tpu.get(ref, timeout=30)
    assert value == {"secret": list(range(1000))}
    res = attached.owner_resolver.counters()
    assert res["owner_locates"] >= 1
    assert res["owner_direct_pulls"] >= 1
    after = attached.head_client.head_stats()
    # The object never touched the head's directory or FT log.
    assert after["rpc_counts"].get("object_announce", 0) == \
        before["rpc_counts"].get("object_announce", 0)
    assert after["num_objects"] == before["num_objects"]
    attached.kv_put(b"own/done", b"1")


def test_owner_locate_protocol_states(peer_driver, attached):
    """The wire protocol itself: ready (store-held object, holder named
    for the relay fallback), unknown, pending-then-notify via a
    subscriber's object server."""
    from ray_tpu._private.ids import TaskID
    from ray_tpu._private.object_server import ObjectServer
    from ray_tpu._private.scheduler import TaskSpec

    _wait_kv(attached, b"own/ready")
    w = attached
    router = w.remote_router
    directory = router.owner_directory

    # ready: a local put object is served from this driver's server.
    local = ray_tpu.put([1, 2, 3])
    reply = directory.lookup(local.object_id.binary())
    assert reply["status"] == "ready"
    assert tuple(reply["addr"]) == w.head_client._object_server.address
    assert reply["holder"] == w.head_client.client_id

    # unknown: an id this owner never tracked.
    assert directory.lookup(b"\x00" * 28)["status"] == "unknown"

    # pending -> notify: a tracked in-flight task's return oid.
    tid = TaskID.from_random()
    spec = TaskSpec(task_id=tid, function=lambda: None, args=(),
                    kwargs={}, num_returns=1,
                    return_ids=[_return_oid(tid)], name="t",
                    resources={})
    with router._lock:
        router.lineage[tid] = spec
        router._done.setdefault(tid, threading.Event())
    ob = spec.return_ids[0].binary()
    notices = []
    got = threading.Event()

    def _on_notify(msg):
        notices.append(pickle.loads(bytes(msg[1])))
        got.set()

    sub_srv = ObjectServer(lambda _ob: b"", w.head_client.token)
    try:
        sub_srv.handlers["owner_notify"] = _on_notify
        reply = directory._on_owner_locate(
            ("owner_locate", ob, list(sub_srv.address)))
        assert reply["status"] == "pending"
        # Completion report lands (inline result): the subscriber is
        # notified with the fresh resolution, event-driven.
        done = pickle.dumps({
            "task_id": tid.binary(),
            "oid_bins": [ob],
            "node_client": w.head_client.client_id,
            "sizes": {}, "errs": {},
            "inline": {ob: w.serialization_context.serialize(
                "produced").to_bytes()},
        }, protocol=5)
        router._on_task_done(("task_done", done))
        assert got.wait(10), "owner_notify never arrived"
        assert notices[0]["oid"] == ob
        assert notices[0]["reply"]["status"] == "ready"
    finally:
        sub_srv.shutdown()
    attached.kv_put(b"own/done", b"1")


def _return_oid(tid):
    from ray_tpu._private.ids import ObjectID

    return ObjectID.for_task_return(tid, 0)


# ------------------------------------------------------------ owner death
def test_dead_owner_materializes_typed_error(attached):
    """Unreachable owner + no head fallback entry + membership says the
    owner is gone => typed OwnerDiedError, not an infinite poll."""
    from ray_tpu._private.ids import ObjectID, TaskID

    oid = ObjectID.for_task_return(TaskID.from_random(), 0)
    resolver = attached.owner_resolver
    # A port nothing listens on + a client id the head never saw.
    resolver.resolve(oid.binary(), ("127.0.0.1", 1), "driver-deadbeef",
                     deadline=time.monotonic() + 20)
    err = attached.store.peek_error(oid)
    assert isinstance(err, OwnerDiedError), f"got {err!r}"
    assert resolver.counters()["owner_died_errors"] >= 1


def test_unresolvable_foreign_ref_times_out_typed(attached):
    """An owner-less foreign ref nobody ever announces materializes a
    typed GetTimeoutError at the (shortened) wait bound."""
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.worker import ObjectRef

    ref = ObjectRef(ObjectID.from_hex("ab" * 28), _add_ref=False)
    t0 = time.monotonic()
    with pytest.raises(GetTimeoutError):
        ray_tpu.get(ref, timeout=2.0)
    assert time.monotonic() - t0 < 10.0


# ---------------------------------------------------------- lease handoff
def test_object_transfer_lease_handoff(peer_driver, attached):
    """``object_transfer`` records the HOLDER (not the announcer) in the
    head's fallback directory, so a consumer with a dead/unknown owner
    still resolves; transfers naming an unknown holder are refused."""
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.worker import ObjectRef

    _wait_kv(attached, b"own/ready")
    oid_hex = _wait_kv(attached, b"own/oid").decode()
    peer_client = _wait_kv(attached, b"own/client").decode()
    ob = ObjectID.from_hex(oid_hex).binary()
    # Simulated handoff: record the peer driver as the entry's holder.
    attached.head_client.object_transfer_many([(ob, peer_client)])
    # A ref with NO owner info now resolves through the head fallback.
    ref = ObjectRef(ObjectID.from_hex(oid_hex))
    assert ray_tpu.get(ref, timeout=30) == {"secret": list(range(1000))}
    # Unknown holder: refused, no directory entry created.
    ghost = os.urandom(24)
    attached.head_client.object_transfer_many([(ghost, "driver-ghost")])
    located = attached.head_client._request(("object_locate", ghost))
    assert located is None
    attached.kv_put(b"own/done", b"1")


def test_router_shutdown_transfers_owner_table(peer_driver, attached):
    """The lease handoff wire path end to end: a router shutdown
    transfers its location table in one flight (entries name live
    holders), visible in the head's directory."""
    from ray_tpu._private.ids import ObjectID

    _wait_kv(attached, b"own/ready")
    peer_client = _wait_kv(attached, b"own/client").decode()
    router = attached.remote_router
    fake_oid = os.urandom(24)
    with router._lock:
        router._oid_owner[fake_oid] = peer_client
    entries = router.owner_directory.snapshot_locations()
    assert (fake_oid, peer_client) in entries
    attached.head_client.object_transfer_many(entries)
    located = attached.head_client._request(("object_locate", fake_oid))
    assert located is not None and located["owner"] == peer_client
    attached.kv_put(b"own/done", b"1")


# ------------------------------------------- event-driven head fallback
def test_foreign_ref_announced_after_lookup_wakes_event_driven(
        peer_driver, attached):
    """The satellite fix for the old re-polling cross-driver pull: a
    foreign (owner-less) ref announced AFTER the get started resolves
    via the head's ``obj|`` directory subscription — a handful of head
    RPCs total, not one per 250 ms poll round."""
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.worker import ObjectRef

    _wait_kv(attached, b"own/ready")
    late_hex = _wait_kv(attached, b"own/late_oid").decode()
    ref = ObjectRef(ObjectID.from_hex(late_hex))  # no owner info

    result = {}

    def _get():
        result["value"] = ray_tpu.get(ref, timeout=30)

    t = threading.Thread(target=_get, daemon=True)
    t.start()
    time.sleep(0.5)  # the getter is inside its subscribed wait now
    before = attached.head_client.head_stats()["object_plane_rpcs"]
    attached.kv_put(b"own/want_late", b"1")
    _wait_kv(attached, b"own/late_announced")
    t.join(timeout=20)
    assert not t.is_alive(), "get never woke on the announce"
    assert result["value"] == "late-bird"
    after = attached.head_client.head_stats()["object_plane_rpcs"]
    # Announce (1) + the woken re-pull (locate + meta/chunks): single
    # digits — the old 4-RPCs-per-second poll loop would show dozens.
    assert after - before <= 8, (before, after)
    attached.kv_put(b"own/done", b"1")


# ------------------------------------------------------------- flatness
def test_fanout_through_a_node_leaves_the_head_object_plane_flat(head_proc):
    """A fan-out through a node daemon to a dialable driver: every
    completion goes direct to the owner, so the head's object-plane
    RPCs, its relayed completions and its state-log appends do not
    move with the number of objects (zero per 1k objects)."""
    node = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.node_daemon", "--address",
         head_proc, "--num-cpus", "2", "--worker-mode", "thread"],
        stdout=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    ray_tpu.shutdown()
    try:
        assert "joined" in node.stdout.readline()
        w = ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                         address=head_proc)
        deadline = time.monotonic() + 10
        while not all(n.get("peer_addr")
                      for n in w.head_client.node_list() or [{}]):
            assert time.monotonic() < deadline, "no peer address"
            time.sleep(0.1)
        sq = ray_tpu.remote(lambda x: x * x)
        # Warm (function shipped, direct dial open) before the baseline.
        assert ray_tpu.get([sq.remote(i) for i in range(4)],
                           timeout=60) == [0, 1, 4, 9]
        before = w.head_client.head_stats()
        direct0 = w.remote_router.direct_done_reports
        n = 300
        assert ray_tpu.get([sq.remote(i) for i in range(n)],
                           timeout=60) == [i * i for i in range(n)]
        after = w.head_client.head_stats()
        for kind in ("object_announce", "object_transfer_batch",
                     "task_done", "task_push"):
            assert (after["rpc_counts"].get(kind, 0)
                    == before["rpc_counts"].get(kind, 0)), kind
        for key in ("object_plane_rpcs", "log_appends"):
            assert after[key] == before[key], key
        assert w.remote_router.direct_done_reports - direct0 >= n
        assert w.remote_router.relayed_done_reports == 0
    finally:
        ray_tpu.shutdown()
        node.kill()
        node.wait(timeout=5)


# ----------------------------------------------------------- observability
def test_head_stats_and_state_surface(attached):
    stats = attached.head_client.head_stats()
    assert "rpc_counts" in stats and stats["rpc_total"] > 0
    assert "log_appends" in stats
    assert stats["clients_alive"] >= 1
    from ray_tpu.util.state import ownership_summary

    summary = ownership_summary()
    assert "owner" in summary and "resolver" in summary
    assert summary["head"]["rpc_total"] >= stats["rpc_total"]


def test_dashboard_api_head(attached):
    import json
    import urllib.request

    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    dash = start_dashboard(port=0)
    try:
        with urllib.request.urlopen(dash.url + "/api/head",
                                    timeout=10) as r:
            payload = json.loads(r.read())
        assert "rpc_counts" in payload["head"]
    finally:
        stop_dashboard()
