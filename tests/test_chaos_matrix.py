"""Chaos × load matrix (ROADMAP item 5; reference model: upstream Ray's
release/nightly_tests/chaos_test NodeKiller tier, productized).

Fault axes: wire faults (frame drop / delay / dup / corrupt, connection
reset — seeded injection in ``_private/transport.py`` behind
``RAY_TPU_CHAOS``), process kills (workers / node daemons via the
seeded NodeKiller), and overload (priority admission + load shedding).
Workload axes: raw transport traffic, task fan-out, serve streams, LLM
decode, workflows, data shuffle.

Every cell asserts the same three invariants: failures surface as
TYPED errors (never hangs), the system RECOVERS (retries/lineage/
replica replacement complete the workload), and nothing LEAKS (KV
blocks, router in-flight slots, store refs return to baseline).

The deterministic fast slice below is NOT slow-marked — it runs inside
tier-1 and `make chaos-gate`. The full multi-process sweep cells at the
bottom are additionally slow-marked (full-run CI only).
"""

import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

import ray_tpu
from ray_tpu._private import transport
from ray_tpu._private.config import GlobalConfig
from ray_tpu.exceptions import ObjectLostError, RequestSheddedError
from ray_tpu.util import chaos

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _chaos_clean():
    """Every cell starts and ends with injection OFF and default flags."""
    chaos.uninstall()
    yield
    chaos.uninstall()
    GlobalConfig.reset()


# --------------------------------------------------------------------------
# Wire-fault plumbing: inertness, determinism, exact per-site accounting.
# --------------------------------------------------------------------------
TOKEN = "0123456789abcdef"


def _conn_pair(site_srv="srv", site_cli="cli"):
    lis = transport.TokenListener("127.0.0.1", 0, TOKEN, site=site_srv)
    out = {}

    def srv():
        out["conn"] = lis.accept()

    t = threading.Thread(target=srv, daemon=True)
    t.start()
    cli = transport.connect("127.0.0.1", lis.address[1], TOKEN,
                            site=site_cli)
    t.join(5)
    return lis, out["conn"], cli


def _drain(conn, timeout=0.5):
    conn._sock.settimeout(timeout)
    got = []
    try:
        while True:
            got.append(conn.recv())
    except Exception:  # noqa: BLE001 — timeout/EOF ends the drain
        pass
    return got


def test_chaos_off_is_provably_inert():
    """With RAY_TPU_CHAOS unset the injection slot is None — the send
    path is one global load + branch — and nothing ever counts."""
    assert transport._CHAOS is None
    assert not chaos.active()
    lis, srv, cli = _conn_pair()
    try:
        for i in range(50):
            cli.send(("m", i))
        cli.send_many([("b", i) for i in range(50)])
        got = _drain(srv)
        assert len(got) == 100  # every frame arrived exactly once
        assert chaos.wire_counters() == {}
        snap = chaos.snapshot()
        assert snap["active"] is False and snap["wire_totals"] == {}
    finally:
        cli.close(), srv.close(), lis.close()


def test_chaos_env_parsing_strict():
    assert chaos.ChaosConfig.from_env("") is None
    assert chaos.ChaosConfig.from_env("off") is None
    cfg = chaos.ChaosConfig.from_env(
        '{"seed": 7, "drop": 0.1, "sites": ["peer"]}')
    assert cfg.seed == 7 and cfg.drop == 0.1 and cfg.sites == ("peer",)
    with pytest.raises(ValueError):
        chaos.ChaosConfig.from_env('{"dorp": 0.1}')  # typo must be loud
    with pytest.raises(ValueError):
        chaos.ChaosConfig.from_env('[1, 2]')


def test_seeded_decisions_replay_exactly():
    cfg = dict(drop=0.2, delay=0.05, dup=0.1, corrupt=0.05, reset=0.02)
    a = chaos.ChaosInjector(chaos.ChaosConfig(seed=11, **cfg))
    b = chaos.ChaosInjector(chaos.ChaosConfig(seed=11, **cfg))
    c = chaos.ChaosInjector(chaos.ChaosConfig(seed=12, **cfg))
    da = [a.decide("s") for _ in range(500)]
    db = [b.decide("s") for _ in range(500)]
    dc = [c.decide("s") for _ in range(500)]
    assert da == db, "same seed must replay the same fault schedule"
    assert da != dc, "different seed must differ"
    assert a.counters == b.counters


def test_frame_drop_counted_exactly_and_site_scoped():
    lis, srv, cli = _conn_pair()
    inj = chaos.install(chaos.ChaosConfig(seed=3, drop=0.5,
                                          sites=("cli",)))
    try:
        n = 40
        for i in range(n):
            cli.send(("m", i))
        srv.send(("server-side", 0))  # site "srv": must NOT be faulted
        got = _drain(srv)
        dropped = inj.counters["cli"]["drop"]
        assert dropped > 0
        assert len(got) == n - dropped, "every loss is an accounted drop"
        assert "srv" not in inj.counters, "site scoping leaked"
        assert _drain(cli) == [("server-side", 0)]
    finally:
        cli.close(), srv.close(), lis.close()


def test_frame_dup_and_delay_counted():
    lis, srv, cli = _conn_pair()
    inj = chaos.install(chaos.ChaosConfig(seed=5, dup=1.0, sites=("cli",)))
    try:
        cli.send(("m", 1))
        got = _drain(srv)
        assert got == [("m", 1), ("m", 1)], "dup must deliver twice"
        assert inj.counters["cli"]["dup"] == 1
        # Delay: 100% at 30ms over 3 frames >= 90ms wall.
        chaos.install(chaos.ChaosConfig(seed=5, delay=1.0, delay_ms=30,
                                        sites=("cli",)))
        t0 = time.perf_counter()
        for i in range(3):
            cli.send(("d", i))
        assert time.perf_counter() - t0 >= 0.09
        assert len(_drain(srv)) == 3  # delayed, not lost
    finally:
        cli.close(), srv.close(), lis.close()


def test_frame_corrupt_fails_receiver_typed():
    """A corrupted frame must fail the receiver's decode (typed, not a
    hang) — the connection dies like a real poisoned stream."""
    lis, srv, cli = _conn_pair()
    inj = chaos.install(chaos.ChaosConfig(seed=2, corrupt=1.0,
                                          sites=("cli",)))
    try:
        cli.send({"k": list(range(64))})
        srv._sock.settimeout(2.0)
        with pytest.raises(Exception) as ei:
            srv.recv()
        assert not isinstance(ei.value, socket.timeout), \
            "corruption must surface an error, not a stall"
        assert inj.counters["cli"]["corrupt"] == 1
    finally:
        cli.close(), srv.close(), lis.close()


def test_connection_reset_typed_at_sender():
    lis, srv, cli = _conn_pair()
    inj = chaos.install(chaos.ChaosConfig(seed=2, reset=1.0,
                                          sites=("cli",)))
    try:
        with pytest.raises(ConnectionResetError):
            cli.send(("m", 1))
        assert inj.counters["cli"]["reset"] == 1
        # The peer observes EOF — a real teardown, not a zombie socket.
        srv._sock.settimeout(2.0)
        with pytest.raises((EOFError, OSError)):
            srv.recv()
    finally:
        cli.close(), srv.close(), lis.close()


# --------------------------------------------------------------------------
# Satellite: handshake/accept timeout — a connect-then-hang client must
# not wedge the accept loop.
# --------------------------------------------------------------------------
def test_connect_then_hang_client_does_not_wedge_accept():
    GlobalConfig.set("transport_handshake_timeout_s", 0.5)
    lis = transport.TokenListener("127.0.0.1", 0, TOKEN, site="srv")
    accepted = []

    def server():
        try:
            accepted.append(lis.accept())
        except OSError:
            pass

    t = threading.Thread(target=server, daemon=True)
    t.start()
    # A half-open peer: TCP connect, then total silence (never answers
    # the HMAC challenge).
    hang = socket.create_connection(("127.0.0.1", lis.address[1]),
                                    timeout=5)
    time.sleep(0.05)  # the hang connection reaches the accept pump first
    try:
        t0 = time.perf_counter()
        good = transport.connect("127.0.0.1", lis.address[1], TOKEN)
        t.join(5)
        wall = time.perf_counter() - t0
        assert accepted, "well-behaved peer was never admitted"
        assert wall < 2.0, f"hang client stalled accept for {wall:.1f}s"
        good.send(("ping", 1))
        assert accepted[0].recv() == ("ping", 1)
        # The stalled peer is cut off at the handshake timeout, not
        # parked forever: its socket sees EOF shortly.
        hang.settimeout(2.0)
        assert hang.recv(64 * 1024) is not None  # server's challenge
        assert hang.recv(1024) == b"", "stalled peer was not dropped"
        good.close()
        accepted[0].close()
    finally:
        hang.close()
        lis.close()


# --------------------------------------------------------------------------
# Satellite: bounded, jittered peer-pull reconnect + typed ObjectLostError.
# --------------------------------------------------------------------------
def test_peer_pull_bounded_retry_then_gives_up():
    """A peer that resets every connection exhausts the attempt budget
    (with backoff) instead of retrying forever; counters record it."""
    from ray_tpu._private.object_server import PeerPool

    GlobalConfig.set("peer_pull_attempts", 3)
    GlobalConfig.set("peer_pull_backoff_s", 0.02)
    lis = transport.TokenListener("127.0.0.1", 0, TOKEN, site="object")

    def evil_server():  # handshake OK, then slam the door
        while True:
            try:
                conn = lis.accept()
            except OSError:
                return
            conn.close()

    t = threading.Thread(target=evil_server, daemon=True)
    t.start()
    pool = PeerPool(TOKEN)
    try:
        t0 = time.perf_counter()
        assert pool.pull_retrying(
            ("127.0.0.1", lis.address[1]), b"x" * 20) is None
        wall = time.perf_counter() - t0
        assert pool.pull_retries == 2      # attempts - 1 backoffs
        assert pool.pull_exhausted == 1
        assert wall >= 0.02 * (1 + 2) * 0.5  # jitter floor of the waits
        assert wall < 10.0
    finally:
        pool.close()
        lis.close()


def test_peer_pull_absent_answer_does_not_retry():
    """An authoritative "I don't serve that object" is not a transport
    fault — no retries, no backoff stall."""
    from ray_tpu._private.object_server import ObjectServer, PeerPool

    def provider(oid):
        raise KeyError(oid)  # owns nothing

    server = ObjectServer(provider, TOKEN)
    pool = PeerPool(TOKEN)
    try:
        t0 = time.perf_counter()
        assert pool.pull_retrying(
            ("127.0.0.1", server.address[1]), b"y" * 20) is None
        assert time.perf_counter() - t0 < 1.0
        assert pool.pull_retries == 0 and pool.pull_exhausted == 0
    finally:
        pool.close()
        server.shutdown()


def test_ensure_local_materializes_object_lost_when_unrecoverable():
    """A COMPLETED object whose bytes no node serves and whose lineage
    is gone must become a typed ObjectLostError within the pull TTL —
    never an infinite chaos-induced retry loop."""
    from ray_tpu._private.ids import JobID, ObjectID, TaskID
    from ray_tpu._private.object_store import ObjectStore
    from ray_tpu._private.remote_router import RemoteRouter

    GlobalConfig.set("external_pull_ttl_s", 0.4)

    class _Head:
        def object_pull(self, oid_bin):
            return None  # nobody serves the bytes anymore

    class _Worker:
        pass

    router = object.__new__(RemoteRouter)
    router.worker = _Worker()
    router.worker.store = ObjectStore(spill_dir="/tmp/ray_tpu_unused")
    router.head = _Head()
    router._lock = threading.Lock()
    router._done = {}
    router._failed = {}
    router._oid_owner = {}
    router._prefetching = set()
    router._stop = threading.Event()
    router.external = set()
    router.lineage = {}

    tid = TaskID.for_driver(JobID.from_int(7))
    oid = ObjectID.for_task_return(tid, 0)
    ev = threading.Event()
    ev.set()  # the task completed; only its bytes are gone
    router._done[tid] = ev

    t0 = time.perf_counter()
    router.ensure_local(oid, timeout=10.0)
    wall = time.perf_counter() - t0
    assert wall < 5.0, "loss was not bounded by the pull TTL"
    err = router.worker.store.peek_error(oid)
    assert isinstance(err, ObjectLostError), f"got {err!r}"


# --------------------------------------------------------------------------
# Overload axis: priority admission + load shedding (LLM engine tier).
# --------------------------------------------------------------------------
def _tiny_engine(**over):
    import jax.numpy as jnp

    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.models import TransformerConfig

    mcfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=1,
                             n_heads=4, n_kv_heads=2, d_ff=64,
                             dtype=jnp.float32)
    kw = dict(model=mcfg, num_blocks=64, block_size=4, max_num_seqs=4,
              prefill_token_budget=64, max_queued_requests=2)
    kw.update(over)
    return InferenceEngine(EngineConfig(**kw))


def test_llm_waitqueue_sheds_lowest_class_typed_no_leaks():
    engine = _tiny_engine()
    try:
        # Hold the step lock so the loop cannot drain the waitqueue:
        # shedding decisions below are fully deterministic.
        with engine._lock:
            keep0 = engine.submit([1, 2], max_new_tokens=2, priority=0)
            low = engine.submit([3, 4], max_new_tokens=2, priority=3)
            # Queue full; a class-2 arrival outranks the waiting class-3:
            # the class-3 request is EVICTED with a typed shed error.
            keep2 = engine.submit([5, 6], max_new_tokens=2, priority=2)
            kind, err = low.output_queue.get(timeout=1)
            assert kind == "__error__"
            assert isinstance(err, RequestSheddedError)
            assert err.priority == 3 and low.status == "SHED"
            # Queue full of classes {0, 2}; a class-2 arrival does NOT
            # outrank its own class — the NEWCOMER sheds.
            with pytest.raises(RequestSheddedError) as ei:
                engine.submit([7, 8], max_new_tokens=2, priority=2)
            assert ei.value.priority == 2
        # Released: the surviving requests complete normally (shed-by-
        # policy is separate from failure — nothing else was touched).
        assert engine.wait_idle(30)
        assert len(keep0.out_tokens) == 2 and keep0.status == "FINISHED"
        assert len(keep2.out_tokens) == 2 and keep2.status == "FINISHED"
        st = engine.stats()
        assert st["shed_requests"] == 2
        assert st["shed_by_class"] == {3: 1, 2: 1}
        assert st["blocks_in_use"] == 0, "shed/finish leaked KV blocks"
        assert engine.scheduler.queue_depth() == 0
    finally:
        engine.shutdown()


def test_llm_overload_storm_degrades_by_policy():
    """A deterministic submit storm over a 3-slot waitqueue (the step
    lock held, so no drain interleaves): 12 class-3 arrivals then 12
    class-0 arrivals. The policy outcome is exact — EVERY class-3
    request sheds (refused or evicted by the better class), exactly 3
    class-0 requests hold queue slots and complete, the class-0
    overflow sheds against its own class, and nothing hangs, fails
    untyped, or leaks blocks."""
    engine = _tiny_engine(max_queued_requests=3, max_num_seqs=2)
    survivors, refused = [], []
    try:
        with engine._lock:  # freeze the drain: decisions are exact
            for i in range(12):
                try:
                    engine.submit([i + 1, i + 2], max_new_tokens=2,
                                  priority=3)
                except RequestSheddedError as e:
                    refused.append(e.priority)
            for i in range(12):
                try:
                    survivors.append(engine.submit(
                        [i + 1, i + 2], max_new_tokens=2, priority=0))
                except RequestSheddedError as e:
                    refused.append(e.priority)
            assert refused == [3] * 9 + [0] * 9
            assert len(survivors) == 3
        assert engine.wait_idle(60)
        for req in survivors:
            assert req.status == "FINISHED" and len(req.out_tokens) == 2
        st = engine.stats()
        # 9 class-3 refused + 3 class-3 evicted by class-0 arrivals;
        # 9 class-0 refused against their own class.
        assert st["shed_by_class"] == {3: 12, 0: 9}
        assert st["shed_requests"] == 21
        assert st["blocks_in_use"] == 0, "shed storm leaked KV blocks"
        assert engine.scheduler.queue_depth() == 0
    finally:
        engine.shutdown()


def test_shed_error_stays_typed_across_task_error_wrapping():
    """An engine-tier shed inside a process-backed replica crosses the
    wire wrapped in RayTaskError; as_instanceof_cause must hand the
    client back the exact RequestSheddedError (priority/retry_after_s
    intact) so `except RequestSheddedError` retry loops keep working."""
    import pickle

    from ray_tpu.exceptions import RayTaskError

    shed = RequestSheddedError(priority=2, retry_after_s=0.7)
    wrapped = RayTaskError.from_exception("llm_call", shed)
    surfaced = wrapped.as_instanceof_cause()
    assert isinstance(surfaced, RequestSheddedError)
    assert surfaced.priority == 2 and surfaced.retry_after_s == 0.7
    # And after a real pickle round trip (the cross-process path).
    rewrapped = pickle.loads(pickle.dumps(wrapped))
    surfaced = rewrapped.as_instanceof_cause()
    assert isinstance(surfaced, RequestSheddedError)
    assert surfaced.priority == 2


def test_preempted_request_is_never_the_shed_victim():
    """A recompute-preempted request is mid-generation (its consumer
    holds streamed tokens): waitqueue eviction must skip it and shed
    the NEWCOMER instead, even when the preempted request's class is
    worse."""
    import jax.numpy as jnp

    from ray_tpu.llm.kv_cache import PagedKVCache
    from ray_tpu.llm.scheduler import Request, Scheduler
    from ray_tpu.models import TransformerConfig

    mcfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=1,
                             n_heads=4, n_kv_heads=2, d_ff=64,
                             dtype=jnp.float32)
    cache = PagedKVCache(mcfg, num_blocks=9, block_size=4)
    sched = Scheduler(cache, max_queued_requests=1)
    victim_shaped = Request([1, 2], 4, priority=3)
    victim_shaped.preemptions = 1  # recompute-preempted, re-queued
    sched.waiting.append(victim_shaped)
    with pytest.raises(RequestSheddedError) as ei:
        sched.submit(Request([3, 4], 4, priority=0))
    assert ei.value.priority == 0  # the newcomer shed, not the preempted
    assert list(sched.waiting) == [victim_shaped]


# --------------------------------------------------------------------------
# Overload axis: serve-tier admission (router thresholds, HTTP 503).
# --------------------------------------------------------------------------
def test_replica_set_nested_class_thresholds():
    from ray_tpu.serve.router import ReplicaSet

    class R:
        pass

    rs = ReplicaSet()
    rs.update([R(), R()])
    rs.configure_admission(4)
    held = [rs.choose(priority=0)[0] for _ in range(4)]
    with pytest.raises(RequestSheddedError):
        rs.choose(priority=0)  # full cap reached even for class 0
    for k in held[:3]:
        rs.release(k)
    # 1 ongoing: class-3 limit is int(4 * 0.25) = 1 → sheds; class 1
    # (limit 3) admits.
    with pytest.raises(RequestSheddedError) as ei:
        rs.choose(priority=3)
    assert ei.value.priority == 3 and ei.value.retry_after_s > 0
    k1, _ = rs.choose(priority=1)
    st = rs.admission_stats()
    assert st["shed_total"] == 2
    assert st["shed_by_class"] == {0: 1, 3: 1}
    assert st["admitted_by_class"][0] == 4
    rs.release(k1)
    rs.release(held[3])
    assert st["max_ongoing_requests"] == 4


def test_serve_deployment_sheds_then_recovers():
    from ray_tpu import serve

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    serve.start()
    try:
        @serve.deployment(name="shed_cell", max_ongoing_requests=1)
        class Slow:
            def __call__(self, x=None):
                time.sleep(1.0)
                return "ok"

        handle = serve.run(Slow.bind())
        first = handle.remote()  # occupies the whole cap
        time.sleep(0.2)
        with pytest.raises(RequestSheddedError):
            handle.remote()
        with pytest.raises(RequestSheddedError) as ei:
            handle.options(priority=2).remote()
        assert ei.value.priority == 2
        assert first.result(timeout=10) == "ok"
        # Recovery: capacity freed → admission resumes (policy, not a
        # latched breaker).
        assert handle.remote().result(timeout=10) == "ok"
        st = serve.status()["shed_cell"]["admission"]
        assert st["shed_total"] == 2
        assert st["shed_by_class"] == {0: 1, 2: 1}
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_http_proxy_shed_is_503_with_retry_after():
    from ray_tpu import serve
    from ray_tpu.serve.http import HTTPProxy

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    serve.start()
    proxy = None
    try:
        @serve.deployment(name="shed_http", max_ongoing_requests=1)
        class Slow:
            def __call__(self, x=None):
                time.sleep(1.0)
                return "ok"

        handle = serve.run(Slow.bind())
        proxy = HTTPProxy(port=0)
        first = handle.remote()
        time.sleep(0.2)
        req = urllib.request.Request(
            f"http://127.0.0.1:{proxy.port}/shed_http", data=b"null",
            headers={"X-Request-Priority": "2"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 503
        assert int(ei.value.headers["Retry-After"]) >= 1
        body = json.loads(ei.value.read())
        assert body["shed"] is True and body["priority"] == 2
        assert first.result(timeout=10) == "ok"
        # After the release the proxy path serves again.
        with urllib.request.urlopen(
                f"http://127.0.0.1:{proxy.port}/shed_http",
                timeout=10) as r:
            assert json.loads(r.read())["result"] == "ok"
    finally:
        if proxy is not None:
            proxy.shutdown()
        serve.shutdown()
        ray_tpu.shutdown()


# --------------------------------------------------------------------------
# Kill axis: seeded NodeKiller schedules + worker-kill × workload cells.
# --------------------------------------------------------------------------
def test_node_killer_schedule_is_seeded_and_recorded():
    calls_a, calls_b = [], []

    def fake(log):
        def _kill():
            log.append("x")
            return {"pid": len(log)}

        return _kill

    ka = chaos.NodeKiller(
        [chaos.KillTarget("a", "worker", fake(calls_a)),
         chaos.KillTarget("b", "daemon", fake(calls_a))],
        seed=21, interval_s=(0.01, 0.03), max_kills=5)
    kb = chaos.NodeKiller(
        [chaos.KillTarget("a", "worker", fake(calls_b)),
         chaos.KillTarget("b", "daemon", fake(calls_b))],
        seed=21, interval_s=(0.01, 0.03), max_kills=5)
    with ka, kb:
        deadline = time.monotonic() + 5
        while (len(ka.kills) < 5 or len(kb.kills) < 5) and \
                time.monotonic() < deadline:
            time.sleep(0.01)
    assert [k["name"] for k in ka.kills[:5]] == \
        [k["name"] for k in kb.kills[:5]], "same seed, same victims"
    assert all("pid" in k for k in ka.kills)
    # The snapshot view (served at /api/chaos) sees every recorded kill.
    assert chaos.snapshot()["num_kills"] >= 10


def test_matrix_worker_kill_x_task_fanout_recovers():
    """Cell (worker kill × task fan-out): the seeded killer SIGKILLs
    worker processes mid-run; retriable tasks all complete correct."""
    ray_tpu.shutdown()
    w = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    if w.worker_mode != "process":
        pytest.skip("worker-kill cell needs the process plane")
    try:
        @ray_tpu.remote(max_retries=10)
        def slow_square(i):
            time.sleep(0.15)
            return i * i

        killer = chaos.NodeKiller([chaos.worker_kill_target()], seed=13,
                                  interval_s=(0.1, 0.25), max_kills=3)
        with killer:
            refs = [slow_square.remote(i) for i in range(12)]
            out = ray_tpu.get(refs, timeout=120)
        assert out == [i * i for i in range(12)]
        kills = [k for k in killer.kills if "error" not in k]
        assert kills, "the killer never fired inside the workload"
    finally:
        ray_tpu.shutdown()


def test_matrix_worker_kill_x_serve_stream_typed_and_recovers():
    """Cell (worker kill × serve stream): killing the streaming replica
    surfaces a typed error at next() quickly, a fresh stream completes
    on a survivor/replacement, and no in-flight slot leaks."""
    from ray_tpu import serve

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    serve.start()
    try:
        @serve.deployment(name="stream_cell", num_replicas=2)
        class S:
            def __call__(self, n):
                for i in range(n):
                    time.sleep(0.05)
                    yield i

        handle = serve.run(S.bind())
        gen = handle.options(stream=True).remote(200)
        assert next(gen) == 0
        victim = gen._replica
        killer = chaos.NodeKiller(
            [chaos.pid_kill_target("replica",
                                   lambda: victim._runtime.pid)],
            seed=3, interval_s=(0.01, 0.02), max_kills=1)
        with killer:
            t0 = time.monotonic()
            with pytest.raises(Exception) as ei:
                for _ in range(1000):
                    next(gen)
            assert not isinstance(ei.value, StopIteration)
            assert time.monotonic() - t0 < 60, "death must be typed+fast"
        assert [k for k in killer.kills if "error" not in k]
        # Recovery within the reconcile window; then router slots drain
        # back to zero (no leak).
        deadline = time.monotonic() + 15
        ok = False
        while time.monotonic() < deadline and not ok:
            try:
                assert list(
                    handle.options(stream=True).remote(3)) == [0, 1, 2]
                ok = True
            except Exception:  # noqa: BLE001 — pre-reconcile routing
                time.sleep(0.2)
        assert ok, "no surviving replica served after the kill"
        ctl = serve.api.get_or_create_controller()
        rs = ctl._replica_set("stream_cell")
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and sum(rs.queue_lengths()):
            time.sleep(0.1)
        assert sum(rs.queue_lengths()) == 0, "in-flight slot leaked"
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_matrix_replica_kill_x_traced_stream_assembles_typed():
    """Cell (replica SIGKILL × traced serve stream): with tracing armed,
    a mid-stream replica kill must still leave a COMPLETE trace — the
    kill visible as an error-status span, every span's parent resolving
    inside the assembled set (no orphans), and the recovery retry's
    spans landing in the SAME trace. Composes with the PR 8 NodeKiller
    replay contract (seeded schedule, kills recorded)."""
    from ray_tpu import serve
    from ray_tpu._private import tracing

    ray_tpu.shutdown()
    os.environ["RAY_TPU_TRACE"] = "1"
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    serve.start()
    try:
        assert tracing.active()

        @serve.deployment(name="traced_stream_cell", num_replicas=2)
        class S:
            def __call__(self, n):
                for i in range(n):
                    time.sleep(0.05)
                    yield i

        handle = serve.run(S.bind())
        with tracing.start_span("request") as root:
            gen = handle.options(stream=True).remote(200)
            assert next(gen) == 0
            victim = gen._replica
            killer = chaos.NodeKiller(
                [chaos.pid_kill_target("replica",
                                       lambda: victim._runtime.pid)],
                seed=5, interval_s=(0.01, 0.02), max_kills=1)
            with killer:
                with pytest.raises(Exception) as ei:
                    with tracing.start_span("stream.consume"):
                        for _ in range(1000):
                            next(gen)
                assert not isinstance(ei.value, StopIteration)
            assert [k for k in killer.kills if "error" not in k]
            # Recovery INSIDE the same trace: a fresh stream completes
            # on the survivor/replacement replica.
            deadline = time.monotonic() + 15
            ok = False
            while time.monotonic() < deadline and not ok:
                try:
                    assert list(handle.options(stream=True)
                                .remote(3)) == [0, 1, 2]
                    ok = True
                except Exception:  # noqa: BLE001 — pre-reconcile route
                    time.sleep(0.2)
            assert ok, "no surviving replica served after the kill"
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline:
            spans = tracing.local_spans(root.ctx.trace_id)
            if any(s["status"] == "error" for s in spans):
                break
            time.sleep(0.05)
        names = {s["name"] for s in spans}
        assert "serve.request" in names
        # Kill visible: the consume span (typed error surfaced at
        # next()) and/or the killed call's exec span carry error
        # status.
        errors = [s for s in spans if s["status"] == "error"]
        assert errors, names
        # Complete-with-typed-error: no orphan spans — every parent
        # resolves inside the assembled trace.
        ids = {s["span_id"] for s in spans}
        orphans = [s for s in spans
                   if s["parent_id"] and s["parent_id"] not in ids]
        assert not orphans, orphans
        # The recovery stream's spans are in the SAME trace, ok-status.
        ok_requests = [s for s in spans if s["name"] == "serve.request"
                       and s["status"] == "ok"]
        assert ok_requests
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        tracing.uninstall()
        os.environ.pop("RAY_TPU_TRACE", None)
        os.environ.pop(tracing.ENV_DIR, None)


# --------------------------------------------------------------------------
# Observability: /api/chaos + util.state.chaos_summary.
# --------------------------------------------------------------------------
def test_api_chaos_reports_faults_kills_and_shedding():
    from ray_tpu import serve
    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    serve.start()
    try:
        # Some wire faults…
        lis, srv, cli = _conn_pair()
        chaos.install(chaos.ChaosConfig(seed=1, drop=1.0, sites=("cli",)))
        cli.send(("m", 1))
        # …one recorded kill…
        killer = chaos.NodeKiller(
            [chaos.KillTarget("fake", "worker",
                              lambda: {"pid": 1234})],
            seed=1, interval_s=(0.01, 0.02), max_kills=1)
        with killer:
            deadline = time.monotonic() + 5
            while not killer.kills and time.monotonic() < deadline:
                time.sleep(0.01)
        # …and one serve-tier shed.
        @serve.deployment(name="chaos_panel", max_ongoing_requests=1)
        class Slow:
            def __call__(self, x=None):
                time.sleep(0.4)
                return 1

        handle = serve.run(Slow.bind())
        hold = handle.remote()
        time.sleep(0.1)
        with pytest.raises(RequestSheddedError):
            handle.options(priority=1).remote()

        dash = start_dashboard(port=0)
        try:
            with urllib.request.urlopen(dash.url + "/api/chaos",
                                        timeout=10) as r:
                panel = json.loads(r.read())
            assert panel["active"] is True
            assert panel["wire_counters"]["cli"]["drop"] == 1
            assert panel["num_kills"] >= 1
            shed = panel["serve_shedding"]["chaos_panel"]
            assert shed["shed_total"] == 1
            assert shed["shed_by_class"] == {"1": 1} or \
                shed["shed_by_class"] == {1: 1}
            # The snapshot page carries the panel too.
            with urllib.request.urlopen(dash.url + "/api/snapshot",
                                        timeout=10) as r:
                snap = json.loads(r.read())
            assert snap["chaos"]["serve_shed_total"] == 1
        finally:
            stop_dashboard()
        assert hold.result(timeout=10) == 1
        cli.close(), srv.close(), lis.close()
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# --------------------------------------------------------------------------
# Disaggregated-serving rows (PR 19): kills across the prefill->decode
# pairing hop — the published-KV handoff, not just steady-state streams.
# --------------------------------------------------------------------------
def _disagg_engine_config():
    import jax.numpy as jnp

    from ray_tpu.llm import EngineConfig
    from ray_tpu.models import TransformerConfig

    mcfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=48, dtype=jnp.float32)
    return EngineConfig(model=mcfg, num_blocks=128, block_size=4,
                        max_num_seqs=4)


def test_matrix_prefill_kill_after_publish_x_decode_fallback():
    """Row (prefill replica SIGKILL × disagg pairing): the prefill
    replica dies AFTER publishing a ticket but BEFORE the decode pull.
    The pull fails (the p2p payload died with its owner), the decode
    replica falls back to a transparent LOCAL re-prefill and completes
    the stream correctly; pool accounting balances on both sides —
    zero leaked KV blocks, the fallback counted."""
    from ray_tpu import serve
    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.llm.disagg import build_disagg_llm_app

    ray_tpu.shutdown()
    # Short pull timeout so the decode replica's doomed pull fails fast
    # instead of stalling the default 10s; replicas inherit the env.
    os.environ["RAY_TPU_LLM_DISAGG_PULL_TIMEOUT_S"] = "2.0"
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    serve.start()
    try:
        ecfg = _disagg_engine_config()
        papp, dapp = build_disagg_llm_app(ecfg)
        serve.run(papp, name="prefill")
        serve.run(dapp, name="decode")
        ph = serve.get_deployment_handle("llm-prefill")
        dh = serve.get_deployment_handle("llm-decode")
        prompt = [9, 8, 7, 6, 5]
        req = {"prompt": prompt, "max_new_tokens": 6}

        # The expected stream: the engines are weight-deterministic
        # (same config seed), so a local engine is the oracle.
        oracle = InferenceEngine(ecfg)
        ref = list(oracle.generate(prompt, max_new_tokens=6))
        oracle.shutdown()

        # Publish without pulling. The DeploymentResponse frees its
        # replica pin at result(); grab the pid BEFORE that.
        resp = ph.options(method_name="prefill",
                          stream=False).remote(dict(req))
        victim = resp._replica
        ticket = resp.result(timeout=60)
        assert ticket["blocks"] > 0
        pre_stats = ph.stats.remote().result(timeout=30)
        assert pre_stats["kv_publications_outstanding"] == 1

        killer = chaos.NodeKiller(
            [chaos.pid_kill_target("prefill_replica",
                                   lambda: victim._runtime.pid)],
            seed=19, interval_s=(0.01, 0.02), max_kills=1)
        with killer:
            deadline = time.monotonic() + 5
            while not killer.kills and time.monotonic() < deadline:
                time.sleep(0.01)
        assert [k for k in killer.kills if "error" not in k], \
            "the prefill replica kill never fired"

        # Barrier: the SIGKILL lands instantly but the payload's
        # owner-death can take a beat to propagate — wait until the
        # published object is actually unresolvable before decoding,
        # otherwise the pull races ahead of the death and adopts.
        deadline = time.monotonic() + 10
        payload_dead = False
        while time.monotonic() < deadline:
            try:
                ray_tpu.get(ticket["ref"], timeout=0.5)
            except Exception:  # noqa: BLE001 — any failure = dead owner
                payload_dead = True
                break
            time.sleep(0.05)
        assert payload_dead, "published KV payload survived its owner"

        # Decode with the dead ticket: the pull must fail typed inside
        # the replica and the SAME request complete via local
        # re-prefill — transparent to the client.
        toks = list(dh.options(stream=True).remote(
            {**req, "_disagg": ticket}))
        assert toks == ref, (toks, ref)
        dst = dh.stats.remote().result(timeout=30)
        assert dst["disagg_fallbacks"] == 1
        assert dst["disagg_adopted"] == 0
        assert dst["blocks_grafted"] == 0

        # Decode side drains clean: nothing adopted, nothing leaked.
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            dst = dh.stats.remote().result(timeout=30)
            if dst["blocks_in_use"] == 0 and dst["running"] == 0:
                break
            time.sleep(0.2)
        assert dst["blocks_in_use"] == 0, "decode side leaked KV blocks"

        # Prefill side: the controller replaces the killed replica
        # within the reconcile window, and the replacement's ledger is
        # balanced — no publication outstanding, no held KV.
        deadline = time.monotonic() + 20
        pst = None
        while time.monotonic() < deadline:
            try:
                pst = ph.stats.remote().result(timeout=30)
                if pst["kv_publications_outstanding"] == 0 and \
                        pst["blocks_in_use"] == 0:
                    break
            except Exception:  # noqa: BLE001 — pre-reconcile routing
                pass
            time.sleep(0.2)
        assert pst is not None, "no prefill replica served after kill"
        assert pst["kv_publications_outstanding"] == 0
        assert pst["blocks_in_use"] == 0
        assert pst["held_sequences"] == 0
    finally:
        os.environ.pop("RAY_TPU_LLM_DISAGG_PULL_TIMEOUT_S", None)
        serve.shutdown()
        ray_tpu.shutdown()


def test_matrix_decode_kill_midstream_x_disagg_repair():
    """Row (decode replica SIGKILL × disagg stream): the decode replica
    dies mid-stream with the disagg plane armed. The client sees a
    typed error (never a hang), re-pairs through the SAME handle —
    fresh prefill ticket, replacement decode replica — and the retried
    request completes token-identical; no publication leaks past the
    episode on the prefill side."""
    from ray_tpu import serve
    from ray_tpu.llm import InferenceEngine
    from ray_tpu.llm.disagg import DisaggHandle, build_disagg_llm_app

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    serve.start()
    try:
        ecfg = _disagg_engine_config()
        papp, dapp = build_disagg_llm_app(ecfg)
        serve.run(papp, name="prefill")
        serve.run(dapp, name="decode")
        h = DisaggHandle.from_deployments()
        ph = serve.get_deployment_handle("llm-prefill")
        prompt = [3, 4, 5, 6]
        max_new = 60

        oracle = InferenceEngine(ecfg)
        ref = list(oracle.generate(prompt, max_new_tokens=max_new))
        oracle.shutdown()

        gen = h.stream({"prompt": prompt, "max_new_tokens": max_new})
        assert next(gen) == ref[0]

        ctl = serve.api.get_or_create_controller()

        def decode_pid():
            for r in ctl._deployments["llm-decode"].replicas:
                pid = r._runtime.pid
                if pid and pid != os.getpid():
                    return pid
            return None

        killer = chaos.NodeKiller(
            [chaos.pid_kill_target("decode_replica", decode_pid,
                                   once=True)],
            seed=23, interval_s=(0.01, 0.02), max_kills=1)
        with killer:
            t0 = time.monotonic()
            with pytest.raises(Exception) as ei:
                for _ in range(max_new + 5):
                    next(gen)
            assert not isinstance(ei.value, StopIteration)
            assert time.monotonic() - t0 < 60, "death must be typed+fast"
        assert [k for k in killer.kills if "error" not in k], \
            "the decode replica kill never fired"

        # Re-pair and complete: the same handle pairs a fresh prefill
        # ticket with the replacement decode replica inside the
        # reconcile window.
        deadline = time.monotonic() + 20
        toks, ok = None, False
        while time.monotonic() < deadline and not ok:
            try:
                toks = list(h.stream({"prompt": prompt,
                                      "max_new_tokens": max_new}))
                ok = len(toks) == max_new
            except Exception:  # noqa: BLE001 — pre-reconcile routing
                time.sleep(0.2)
        assert ok, "re-paired request never completed after the kill"
        assert toks == ref, (toks[:8], ref[:8])

        # Publish/ack lifecycle balanced on the prefill side: the dead
        # pairing's publication is acked-or-expired, never leaked (the
        # TTL backstop covers a decode death between publish and ack).
        deadline = time.monotonic() + 35
        pst = None
        while time.monotonic() < deadline:
            pst = ph.stats.remote().result(timeout=30)
            if pst["kv_publications_outstanding"] == 0 and \
                    pst["blocks_in_use"] == 0:
                break
            time.sleep(0.5)
        assert pst["kv_publications_outstanding"] == 0, pst
        assert pst["blocks_in_use"] == 0, "prefill side leaked held KV"
        assert pst["kv_publishes"] >= 2
        assert pst["kv_acks"] + pst["kv_expiries"] == \
            pst["kv_publishes"]
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# ==========================================================================
# FULL SWEEP (slow): multi-process cluster cells — wire faults + daemon
# kills composed over the cross-node task plane, data shuffle, workflows.
# ==========================================================================
def _spawn_env(extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_HEAD_CLIENT_TIMEOUT_S"] = "2.0"
    if extra:
        env.update(extra)
    return env


# --------------------------------------------------------------------------
# Elasticity rows (PR 12): kills and wire faults against the AUTOSCALER's
# node-launch path — the scaling transient, not just steady state.
# --------------------------------------------------------------------------
def test_matrix_nodekill_during_launch_x_retry_path(tmp_path):
    """Cell (NodeKiller × node launch): the seeded killer SIGKILLs a
    node daemon WHILE the autoscaler is launching it (before the join
    line). The bounded launch-retry path must absorb the kill — the
    next attempt joins — with the attempt/failure counters recording
    the murdered try, and never a silent half-member."""
    import subprocess
    import sys

    from ray_tpu.autoscaler import LocalSubprocessProvider, NodeTypeConfig

    head = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.head_service",
         "--port", "0"],
        stdout=subprocess.PIPE, text=True, env=_spawn_env())
    address = head.stdout.readline().strip().rsplit(" ", 1)[-1]
    GlobalConfig.set("autoscaler_launch_retries", 3)
    GlobalConfig.set("autoscaler_launch_backoff_s", 0.05)
    try:
        prov = LocalSubprocessProvider(
            address, worker_mode="thread", env=_spawn_env())
        spawned = []
        real_spawn = prov._spawn

        def killing_spawn(node_type):
            proc = real_spawn(node_type)
            spawned.append(proc)
            if len(spawned) == 1:
                # The seeded killer hits the LAUNCHING node: one shot,
                # recorded, before it can print its join line.
                killer = chaos.NodeKiller(
                    [chaos.pid_kill_target("launching-node",
                                           lambda: proc.pid,
                                           kind="daemon", once=True)],
                    seed=5, interval_s=(0.0, 0.01), max_kills=1)
                killer.start()
                for _ in range(200):
                    if proc.poll() is not None:
                        break
                    time.sleep(0.05)
                killer.stop()
                assert [k for k in killer.kills if "error" not in k], \
                    "the seeded kill never fired"
            return proc

        prov._spawn = killing_spawn
        handle = prov.launch(NodeTypeConfig("base", {"CPU": 1}))
        assert handle["client_id"]
        assert prov.launch_attempts == 2, "kill must cost one attempt"
        assert prov.launch_failures == 1
        assert spawned[0].poll() is not None  # the victim died
        assert spawned[1].poll() is None      # the retry lives
        prov.terminate(handle)
    finally:
        GlobalConfig.reset()
        for p in spawned:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=5)
        head.kill()
        head.wait(timeout=5)


def test_matrix_wire_delay_x_scale_up_cold_start_bounded(tmp_path):
    """Cell (frame delay × scale-up): a node launched WITH seeded wire
    delays armed (inherited via RAY_TPU_CHAOS) still joins inside the
    launch grace window — the cold-start SLO holds under wire chaos —
    and serves a real task end to end."""
    from ray_tpu.autoscaler import (
        ClusterAutoscaler,
        LocalSubprocessProvider,
        NodeTypeConfig,
    )
    import subprocess
    import sys

    head = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.head_service",
         "--port", "0", "--state", str(tmp_path / "state.log")],
        stdout=subprocess.PIPE, text=True, env=_spawn_env())
    address = head.stdout.readline().strip().rsplit(" ", 1)[-1]
    chaos_env = {"RAY_TPU_CHAOS": json.dumps({
        "seed": 6, "delay": 0.3, "delay_ms": 5, "sites": ["head"]})}
    scaler = None
    try:
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=address)
        GlobalConfig.set("autoscaler_launch_grace_s", 30.0)
        scaler = ClusterAutoscaler(
            address,
            [NodeTypeConfig("base", {"CPU": 2}, min_workers=1,
                            max_workers=1)],
            provider=LocalSubprocessProvider(
                address, worker_mode="thread",
                env=_spawn_env(chaos_env)),
            idle_timeout_s=3600.0, update_interval_s=0.5)
        summ = scaler.summary()
        assert summ["launch_failures"] == 0, summ
        events = [e for e in summ["scale_events"] if e.get("joined")]
        assert events, "no scale-up event recorded"
        assert events[0]["join_latency_s"] < 30.0  # inside the grace

        @ray_tpu.remote
        def probe(x):
            return x + 1

        assert ray_tpu.get(probe.remote(1), timeout=60) == 2
    finally:
        if scaler is not None:
            scaler.shutdown()
        ray_tpu.shutdown()
        GlobalConfig.reset()
        head.kill()
        head.wait(timeout=5)


# --------------------------------------------------------------------------
# Head-kill rows (PR 15): the control plane ITSELF is the victim — a warm
# standby promotes over the shared state log, clients fail over by epoch,
# and the workload keeps its SLO (head death is a non-event).
# --------------------------------------------------------------------------
def _spawn_head_pair(tmp_path):
    """(primary_proc, standby_proc, address_list_str, env) — a primary
    + warm standby over one shared state log, promotion knobs tightened
    so the blackout stays test-sized."""
    import socket
    import subprocess
    import sys

    token = "feedface%08x" % (os.getpid() & 0xFFFFFFFF)
    env = _spawn_env({
        "RAY_TPU_CLUSTER_TOKEN": token,
        "RAY_TPU_HEAD_STANDBY_PROBE_PERIOD_S": "0.2",
        "RAY_TPU_HEAD_STANDBY_MISSES_TO_PROMOTE": "2",
    })
    os.environ["RAY_TPU_CLUSTER_TOKEN"] = token
    state = str(tmp_path / "shared_head_state.log")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        standby_port = s.getsockname()[1]
    primary = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.head_service",
         "--port", "0", "--state", state, "--token", token],
        stdout=subprocess.PIPE, text=True, env=env)
    address = primary.stdout.readline().strip().rsplit(" ", 1)[-1]
    standby = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.head_service",
         "--port", str(standby_port), "--state", state,
         "--token", token, "--standby-of", address],
        stdout=subprocess.PIPE, text=True, env=env)
    assert "standing by" in standby.stdout.readline()
    addresses = f"{address},127.0.0.1:{standby_port}"
    env["RAY_TPU_HEAD_ADDRESSES"] = addresses
    return primary, standby, addresses, env


@pytest.fixture
def _head_pair_cleanup():
    yield
    os.environ.pop("RAY_TPU_CLUSTER_TOKEN", None)


def test_matrix_headkill_x_task_fanout_survives(tmp_path,
                                                _head_pair_cleanup):
    """Cell (head SIGKILL × cluster fan-out): the head dies mid-flight
    under a task fan-out across two node daemons. The steady-state
    task plane is head-free (PR 10), the standby promotes, every
    client fails over by epoch and re-registers — ALL tasks complete,
    zero ref loss, the blackout is measured, and the killer's record
    shows exactly one head kill."""
    import subprocess
    import sys

    primary, standby, addresses, env = _spawn_head_pair(tmp_path)
    nodes = []
    try:
        for _ in range(2):
            p = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.node_daemon",
                 "--address", addresses, "--num-cpus", "2",
                 "--worker-mode", "thread"],
                stdout=subprocess.PIPE, text=True, env=env)
            assert "joined" in p.stdout.readline()
            nodes.append(p)
        ray_tpu.shutdown()
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=addresses)
        w = ray_tpu._private.worker.global_worker()

        @ray_tpu.remote
        def work(i):
            time.sleep(0.05)
            return i * 2

        warm = [work.remote(i) for i in range(4)]
        assert ray_tpu.get(warm, timeout=60) == [0, 2, 4, 6]

        killer = chaos.NodeKiller(
            [chaos.head_kill_target(primary)],
            seed=15, interval_s=(0.05, 0.1), max_kills=1)
        refs = [work.remote(i) for i in range(40)]
        with killer:
            # The kill fires while the fan-out is in flight.
            out = ray_tpu.get(refs, timeout=120)
        assert out == [i * 2 for i in range(40)]
        kills = [k for k in killer.kills if "error" not in k]
        assert len(kills) == 1 and kills[0]["kind"] == "head"
        assert primary.poll() is not None
        # Post-failover control plane is live: epoch bumped, the
        # promoted head answers, membership reconciled by re-join.
        # (The blackout records on the first successful round trip
        # AFTER the failover observation — up to one heartbeat tick
        # later — so wait for it, not just for the observation.)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and (
                w.head_client.failovers < 1
                or w.head_client.last_blackout_s is None):
            time.sleep(0.2)
        assert w.head_client.failovers == 1
        assert w.head_client.head_epoch == 2
        assert w.head_client.last_blackout_s is not None
        stats = w.head_client.head_stats()
        assert stats["epoch"] == 2 and not stats["fenced"]
        live = [n for n in w.head_client.node_list() if n["alive"]]
        assert len(live) >= 2
        # And the task plane still works END TO END on the new head —
        # within the usual post-fault reconcile window (node event
        # channels re-dial on their own cadence; a probe racing that
        # retries like any client would).
        deadline = time.monotonic() + 20
        ok = False
        while time.monotonic() < deadline and not ok:
            try:
                assert ray_tpu.get(work.remote(100), timeout=30) == 200
                ok = True
            except AssertionError:
                raise
            except Exception:  # noqa: BLE001 — pre-reconcile routing
                time.sleep(0.5)
        assert ok, "no node served a task after the promotion settled"
    finally:
        ray_tpu.shutdown()
        for p in reversed(nodes + [standby, primary]):
            p.kill()
            p.wait(timeout=5)


def test_matrix_headkill_x_scale_up_resumes(tmp_path,
                                            _head_pair_cleanup):
    """Cell (head SIGKILL × scale-up): the head dies the moment the
    autoscaler's first node launch spawns — the launching daemon dials
    into the blackout. The provider's bounded retry plus the inherited
    standby list (RAY_TPU_HEAD_ADDRESSES) land the node on the
    PROMOTED head, parked demand is preserved, and the episode
    completes: mid-scale-up operations resume rather than orphan."""
    import subprocess
    import sys

    from ray_tpu.autoscaler import (
        ClusterAutoscaler,
        LocalSubprocessProvider,
        NodeTypeConfig,
    )

    primary, standby, addresses, env = _spawn_head_pair(tmp_path)
    scaler = None
    try:
        ray_tpu.shutdown()
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=addresses)
        GlobalConfig.set("autoscaler_launch_retries", 5)
        GlobalConfig.set("autoscaler_launch_backoff_s", 0.3)
        GlobalConfig.set("autoscaler_launch_grace_s", 30.0)
        prov = LocalSubprocessProvider(
            addresses, worker_mode="thread", env=env)
        real_spawn = prov._spawn
        spawned = []

        def killing_spawn(node_type):
            if not spawned:
                # Head dies exactly as the first launch leaves the
                # gate: the daemon cold-starts INTO the blackout.
                killer = chaos.NodeKiller(
                    [chaos.head_kill_target(primary)],
                    seed=16, interval_s=(0.0, 0.01), max_kills=1)
                killer.start()
                time.sleep(0.3)
                killer.stop()
                assert [k for k in killer.kills if "error" not in k]
            proc = real_spawn(node_type)
            spawned.append(proc)
            return proc

        prov._spawn = killing_spawn
        scaler = ClusterAutoscaler(
            addresses,
            [NodeTypeConfig("base", {"CPU": 2}, min_workers=0,
                            max_workers=1)],
            provider=prov, idle_timeout_s=3600.0,
            update_interval_s=0.3)

        @ray_tpu.remote
        def work(x):
            return x + 1

        refs = [work.remote(i) for i in range(4)]
        assert ray_tpu.get(refs, timeout=120) == [1, 2, 3, 4]
        w = ray_tpu._private.worker.global_worker()
        assert w.head_client.head_epoch == 2
        summ = scaler.summary()
        assert summ["managed_nodes"] == 1
        assert any(e.get("joined") for e in summ["scale_events"])
    finally:
        GlobalConfig.reset()
        if scaler is not None:
            scaler.shutdown()
        ray_tpu.shutdown()
        for p in reversed([standby, primary]):
            p.kill()
            p.wait(timeout=5)


def test_matrix_headkill_x_serve_stream_completes(tmp_path,
                                                  _head_pair_cleanup):
    """Cell (head SIGKILL × serve stream): token streams in flight when
    the head dies must run to completion (the serve data plane is
    head-free), and a NEW stream after promotion succeeds — the serve
    controller rides the failed-over client without re-deploying."""
    import threading

    from ray_tpu import serve

    primary, standby, addresses, env = _spawn_head_pair(tmp_path)
    try:
        ray_tpu.shutdown()
        ray_tpu.init(num_cpus=2, num_tpus=0, worker_mode="thread",
                     address=addresses)
        serve.start()

        @serve.deployment(name="head_kill_stream", num_replicas=2)
        class S:
            def __call__(self, n):
                for i in range(n):
                    time.sleep(0.05)
                    yield i

        handle = serve.run(S.bind())
        assert list(handle.options(stream=True).remote(3)) == [0, 1, 2]

        results = []
        errors = []

        def stream(n=40):
            try:
                results.append(
                    list(handle.options(stream=True).remote(n)))
            except BaseException as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=stream) for _ in range(4)]
        for t in threads:
            t.start()
        killer = chaos.NodeKiller(
            [chaos.head_kill_target(primary)],
            seed=17, interval_s=(0.1, 0.2), max_kills=1)
        killer.start()
        for t in threads:
            t.join(120)
        killer.stop()
        assert [k for k in killer.kills if "error" not in k]
        assert not errors, errors
        assert results == [list(range(40))] * 4
        # Post-promotion: a fresh stream through the same deployment.
        w = ray_tpu._private.worker.global_worker()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and w.head_client.failovers < 1:
            time.sleep(0.2)
        assert w.head_client.head_epoch == 2
        assert list(handle.options(stream=True).remote(5)) == \
            [0, 1, 2, 3, 4]
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
        for p in reversed([standby, primary]):
            p.kill()
            p.wait(timeout=5)


def _spawn_cluster(tmp_path, n_nodes=2, node_env=None):
    import subprocess
    import sys

    head = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.head_service",
         "--port", "0", "--state", str(tmp_path / "head_state.log")],
        stdout=subprocess.PIPE, text=True, env=_spawn_env())
    address = head.stdout.readline().strip().rsplit(" ", 1)[-1]
    nodes = []
    for i in range(n_nodes):
        p = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.node_daemon",
             "--address", address, "--num-cpus", "2",
             "--worker-mode", "thread"],
            stdout=subprocess.PIPE, text=True, env=_spawn_env(node_env))
        assert "joined" in p.stdout.readline()
        nodes.append(p)
    return head, address, nodes


@pytest.mark.slow
def test_sweep_wire_delay_and_daemon_kill_x_cluster_fanout(tmp_path):
    """Cell (frame delay + daemon SIGKILL × cross-node fan-out): with
    every node daemon running seeded frame delays, killing one daemon
    mid-fan-out still completes every retriable task on the survivor."""
    node_env = {"RAY_TPU_CHAOS":
                '{"seed": 5, "delay": 0.1, "delay_ms": 3}'}
    ray_tpu.shutdown()
    head, address, nodes = _spawn_cluster(tmp_path, node_env=node_env)
    try:
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=address)

        @ray_tpu.remote(max_retries=10)
        def slow_id(i):
            time.sleep(0.05)
            return i

        killer = chaos.NodeKiller(
            [chaos.popen_kill_target("node2", nodes[1])],
            seed=9, interval_s=(0.4, 0.6), max_kills=1)
        with killer:
            refs = [slow_id.remote(i) for i in range(60)]
            out = ray_tpu.get(refs, timeout=180)
        assert out == list(range(60))
        assert [k for k in killer.kills if "error" not in k], \
            "daemon kill never fired"
    finally:
        ray_tpu.shutdown()
        for p in nodes + [head]:
            p.kill()
            p.wait(timeout=5)


@pytest.mark.slow
def test_sweep_connection_reset_x_object_pull_falls_back(tmp_path):
    """Cell (connection reset × object pull): with the driver's peer
    lanes resetting at random, cross-node results still materialize
    (bounded direct retries, then the head relay) — bytes intact."""
    ray_tpu.shutdown()
    head, address, nodes = _spawn_cluster(tmp_path, n_nodes=1)
    try:
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=address)
        GlobalConfig.set("peer_pull_backoff_s", 0.01)

        @ray_tpu.remote
        def blob(i):
            import numpy as np

            return np.full(512 * 1024, i, dtype=np.uint8)

        chaos.install(chaos.ChaosConfig(seed=4, reset=0.3,
                                        sites=("peer",)))
        try:
            for i in range(6):
                out = ray_tpu.get(blob.remote(i), timeout=60)
                assert out.shape == (512 * 1024,) and int(out[0]) == i
        finally:
            chaos.uninstall()
    finally:
        ray_tpu.shutdown()
        for p in nodes + [head]:
            p.kill()
            p.wait(timeout=5)


@pytest.mark.slow
def test_sweep_worker_kill_x_data_shuffle():
    """Cell (worker kill × data shuffle): a groupby-shuffle pipeline
    under random worker SIGKILLs still produces the exact aggregate."""
    from ray_tpu import data

    ray_tpu.shutdown()
    w = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    if w.worker_mode != "process":
        pytest.skip("worker-kill cell needs the process plane")
    try:
        killer = chaos.NodeKiller([chaos.worker_kill_target()], seed=17,
                                  interval_s=(0.2, 0.4), max_kills=2)
        with killer:
            ds = data.range(400, parallelism=8).map_batches(
                lambda b: {"id": b["id"], "bucket": b["id"] % 4},
                batch_format="numpy")
            rows = ds.groupby("bucket").count().take_all()
        counts = {int(r["bucket"]): int(r["count()"]) for r in rows}
        assert counts == {0: 100, 1: 100, 2: 100, 3: 100}
    finally:
        ray_tpu.shutdown()


@pytest.mark.slow
def test_sweep_worker_kill_x_workflow_exactly_once(tmp_path):
    """Cell (worker kill × workflow): steps re-execute under kills but
    COMMIT exactly once — the side-effect journal shows one commit per
    step and the DAG result is correct."""
    from ray_tpu import workflow

    ray_tpu.shutdown()
    w = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    if w.worker_mode != "process":
        pytest.skip("worker-kill cell needs the process plane")
    try:
        workflow.init(str(tmp_path / "wf"))

        @workflow.step(max_retries=10)
        def add(x, i):
            time.sleep(0.1)
            return x + i

        node = add.bind(0, 1)
        for i in range(2, 6):
            node = add.bind(node, i)
        killer = chaos.NodeKiller([chaos.worker_kill_target()], seed=23,
                                  interval_s=(0.1, 0.3), max_kills=2)
        with killer:
            result = workflow.run(node, workflow_id="chaos_wf")
        assert result == 15
        assert workflow.get_status("chaos_wf") == "SUCCESS"
        assert workflow.get_output("chaos_wf") == 15
    finally:
        ray_tpu.shutdown()


# --------------------------------------------------------------------------
# Ownership axis: owner death x borrowed-ref consumers (PR 10 rows).
# --------------------------------------------------------------------------
_OWNER_DRIVER = r"""
import sys, time
import cloudpickle
import ray_tpu

address, mode = sys.argv[1], sys.argv[2]
ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
             address=address)
w = ray_tpu._private.worker.global_worker()

@ray_tpu.remote
def blob(i):
    return bytes(300_000) + bytes([i])  # > inline cap: bytes stay node-side

refs = [blob.remote(i) for i in range(6)]
ray_tpu.wait(refs, num_returns=len(refs), timeout=120)
w.kv_put(b"ownchaos/refs", cloudpickle.dumps(refs))
w.kv_put(b"ownchaos/ready", b"1")
if mode == "graceful":
    # Lease handoff: router.shutdown transfers the owner's location
    # table to the head before the process exits.
    ray_tpu.shutdown()
    sys.exit(0)
while True:  # hold ownership until SIGKILLed by the test
    time.sleep(0.2)
"""


def _wait_kv_poll(worker, key, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = worker.kv_get(key)
        if v is not None:
            return v
        time.sleep(0.05)
    raise AssertionError(f"kv key {key} never appeared")


def _wait_client_gone(worker, client_id, timeout=30):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if client_id not in worker.head_client.cluster_info()["clients"]:
            return
        time.sleep(0.25)
    raise AssertionError(f"head never declared {client_id} dead")


@pytest.mark.slow
def test_matrix_owner_kill9_x_borrowed_refs_typed(tmp_path):
    """Cell (owner SIGKILL × borrowed-ref consumer): driver A fans out
    onto a real node, its refs are borrowed by driver B, A dies -9
    WITHOUT a lease handoff — B's gets fail typed
    (OwnerDiedError/ObjectLostError), never an unbounded poll."""
    import pickle as _pickle
    import subprocess
    import sys as _sys

    from ray_tpu.exceptions import ObjectLostError

    ray_tpu.shutdown()
    head, address, nodes = _spawn_cluster(tmp_path, n_nodes=1)
    owner = None
    try:
        owner = subprocess.Popen(
            [_sys.executable, "-c", _OWNER_DRIVER, address, "hold"],
            env=_spawn_env())
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=address)
        w = ray_tpu._private.worker.global_worker()
        _wait_kv_poll(w, b"ownchaos/ready")
        refs = _pickle.loads(w.kv_get(b"ownchaos/refs"))
        owner_id = w.borrowed_owner(refs[0].object_id.binary())[0]
        owner.kill()
        owner.wait(timeout=5)
        _wait_client_gone(w, owner_id)
        t0 = time.monotonic()
        for ref in refs[:3]:
            with pytest.raises(ObjectLostError):  # OwnerDiedError is-a
                ray_tpu.get(ref, timeout=60)
        assert time.monotonic() - t0 < 60, "loss was not typed promptly"
        res = w.owner_resolver.counters()
        assert res["owner_died_errors"] >= 1
    finally:
        ray_tpu.shutdown()
        for p in [owner] + nodes + [head]:
            if p is not None:
                p.kill()
                p.wait(timeout=5)


@pytest.mark.slow
def test_matrix_owner_graceful_exit_x_lease_handoff_resolves(tmp_path):
    """Cell (owner graceful exit × borrowed-ref consumer): the same
    topology, but A exits cleanly — its location table lease-transfers
    to the head, so B's borrowed refs still resolve (head fallback →
    p2p pull from the holding node) after the owner is gone."""
    import pickle as _pickle
    import subprocess
    import sys as _sys

    ray_tpu.shutdown()
    head, address, nodes = _spawn_cluster(tmp_path, n_nodes=1)
    owner = None
    try:
        owner = subprocess.Popen(
            [_sys.executable, "-c", _OWNER_DRIVER, address, "graceful"],
            env=_spawn_env())
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=address)
        w = ray_tpu._private.worker.global_worker()
        _wait_kv_poll(w, b"ownchaos/ready")
        refs = _pickle.loads(w.kv_get(b"ownchaos/refs"))
        owner_id = w.borrowed_owner(refs[0].object_id.binary())[0]
        owner.wait(timeout=30)  # graceful exit ran the lease handoff
        _wait_client_gone(w, owner_id)
        for i, ref in enumerate(refs):
            value = ray_tpu.get(ref, timeout=60)
            assert value == bytes(300_000) + bytes([i])
    finally:
        ray_tpu.shutdown()
        for p in [owner] + nodes + [head]:
            if p is not None:
                p.kill()
                p.wait(timeout=5)
