"""Elastic production-loop tests (PR 12): typed node-launch failures,
drain-before-reap lease transfer (reaping a node that holds live
borrowed refs strands nothing), the idle-reap push race
(refuse-and-reroute), and scale-to-zero wake semantics (queue, not
shed, while the deployment scales back up)."""

import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu._private.config import GlobalConfig
from ray_tpu.exceptions import (
    GetTimeoutError,
    NodeLaunchFailedError,
    ObjectLostError,
    OwnerDiedError,
    RequestSheddedError,
)


def _spawn_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_HEAD_CLIENT_TIMEOUT_S"] = "2.0"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.fixture
def head_proc():
    os.environ["RAY_TPU_HEAD_CLIENT_TIMEOUT_S"] = "2.0"
    ray_tpu.shutdown()
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.head_service",
         "--port", "0"],
        stdout=subprocess.PIPE, text=True, env=_spawn_env())
    line = proc.stdout.readline()
    address = line.strip().rsplit(" ", 1)[-1]
    yield address
    ray_tpu.shutdown()
    proc.kill()
    proc.wait(timeout=5)
    os.environ.pop("RAY_TPU_HEAD_CLIENT_TIMEOUT_S", None)


def _wait_nodes(hc, n, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        nodes = hc.node_list()
        live = [x for x in nodes if x.get("alive") and x.get("peer_addr")]
        if len(live) >= n:
            return live
        time.sleep(0.1)
    raise AssertionError(f"cluster never reached {n} nodes: {nodes}")


# ------------------------------------------------------------ launch typed
def test_launch_failure_is_typed_with_counters():
    """A provider that can never join surfaces NodeLaunchFailedError
    after bounded retries — never silent membership absence — and the
    launch_attempts/launch_failures counters record every try."""
    from ray_tpu.autoscaler import LocalSubprocessProvider, NodeTypeConfig

    GlobalConfig.set("autoscaler_launch_retries", 2)
    GlobalConfig.set("autoscaler_launch_backoff_s", 0.02)
    GlobalConfig.set("autoscaler_launch_grace_s", 3.0)
    try:
        prov = LocalSubprocessProvider("127.0.0.1:1")  # nothing listens
        with pytest.raises(NodeLaunchFailedError) as ei:
            prov.launch(NodeTypeConfig("base", {"CPU": 1}))
        assert ei.value.node_type == "base"
        assert ei.value.attempts == 2
        assert prov.launch_attempts == 2
        assert prov.launch_failures == 2
    finally:
        GlobalConfig.reset()


def test_read_join_line_bounds_slow_cold_start():
    """The join read is bounded by the launch grace window: EOF (daemon
    died mid-boot) returns immediately, silence returns at the bound —
    the autoscaler monitor can never hang on one cold node."""
    from ray_tpu.autoscaler import LocalSubprocessProvider

    quick_eof = subprocess.Popen(
        [sys.executable, "-c", "pass"], stdout=subprocess.PIPE, text=True)
    assert LocalSubprocessProvider._read_join_line(quick_eof, 5.0) is None
    quick_eof.wait(timeout=5)

    silent = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(30)"],
        stdout=subprocess.PIPE, text=True)
    t0 = time.monotonic()
    assert LocalSubprocessProvider._read_join_line(silent, 0.5) is None
    assert time.monotonic() - t0 < 5.0
    silent.kill()
    silent.wait(timeout=5)

    joins = subprocess.Popen(
        [sys.executable, "-c",
         "print('node x joined h:1 as client-abc', flush=True); "
         "import time; time.sleep(30)"],
        stdout=subprocess.PIPE, text=True)
    line = LocalSubprocessProvider._read_join_line(joins, 5.0)
    assert line is not None and line.strip().endswith("client-abc")
    joins.kill()
    joins.wait(timeout=5)


# -------------------------------------------------------- drain-before-reap
def test_reap_drains_borrowed_refs_before_terminate(head_proc):
    """The acceptance row: an autoscaler-managed node holding a live
    borrowed ref's BYTES is reaped — drain-before-reap offloads the
    bytes to the owning driver (object_offload + object_transfer
    re-point), and the ref keeps resolving after the process exits
    with zero ObjectLostError/OwnerDiedError (counter-asserted)."""
    from ray_tpu.autoscaler import (
        ClusterAutoscaler,
        LocalSubprocessProvider,
        NodeTypeConfig,
    )

    ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                 address=head_proc)
    w = ray_tpu._private.worker.global_worker()
    scaler = ClusterAutoscaler(
        head_proc,
        [NodeTypeConfig("base", {"CPU": 2}, min_workers=2,
                        max_workers=2)],
        provider=LocalSubprocessProvider(
            head_proc, worker_mode="thread", env=_spawn_env()),
        idle_timeout_s=3600.0, update_interval_s=0.5)
    try:
        _wait_nodes(w.head_client, 2)

        @ray_tpu.remote
        def big(i):
            return bytes(200_000) + bytes([i])

        ref = big.remote(9)
        router = w.remote_router
        ob = ref.object_id.binary()
        deadline = time.monotonic() + 30
        holder = None
        while time.monotonic() < deadline:
            with router._lock:
                holder = router._oid_owner.get(ob)
            if holder is not None:
                break
            time.sleep(0.05)
        assert holder is not None, "result never reported"

        victim = None
        with scaler._lock:
            for m in scaler._managed:
                if m.client_id == holder:
                    victim = m
        assert victim is not None

        before = router.offloaded_objects
        scaler._terminate(victim, drain=True)  # the idle-reap path
        summary = scaler.summary()
        assert summary["drained_nodes"] == 1
        assert summary["drain_transferred_objects"] >= 1
        assert router.offloaded_objects > before
        assert w.store.is_ready(ref.object_id), \
            "drain did not offload the bytes to the owner"
        # The victim process is gone; the borrowed ref must resolve
        # from the offloaded copy — no loss, no lineage replay needed.
        val = ray_tpu.get(ref, timeout=30)
        assert val[-1] == 9 and len(val) == 200_001
        # State-API surface carries the counters.
        from ray_tpu.util import state as state_api

        summ = state_api.autoscaler_summary()
        assert summ["drained_nodes"] >= 1
        assert summ["drain_transferred_objects"] >= 1
        assert summ["launch_attempts"] >= 2
        assert summ["offloaded_objects"] >= 1
    finally:
        scaler.shutdown()
        ray_tpu.shutdown()


def test_reap_race_push_refuses_and_reroutes(head_proc):
    """Deterministic interleave for the idle-reap race: node A is
    draining but THIS driver's router does not know yet (its cordon
    check is disabled and membership is stale) — the in-flight push
    must come back as a typed 'draining' refusal, the router must
    reroute to node B, and the task completes. Counter-asserted on
    both sides."""
    ray_tpu.shutdown()
    procs = []
    try:
        env = _spawn_env()
        node_ids = []
        for _ in range(2):
            node = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.node_daemon",
                 "--address", head_proc, "--num-cpus", "2",
                 "--worker-mode", "thread"],
                stdout=subprocess.PIPE, text=True, env=env)
            procs.append(node)
            line = node.stdout.readline()
            assert "joined" in line
            node_ids.append(line.strip().rsplit(" ", 1)[-1])
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=head_proc)
        w = ray_tpu._private.worker.global_worker()
        router = w.remote_router
        _wait_nodes(w.head_client, 2)

        # Drain node A: it cordons itself and reports the refusal
        # counter back on later drains.
        report = w.head_client.node_drain(node_ids[0], timeout=5.0)
        assert report["refused"] == 0

        # The driver's router must NOT know: disable its cordon check
        # and pin the membership snapshot to the pre-drain view.
        nodes_now = w.head_client.node_list()
        for n in nodes_now:
            n.setdefault("status", {})
            n["status"] = dict(n["status"], draining=False)
        router._nodes_cache = (time.monotonic() + 3600, nodes_now)
        before = router.drain_reroutes

        from ray_tpu.util.scheduling_strategies import (
            NodeAffinitySchedulingStrategy,
        )

        node_a = next(n for n in nodes_now
                      if n["client_id"] == node_ids[0])

        @ray_tpu.remote
        def work(x):
            return x + 1

        # Soft affinity: the router deterministically targets the
        # draining node first, gets the typed refusal, and falls over
        # to node B on the reroute.
        ref = work.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_a["node_id"], soft=True)).remote(41)
        router._nodes_cache = (0.0, [])  # un-pin for the reroute
        assert ray_tpu.get(ref, timeout=60) == 42
        assert router.drain_reroutes == before + 1
        with router._lock:
            assert node_ids[0] in router._draining_nodes
        # Node-side counter round-trips through a second drain report.
        report = w.head_client.node_drain(node_ids[0], timeout=5.0)
        assert report["refused"] == 1
        # And the cordon holds: new spread tasks avoid node A.
        refs = [work.remote(i) for i in range(8)]
        assert ray_tpu.get(refs, timeout=60) == [i + 1 for i in range(8)]
        assert router.drain_reroutes == before + 1, \
            "cordoned node was chosen again"
    finally:
        ray_tpu.shutdown()
        for p in reversed(procs):
            p.kill()
            p.wait(timeout=5)


def test_drain_raced_by_second_reap(head_proc):
    """The ROADMAP item 5 race row, deterministic: two concurrent
    idle-reap passes target the SAME node. Exactly one claims and
    drains it (one drain, one terminate, one drained_nodes count); the
    loser observes the cordon and backs off; the held object's bytes
    are offloaded exactly once — no double ``object_offload``."""
    import threading

    from ray_tpu.autoscaler import (
        ClusterAutoscaler,
        LocalSubprocessProvider,
        NodeTypeConfig,
    )

    ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                 address=head_proc)
    w = ray_tpu._private.worker.global_worker()
    scaler = ClusterAutoscaler(
        head_proc,
        [NodeTypeConfig("base", {"CPU": 2}, min_workers=1,
                        max_workers=1)],
        provider=LocalSubprocessProvider(
            head_proc, worker_mode="thread", env=_spawn_env()),
        idle_timeout_s=3600.0, update_interval_s=0.5)
    try:
        _wait_nodes(w.head_client, 1)

        @ray_tpu.remote
        def big(i):
            return bytes(200_000) + bytes([i])

        ref = big.remote(7)
        router = w.remote_router
        ob = ref.object_id.binary()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            with router._lock:
                if router._oid_owner.get(ob) is not None:
                    break
            time.sleep(0.05)
        with scaler._lock:
            victim = scaler._managed[0]

        before_offloaded = router.offloaded_objects
        outcomes = []

        def reap():
            outcomes.append(scaler._terminate(victim, drain=True))

        t1 = threading.Thread(target=reap)
        t2 = threading.Thread(target=reap)
        t1.start()
        t2.start()
        t1.join(60)
        t2.join(60)
        # Exactly one pass claimed the node; the loser backed off.
        assert sorted(outcomes) == [False, True], outcomes
        summary = scaler.summary()
        assert summary["drained_nodes"] == 1
        assert summary["terminated"] == ["base"]
        assert summary["managed_nodes"] == 0
        # The bytes moved once: one offload, and the ref still resolves.
        assert router.offloaded_objects == before_offloaded + 1
        assert summary["drain_transferred_objects"] == 1
        val = ray_tpu.get(ref, timeout=30)
        assert val[-1] == 7 and len(val) == 200_001
    finally:
        scaler.shutdown()
        ray_tpu.shutdown()


def test_drain_rpc_itself_is_exactly_once(head_proc):
    """Node-side half of the race row: two CONCURRENT node_drain RPCs
    against one node (two reapers that both got past their own claim
    — e.g. two autoscalers). The first claims the cordon and runs the
    lease transfer; the second answers ``already_draining`` with the
    same counters and performs no second offload."""
    import threading

    ray_tpu.shutdown()
    procs = []
    try:
        env = _spawn_env()
        node = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.node_daemon",
             "--address", head_proc, "--num-cpus", "2",
             "--worker-mode", "thread"],
            stdout=subprocess.PIPE, text=True, env=env)
        procs.append(node)
        assert "joined" in node.stdout.readline()
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=head_proc)
        w = ray_tpu._private.worker.global_worker()
        router = w.remote_router
        live = _wait_nodes(w.head_client, 1)
        node_client = live[0]["client_id"]

        @ray_tpu.remote
        def big():
            return bytes(200_000)

        ref = big.remote()
        ob = ref.object_id.binary()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            with router._lock:
                if router._oid_owner.get(ob) is not None:
                    break
            time.sleep(0.05)
        before = router.offloaded_objects
        reports = []

        def drain():
            reports.append(dict(w.head_client.node_drain(
                node_client, timeout=10.0)))

        t1 = threading.Thread(target=drain)
        t2 = threading.Thread(target=drain)
        t1.start()
        t2.start()
        t1.join(30)
        t2.join(30)
        assert len(reports) == 2, reports
        flags = sorted(r.get("already_draining", False)
                       for r in reports)
        assert flags == [False, True], reports
        # One transfer of the one held object — never double-counted.
        assert all(r["transferred"] == 1 for r in reports
                   if not r.get("already_draining")), reports
        assert router.offloaded_objects == before + 1
        assert w.store.is_ready(ref.object_id)
        assert len(ray_tpu.get(ref, timeout=30)) == 200_000
    finally:
        ray_tpu.shutdown()
        for p in reversed(procs):
            p.kill()
            p.wait(timeout=5)


class _BrownoutProvider:
    """Provider decorator: every launch raises NodeLaunchFailedError
    while the brown-out window is closed (the cloud's capacity outage
    shape), then delegates once it lifts."""

    def __init__(self, inner):
        self.inner = inner
        self.window_open = False
        self.browned_out_launches = 0

    def launch(self, node_type):
        if not self.window_open:
            self.browned_out_launches += 1
            raise NodeLaunchFailedError(
                node_type.name, 1,
                "provider brown-out: no capacity in any zone")
        return self.inner.launch(node_type)

    def terminate(self, handle):
        return self.inner.terminate(handle)

    def poll_alive(self, handle):
        return self.inner.poll_alive(handle)

    @property
    def launch_attempts(self):
        return self.inner.launch_attempts + self.browned_out_launches

    @property
    def launch_failures(self):
        return self.inner.launch_failures + self.browned_out_launches


def test_provider_brownout_demand_preserved_until_window_lifts(head_proc):
    """The provider brown-out fault row: EVERY node launch fails for a
    window (typed NodeLaunchFailedError, counted). Demand — parked
    infeasible tasks — is preserved through the outage, and when the
    window lifts the autoscaler's next tick launches for the SAME
    demand and the episode completes."""
    from ray_tpu.autoscaler import (
        ClusterAutoscaler,
        LocalSubprocessProvider,
        NodeTypeConfig,
    )

    ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                 address=head_proc)
    prov = _BrownoutProvider(LocalSubprocessProvider(
        head_proc, worker_mode="thread", env=_spawn_env()))
    scaler = ClusterAutoscaler(
        head_proc,
        [NodeTypeConfig("base", {"CPU": 2}, min_workers=0,
                        max_workers=2)],
        provider=prov, idle_timeout_s=3600.0, update_interval_s=0.3)
    try:
        @ray_tpu.remote
        def work(x):
            return x + 1

        # Demand lands DURING the brown-out: infeasible here (0 CPUs),
        # parked and advertised to the autoscaler via heartbeats.
        refs = [work.remote(i) for i in range(4)]
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if scaler.launch_errors >= 2:
                break
            time.sleep(0.1)
        assert scaler.launch_errors >= 2, \
            "brown-out launches never surfaced typed"
        assert prov.browned_out_launches >= 2
        assert scaler.summary()["managed_nodes"] == 0
        # Demand preserved: nothing completed, nothing was dropped.
        with pytest.raises(GetTimeoutError):
            ray_tpu.get(refs[0], timeout=0.2)

        prov.window_open = True  # the outage lifts
        assert ray_tpu.get(refs, timeout=90) == [i + 1 for i in range(4)]
        summary = scaler.summary()
        assert summary["managed_nodes"] >= 1
        assert summary["launch_failures"] >= 2
        # The launch that finally succeeded is recorded as a scale
        # event with a join timestamp (cold-start SLO input).
        assert any(e.get("joined") for e in summary["scale_events"])
    finally:
        scaler.shutdown()
        ray_tpu.shutdown()


# ------------------------------------------------------- scale-to-zero wake
def test_scale_to_zero_then_wake_queues_not_sheds():
    """A deployment with min_replicas=0 drops to zero after the idle
    window; the next request WAKES it (queued, not shed) within the
    bounded wake latency, and a second request arriving MID-WAKE also
    queues (class-0 never sheds on an empty deployment)."""
    import threading

    from ray_tpu import serve

    ray_tpu.init(num_cpus=2, worker_mode="thread",
                 ignore_reinit_error=True)
    serve.start()

    @serve.deployment(name="z", num_replicas=1,
                      autoscaling_config={
                          "min_replicas": 0, "max_replicas": 2,
                          "target_ongoing_requests": 2.0,
                          "upscale_delay_s": 0.2,
                          "downscale_delay_s": 0.4},
                      max_ongoing_requests=8)
    class Echo:
        def __init__(self):
            time.sleep(0.3)  # visible wake window

        def __call__(self, x):
            return x * 2

    try:
        handle = serve.run(Echo.bind())
        assert handle.remote(3).result(timeout=30) == 6
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            st = serve.status()["z"]
            if st["replicas"] == 0 and st["target_replicas"] == 0:
                break
            time.sleep(0.1)
        st = serve.status()["z"]
        assert st["replicas"] == 0, st

        results = []
        errors = []

        def fire(x):
            try:
                results.append(handle.remote(x).result(timeout=30))
            except BaseException as exc:  # noqa: BLE001
                errors.append(exc)

        t1 = threading.Thread(target=fire, args=(5,))
        t1.start()
        time.sleep(0.05)  # second request lands MID-wake
        t2 = threading.Thread(target=fire, args=(7,))
        t2.start()
        t1.join(40)
        t2.join(40)
        assert not errors, errors
        assert sorted(results) == [10, 14]
        st = serve.status()["z"]
        assert st["wake_events"] == 1, st  # one shared wake
        assert not any(isinstance(e, RequestSheddedError)
                       for e in errors)
        reasons = [e["reason"] for e in st["scale_events"]]
        assert "idle" in reasons and "wake" in reasons
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_wake_timeout_surfaces_typed(head_proc):
    """A deployment that can never place a replica fails the waking
    request with a typed GetTimeoutError at the wake bound — not an
    unbounded hang. (Cluster-attached with zero local CPUs, so the
    replica's resource demand is genuinely infeasible.)"""
    from ray_tpu import serve

    ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                 address=head_proc)
    serve.start()
    GlobalConfig.set("serve_wake_timeout_s", 1.0)

    @serve.deployment(name="never", num_replicas=1,
                      ray_actor_options={"resources": {"nope": 1.0}})
    class Never:
        def __call__(self, x):
            return x

    try:
        handle = serve.run(Never.bind())
        t0 = time.monotonic()
        with pytest.raises(GetTimeoutError):
            handle.remote(1)
        assert time.monotonic() - t0 < 10.0
    finally:
        GlobalConfig.reset()
        serve.shutdown()
        ray_tpu.shutdown()


def test_no_ref_loss_error_types_in_drain_paths():
    """Belt-and-braces: the drain plane's typed vocabulary exists and
    is distinct (the episode assertion counts on exact types)."""
    from ray_tpu.exceptions import NodeDrainingError

    exc = NodeDrainingError("node-1")
    assert "node-1" in str(exc)
    assert not isinstance(exc, (ObjectLostError, OwnerDiedError))
    launch = NodeLaunchFailedError("t", 3)
    assert launch.attempts == 3 and launch.node_type == "t"
