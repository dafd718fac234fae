"""Multi-machine cluster tests: a real head process plus two real node
daemon OS processes with distinct resource specs (reference test model:
multi-raylet cluster tests — spillover scheduling, cross-node object pull,
node-death lineage re-execution; SURVEY.md §4)."""

import os
import subprocess
import sys
import time

import pytest

import ray_tpu

pytestmark = pytest.mark.slow  # full-cluster / env-build suite


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


def _spawn_node(address, num_cpus, resources, worker_mode="thread"):
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.node_daemon",
         "--address", address, "--num-cpus", str(num_cpus),
         "--resources", resources, "--worker-mode", worker_mode],
        stdout=subprocess.PIPE, text=True, env=_spawn_env())
    line = proc.stdout.readline()  # blocks until the node has joined
    assert "joined" in line
    return proc


@pytest.fixture(params=["thread", "process"])
def two_node_cluster(request, tmp_path):
    """head + node1 {CPU:1, n1:1} + node2 {CPU:1, n2:1}, driver with no
    local CPUs so every task must cross onto a node process. Runs under
    BOTH execution planes: thread-mode daemons and the default
    process-worker plane (shm staging + kill -9 isolation), so
    daemon-hosted worker processes execute across the machine boundary
    in CI."""
    mode = request.param
    os.environ["RAY_TPU_HEAD_CLIENT_TIMEOUT_S"] = "2.0"
    ray_tpu.shutdown()
    head, address = _spawn_head(tmp_path)
    node1 = node2 = None
    try:
        node1 = _spawn_node(address, 1, '{"n1": 1}', mode)
        node2 = _spawn_node(address, 1, '{"n2": 1}', mode)
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=address)
        yield {"address": address, "head": head,
               "node1": node1, "node2": node2}
    finally:
        ray_tpu.shutdown()
        for p in (node1, node2, head):
            if p is not None:
                p.kill()
                p.wait(timeout=5)
        os.environ.pop("RAY_TPU_HEAD_CLIENT_TIMEOUT_S", None)


def test_membership_lists_both_nodes(two_node_cluster):
    w = ray_tpu._private.worker.global_worker()
    info = w.head_client.cluster_info()
    assert len(info["nodes"]) == 2


def test_remote_execution_and_object_pull(two_node_cluster):
    """A task the driver cannot run (no local CPU, node-only resource)
    executes on node 2; its result bytes pull back head-relayed."""
    driver_pid = os.getpid()

    @ray_tpu.remote(resources={"n2": 0.1})
    def whoami(payload):
        import os as _os

        return (_os.getpid(), payload * 2)

    pid, doubled = ray_tpu.get(whoami.remote(21), timeout=60)
    assert pid != driver_pid
    assert doubled == 42


def test_spill_spreads_across_nodes(two_node_cluster):
    """A burst wider than one node's CPUs spreads over both daemons."""

    @ray_tpu.remote
    def slow_pid():
        import os as _os
        import time as _time

        _time.sleep(0.3)
        return _os.getpid()

    refs = [slow_pid.remote() for _ in range(6)]
    pids = set(ray_tpu.get(refs, timeout=120))
    assert len(pids) >= 2, f"expected spill across nodes, got {pids}"


def test_chained_remote_tasks_pull_node_to_node(two_node_cluster):
    """Task B on node 2 consumes task A's output produced on node 1: the
    bytes move node-to-node, not via the driver — the driver never pulls
    A's bytes (they travel as a pull-ref resolved on node 2; only the
    final result it actually get()s may cross to it)."""
    w = ray_tpu._private.worker.global_worker()
    pulled = []
    orig_pull = w.head_client._peers.pull

    def _spy(addr, oid_bin):
        pulled.append(bytes(oid_bin))
        return orig_pull(addr, oid_bin)

    w.head_client._peers.pull = _spy

    @ray_tpu.remote(resources={"n1": 0.1})
    def produce():
        return list(range(100))

    @ray_tpu.remote(resources={"n2": 0.1})
    def consume(xs):
        return sum(xs)

    try:
        a = produce.remote()
        total = ray_tpu.get(consume.remote(a), timeout=60)
    finally:
        w.head_client._peers.pull = orig_pull
    assert total == sum(range(100))
    assert a.object_id.binary() not in pulled, \
        "driver pulled the intermediate's bytes"


def test_large_object_chunked_pull(two_node_cluster):
    """Results above the pull chunk size arrive intact (chunked relay)."""
    import numpy as np

    @ray_tpu.remote(resources={"n1": 0.1})
    def big():
        import numpy as _np

        return _np.arange(6_000_000, dtype=_np.uint8)  # > one 4MiB chunk

    arr = ray_tpu.get(big.remote(), timeout=120)
    assert arr.shape == (6_000_000,)
    assert int(arr[-1]) == (6_000_000 - 1) % 256
    assert np.all(arr[:256] == np.arange(256, dtype=np.uint8))


def test_node_kill_lineage_reexecution(two_node_cluster, tmp_path):
    """SIGKILL the node holding a not-yet-pulled result: the driver's get
    re-executes the task from lineage on the surviving node."""
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    cluster = two_node_cluster
    w = ray_tpu._private.worker.global_worker()
    nodes = w.head_client.node_list()
    # Find node2's node_id (it owns the "n2" resource).
    node2_entry = next(n for n in nodes if "n2" in (n["resources"] or {}))
    marker = str(tmp_path / "runs.log")

    @ray_tpu.remote(scheduling_strategy=NodeAffinitySchedulingStrategy(
        node2_entry["node_id"], soft=True))
    def tracked():
        with open(marker, "a") as f:
            f.write("run\n")
        # Above the inline cap: the bytes stay on the producing node
        # (small results would ride task_done to the driver and survive
        # the kill — this test needs a result that actually dies).
        return "alive" * 50_000

    ref = tracked.remote()
    # Wait until the task has completed ON node2 (task_done seen) without
    # pulling the result to the driver.
    router = w.remote_router
    deadline = time.monotonic() + 30
    tid = ref.object_id.task_id()
    while time.monotonic() < deadline:
        ev = router._done.get(tid)
        if ev is not None and ev.is_set():
            break
        time.sleep(0.1)
    else:
        pytest.fail("task never completed on node2")
    assert not w.store.is_ready(ref.object_id)

    cluster["node2"].kill()  # SIGKILL: result bytes die with the node
    cluster["node2"].wait(timeout=5)

    # get() must recover: pull fails -> lineage re-execution on node1.
    assert ray_tpu.get(ref, timeout=60) == "alive" * 50_000
    with open(marker) as f:
        runs = f.read().count("run")
    assert runs == 2, f"expected re-execution (2 runs), saw {runs}"


def test_inflight_tasks_reroute_off_dead_node(two_node_cluster):
    """A long task in flight on a killed node re-routes to the survivor."""
    cluster = two_node_cluster

    @ray_tpu.remote
    def eventually():
        import time as _time

        _time.sleep(1.0)
        return "done"

    # Saturate node1 so the next task lands on node2.
    pin = [eventually.remote() for _ in range(2)]
    time.sleep(0.3)
    victim = eventually.remote()
    time.sleep(0.2)
    cluster["node2"].kill()
    cluster["node2"].wait(timeout=5)
    results = ray_tpu.get(pin + [victim], timeout=120)
    assert results == ["done"] * 3


def test_ray_client_mode_routes_to_cluster(tmp_path):
    """`init(address="ray://...")` is the thin-client role: the local
    process keeps zero execution capacity and every task lands on a node
    daemon (reference: ray client semantics)."""
    ray_tpu.shutdown()
    head, address = _spawn_head(tmp_path)
    node = None
    try:
        node = _spawn_node(address, 2, '{"n1": 1}')
        ray_tpu.init(address=f"ray://{address}")
        w = ray_tpu._private.worker.global_worker()
        assert w.client_mode
        assert w.resource_pool.total.get("CPU", 0) == 0

        @ray_tpu.remote
        def where():
            return os.getpid()

        pids = set(ray_tpu.get([where.remote() for _ in range(4)],
                               timeout=60))
        assert os.getpid() not in pids  # nothing ran in the client
    finally:
        ray_tpu.shutdown()
        for p in (node, head):
            if p is not None:
                p.kill()
                p.wait(timeout=5)


def test_ray_client_mode_without_nodes_errors(tmp_path):
    """A client-mode task with no cluster capacity fails loudly instead
    of hanging on an infeasible local queue."""
    from ray_tpu.exceptions import RayTpuError

    ray_tpu.shutdown()
    head, address = _spawn_head(tmp_path)
    try:
        ray_tpu.init(address=f"ray://{address}")

        @ray_tpu.remote
        def f():
            return 1

        with pytest.raises(RayTpuError, match="client-mode"):
            f.remote()
    finally:
        ray_tpu.shutdown()
        head.kill()
        head.wait(timeout=5)


def test_remote_task_env_vars_runtime_env(two_node_cluster):
    """runtime_env crosses the push boundary: env_vars apply in the
    node-side execution (the pip path shares this plumbing and is
    covered by tests/test_runtime_env_pip.py locally)."""

    @ray_tpu.remote(resources={"n1": 0.1},
                    runtime_env={"env_vars": {"RTE_PROBE": "crossed"}})
    def read_env():
        import os as _os

        return _os.environ.get("RTE_PROBE")

    assert ray_tpu.get(read_env.remote(), timeout=60) == "crossed"


def test_direct_peer_object_pull(two_node_cluster):
    """Object bytes move peer-to-peer through the owner's object server
    (the ObjectManager data plane); the head only resolves the location."""

    @ray_tpu.remote(resources={"n1": 0.1})
    def make():
        return {"blob": list(range(50_000))}

    ref = make.remote()
    out = ray_tpu.get(ref, timeout=60)
    assert out["blob"][-1] == 49_999
    w = ray_tpu._private.worker.global_worker()
    # Ownership directory: the driver resolves the holder from its OWN
    # location table (owner_table_pulls); head-located direct pulls
    # (direct_pulls) cover the pre-ownership/fallback directory path.
    p2p = w.remote_router.owner_table_pulls + w.head_client.direct_pulls
    assert p2p > 0, (
        w.remote_router.owner_table_pulls, w.head_client.direct_pulls,
        w.head_client.relayed_pulls)


def test_peer_pull_falls_back_to_relay(two_node_cluster):
    """A dead/unreachable peer address degrades to the head-relayed
    chunked pull instead of failing the get."""
    w = ray_tpu._private.worker.global_worker()

    @ray_tpu.remote(resources={"n2": 0.1})
    def make():
        return "via-relay"

    ref = make.remote()
    # Poison the peer pool: every direct attempt (including the bounded
    # pull_retrying reconnect loop) fails as a transport error, so the
    # pull must exhaust its attempts and take the relay path.
    orig = w.head_client._peers._pull_attempt
    w.head_client._peers._pull_attempt = \
        lambda addr, oid: ("error", None)
    try:
        before = w.head_client.relayed_pulls
        assert ray_tpu.get(ref, timeout=60) == "via-relay"
        assert w.head_client.relayed_pulls > before
    finally:
        w.head_client._peers._pull_attempt = orig
