"""Cluster-wide actor placement tests: actors hosted on real node-daemon
OS processes (reference test model: GCS actor scheduling across raylets —
resource placement, node-death restart, named cross-driver resolution,
library spread; SURVEY.md §2.1/§3.3)."""

import os
import subprocess
import sys
import textwrap
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


def _spawn_node(address, num_cpus, resources):
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.node_daemon",
         "--address", address, "--num-cpus", str(num_cpus),
         "--resources", resources, "--worker-mode", "thread"],
        stdout=subprocess.PIPE, text=True, env=_spawn_env())
    line = proc.stdout.readline()
    assert "joined" in line
    return proc


@pytest.fixture
def cluster(tmp_path):
    """head + node1 {CPU:1, n1:1} + node2 {CPU:1, n2:1}; the driver keeps
    zero CPUs so placement decisions are observable."""
    os.environ["RAY_TPU_HEAD_CLIENT_TIMEOUT_S"] = "2.0"
    ray_tpu.shutdown()
    head, address = _spawn_head(tmp_path)
    node1 = node2 = None
    try:
        node1 = _spawn_node(address, 1, '{"n1": 1}')
        node2 = _spawn_node(address, 1, '{"n2": 1}')
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


@ray_tpu.remote
class Counter:
    def __init__(self, start=0):
        self.n = start

    def add(self, k=1):
        self.n += k
        return self.n

    def total(self):
        return self.n

    def pid(self):
        import os as _os

        return _os.getpid()


def test_actor_places_on_resource_node(cluster):
    """An actor demanding a node-only resource is hosted BY that node
    daemon's process tree (PID proof), and the head's placement
    directory records the hosting node."""
    a = Counter.options(resources={"n2": 1}).remote(10)
    assert ray_tpu.get(a.add.remote(5), timeout=60) == 15
    pid = ray_tpu.get(a.pid.remote(), timeout=60)
    assert pid == cluster["node2"].pid  # thread-plane daemon hosts in-proc
    assert pid != os.getpid()
    w = ray_tpu._private.worker.global_worker()
    rec = w.head_client.actor_locate(a._actor_id.binary())
    assert rec is not None and rec["alive"]
    nodes = w.head_client.node_list()
    node2 = next(n for n in nodes if "n2" in (n["resources"] or {}))
    assert rec["node"] == node2["client_id"]


def test_actor_spread_lands_on_multiple_nodes(cluster):
    """SPREAD round-robins a group of actors across the cluster."""
    actors = [Counter.options(scheduling_strategy="SPREAD").remote()
              for _ in range(4)]
    pids = set(ray_tpu.get([a.pid.remote() for a in actors], timeout=60))
    daemon_pids = {cluster["node1"].pid, cluster["node2"].pid}
    assert pids & daemon_pids, pids
    assert len(pids) >= 2, pids


def test_actor_method_pull_ref_args(cluster):
    """A ref produced on node 1 feeds an actor on node 2 as a pull-ref:
    the bytes move node-to-node — the driver never pulls them (the ref
    arg resolves on the consuming node; only results it get()s may
    cross to it)."""
    w = ray_tpu._private.worker.global_worker()
    pulled = []
    orig_pull = w.head_client._peers.pull

    def _spy(addr, oid_bin):
        pulled.append(bytes(oid_bin))
        return orig_pull(addr, oid_bin)

    w.head_client._peers.pull = _spy

    @ray_tpu.remote(resources={"n1": 0.1})
    def produce():
        return list(range(1000))

    try:
        ref = produce.remote()
        a = Counter.options(resources={"n2": 1}).remote()

        # Define a method call that consumes the ref: Counter.add takes k.
        @ray_tpu.remote(resources={"n2": 0.1})
        def check(xs):
            return sum(xs)

        assert ray_tpu.get(check.remote(ref), timeout=60) == \
            sum(range(1000))
        # Ref into an actor method too (value resolves host-side).
        out = ray_tpu.get(a.add.remote(ray_tpu.put(7)), timeout=60)
        assert out == 7
    finally:
        w.head_client._peers.pull = orig_pull
    assert ref.object_id.binary() not in pulled, \
        "driver pulled the intermediate's bytes"


def test_actor_ordering_and_state(cluster):
    """Method calls execute in submission order against real state."""
    a = Counter.options(resources={"n1": 1}).remote()
    refs = [a.add.remote() for _ in range(20)]
    assert ray_tpu.get(refs[-1], timeout=60) == 20
    assert ray_tpu.get(a.total.remote(), timeout=60) == 20


def test_actor_node_kill_restarts_on_survivor(cluster):
    """SIGKILL the hosting node: in-flight calls fail, the actor
    restarts with FRESH state on the surviving node (max_restarts
    budget), and the placement directory re-resolves."""
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    w = ray_tpu._private.worker.global_worker()
    nodes = w.head_client.node_list()
    node2 = next(n for n in nodes if "n2" in (n["resources"] or {}))
    a = Counter.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(
            node2["node_id"]),
        max_restarts=1).remote()
    assert ray_tpu.get(a.add.remote(5), timeout=60) == 5
    pid_before = ray_tpu.get(a.pid.remote(), timeout=60)
    assert pid_before == cluster["node2"].pid

    cluster["node2"].kill()
    cluster["node2"].wait(timeout=5)

    # The router watcher notices the death (2s heartbeat timeout + tick),
    # restarts on node1; the first post-restart call sees fresh state.
    deadline = time.monotonic() + 30
    value = None
    while time.monotonic() < deadline:
        try:
            value = ray_tpu.get(a.add.remote(1), timeout=10)
            break
        except Exception:
            time.sleep(0.5)
    assert value == 1, f"expected fresh state after restart, got {value}"
    pid_after = ray_tpu.get(a.pid.remote(), timeout=30)
    assert pid_after == cluster["node1"].pid
    rec = w.head_client.actor_locate(a._actor_id.binary())
    assert rec is not None and rec["alive"]


def test_named_actor_from_second_driver_direct(cluster, tmp_path):
    """Another driver resolves a placed named actor by name and calls it
    DIRECT to the hosting node (borrower path) — shared state proves
    both drivers hit the same instance."""
    a = Counter.options(name="shared-counter",
                        resources={"n1": 1}).remote(100)
    assert ray_tpu.get(a.add.remote(1), timeout=60) == 101

    script = textwrap.dedent(f"""
        import ray_tpu
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address={cluster['address']!r})
        h = ray_tpu.get_actor("shared-counter")
        print("RESULT", ray_tpu.get(h.add.remote(10), timeout=60))
        ray_tpu.shutdown()
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=_spawn_env(), timeout=120)
    assert "RESULT 111" in out.stdout, (out.stdout, out.stderr)
    # The shared instance really advanced.
    assert ray_tpu.get(a.total.remote(), timeout=60) == 111


def test_actor_handle_crosses_into_task(cluster):
    """An ActorHandle pickled into a task running on ANOTHER node
    resolves through the placement directory and calls direct."""
    a = Counter.options(resources={"n2": 1}).remote()

    @ray_tpu.remote(resources={"n1": 0.1})
    def poke(handle, k):
        return ray_tpu.get(handle.add.remote(k), timeout=60)

    assert ray_tpu.get(poke.remote(a, 4), timeout=120) == 4
    assert ray_tpu.get(a.total.remote(), timeout=60) == 4


def test_kill_remote_actor(cluster):
    a = Counter.options(resources={"n1": 1}).remote()
    assert ray_tpu.get(a.add.remote(), timeout=60) == 1
    ray_tpu.kill(a)
    from ray_tpu.exceptions import ActorDiedError, RayActorError

    with pytest.raises((ActorDiedError, RayActorError)):
        ray_tpu.get(a.add.remote(), timeout=30)
    w = ray_tpu._private.worker.global_worker()
    assert w.head_client.actor_locate(a._actor_id.binary()) is None


def test_actor_on_process_plane_node(tmp_path):
    """On a process-plane daemon the hosted actor lives in a dedicated
    WORKER process (not the daemon itself) — kill -9 isolation holds
    across the machine boundary."""
    os.environ["RAY_TPU_HEAD_CLIENT_TIMEOUT_S"] = "2.0"
    ray_tpu.shutdown()
    head, address = _spawn_head(tmp_path)
    node = None
    try:
        node = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.node_daemon",
             "--address", address, "--num-cpus", "1",
             "--resources", '{"n1": 1}', "--worker-mode", "process"],
            stdout=subprocess.PIPE, text=True, env=_spawn_env())
        assert "joined" in node.stdout.readline()
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=address)
        a = Counter.options(resources={"n1": 1}, max_restarts=1).remote()
        assert ray_tpu.get(a.add.remote(3), timeout=120) == 3
        pid = ray_tpu.get(a.pid.remote(), timeout=60)
        assert pid not in (os.getpid(), node.pid)  # dedicated process
        # kill -9 the actor's worker process: the node-local restart
        # policy respawns it with fresh state on the same node.
        os.kill(pid, 9)
        deadline = time.monotonic() + 30
        value = None
        while time.monotonic() < deadline:
            try:
                value = ray_tpu.get(a.add.remote(1), timeout=10)
                break
            except Exception:
                time.sleep(0.5)
        assert value == 1  # fresh state
        pid2 = ray_tpu.get(a.pid.remote(), timeout=30)
        assert pid2 != pid and pid2 not in (os.getpid(), node.pid)
    finally:
        ray_tpu.shutdown()
        for p in (node, head):
            if p is not None:
                p.kill()
                p.wait(timeout=5)
        os.environ.pop("RAY_TPU_HEAD_CLIENT_TIMEOUT_S", None)


def test_serve_replicas_spread_across_nodes(cluster):
    """serve.run with multiple replicas places them across both node
    daemons; routed calls hit more than one machine."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=4)
    class Who:
        def __call__(self):
            import os as _os

            return _os.getpid()

    try:
        handle = serve.run(Who.bind())
        pids = set()
        for _ in range(24):
            pids.add(handle.remote().result(timeout=60))
        daemon_pids = {cluster["node1"].pid, cluster["node2"].pid}
        assert pids & daemon_pids, pids
        assert len(pids) >= 2, pids
    finally:
        serve.shutdown()


def test_trainer_workers_cross_node(cluster):
    """A 2-worker JaxTrainer DP run lands one worker per node (the
    driver has no CPU capacity), with the KV-rendezvous collective
    crossing the machine boundary."""
    import numpy as np

    from ray_tpu import collective
    from ray_tpu.train import JaxTrainer, ScalingConfig, session

    def loop():
        ctx = session.get_context()
        pid_sum = collective.allreduce(
            np.array([os.getpid()], dtype=np.int64),
            group_name=ctx.collective_group)
        session.report({"rank": ctx.world_rank,
                        "pid": os.getpid(),
                        "pid_sum": int(pid_sum[0])})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(
            num_workers=2, resources_per_worker={"CPU": 0.5}))
    result = trainer.fit()
    # Rank 0's report carries the allreduced pid sum: both workers'
    # pids are daemon pids and they differ (one worker per node).
    pid_sum = result.metrics["pid_sum"]
    assert pid_sum == cluster["node1"].pid + cluster["node2"].pid, (
        result.metrics, cluster["node1"].pid, cluster["node2"].pid)
