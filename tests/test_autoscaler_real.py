"""Real-node autoscaling tests: a ClusterAutoscaler launching genuine
node-daemon OS processes from head-observed demand and reaping them when
idle (reference model: StandardAutoscaler + NodeProvider over the GCS
resource load; SURVEY §2.7 / §4 FakeMultiNodeProvider — except the nodes
are real)."""

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


@pytest.fixture
def head(tmp_path):
    os.environ["RAY_TPU_HEAD_CLIENT_TIMEOUT_S"] = "2.0"
    ray_tpu.shutdown()
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.head_service",
         "--port", "0", "--state", str(tmp_path / "state.log")],
        stdout=subprocess.PIPE, text=True, env=_spawn_env())
    address = proc.stdout.readline().strip().rsplit(" ", 1)[-1]
    yield address
    ray_tpu.shutdown()
    proc.kill()
    proc.wait(timeout=5)
    os.environ.pop("RAY_TPU_HEAD_CLIENT_TIMEOUT_S", None)


def test_demand_spawns_real_node_then_idles_down(head):
    """A burst of tasks demanding a resource no node offers parks on the
    driver, the autoscaler launches a REAL node daemon that fits, the
    router routes the parked work there, and the idle timeout terminates
    the node afterwards."""
    from ray_tpu.autoscaler import (
        ClusterAutoscaler,
        LocalSubprocessProvider,
        NodeTypeConfig,
    )

    ray_tpu.init(num_cpus=1, worker_mode="thread", address=head)
    scaler = ClusterAutoscaler(
        head,
        [NodeTypeConfig("accel", {"CPU": 1, "accel": 1}, max_workers=2)],
        provider=LocalSubprocessProvider(
            head, worker_mode="thread", env=_spawn_env()),
        idle_timeout_s=2.0, update_interval_s=0.25)
    try:
        assert scaler.num_nodes_of_type("accel") == 0  # min_workers=0

        @ray_tpu.remote(resources={"accel": 1})
        def probe():
            import os as _os

            return _os.getpid()

        refs = [probe.remote() for _ in range(3)]
        pids = set(ray_tpu.get(refs, timeout=120))
        assert pids and os.getpid() not in pids  # ran on launched node
        assert scaler.launched.count("accel") >= 1
        assert scaler.num_nodes_of_type("accel") >= 1
        # The head's membership saw the real node.
        w = ray_tpu._private.worker.global_worker()
        assert any("accel" in (n["resources"] or {})
                   for n in w.head_client.node_list())

        # Idle scale-down back to zero.
        deadline = time.monotonic() + 40
        while time.monotonic() < deadline \
                and scaler.num_nodes_of_type("accel") > 0:
            time.sleep(0.5)
        assert scaler.num_nodes_of_type("accel") == 0
        assert scaler.terminated.count("accel") >= 1
    finally:
        scaler.shutdown()
        ray_tpu.shutdown()


def test_backlog_pressure_scales_up(head):
    """Plain CPU tasks queued beyond an existing node's capacity launch
    another node even though their shape 'fits' the overloaded node's
    totals."""
    from ray_tpu.autoscaler import (
        ClusterAutoscaler,
        LocalSubprocessProvider,
        NodeTypeConfig,
    )

    ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                 address=head)
    scaler = ClusterAutoscaler(
        head,
        [NodeTypeConfig("base", {"CPU": 1}, min_workers=1,
                        max_workers=3)],
        provider=LocalSubprocessProvider(
            head, worker_mode="thread", env=_spawn_env()),
        idle_timeout_s=30.0, update_interval_s=0.25)
    try:
        assert scaler.num_nodes_of_type("base") == 1

        @ray_tpu.remote
        def slow():
            import time as _time

            _time.sleep(0.6)
            return 1

        refs = [slow.remote() for _ in range(10)]
        deadline = time.monotonic() + 40
        while time.monotonic() < deadline \
                and scaler.num_nodes_of_type("base") < 2:
            time.sleep(0.25)
        assert scaler.num_nodes_of_type("base") >= 2, scaler.launched
        assert sum(ray_tpu.get(refs, timeout=120)) == 10
    finally:
        scaler.shutdown()
        ray_tpu.shutdown()


def test_crashed_managed_node_replaced(head):
    """A managed daemon that dies is reaped AND replaced back up to
    min_workers."""
    from ray_tpu.autoscaler import (
        ClusterAutoscaler,
        LocalSubprocessProvider,
        NodeTypeConfig,
    )

    ray_tpu.init(num_cpus=1, worker_mode="thread", address=head)
    scaler = ClusterAutoscaler(
        head,
        [NodeTypeConfig("base", {"CPU": 1}, min_workers=1,
                        max_workers=2)],
        provider=LocalSubprocessProvider(
            head, worker_mode="thread", env=_spawn_env()),
        idle_timeout_s=30.0, update_interval_s=0.25)
    try:
        assert scaler.num_nodes_of_type("base") == 1
        with scaler._lock:
            victim = scaler._managed[0]
        victim.handle["proc"].kill()
        victim.handle["proc"].wait(timeout=5)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            with scaler._lock:
                alive = [m for m in scaler._managed if m is not victim]
            if alive and scaler.provider.poll_alive(alive[0].handle):
                break
            time.sleep(0.25)
        assert scaler.num_nodes_of_type("base") == 1
        with scaler._lock:
            assert scaler._managed[0] is not victim
    finally:
        scaler.shutdown()
        ray_tpu.shutdown()


def test_min_workers_floor_respected(head):
    """min_workers launches eagerly and the idle reaper never goes
    below the floor."""
    from ray_tpu.autoscaler import (
        ClusterAutoscaler,
        LocalSubprocessProvider,
        NodeTypeConfig,
    )

    ray_tpu.init(num_cpus=1, worker_mode="thread", address=head)
    scaler = ClusterAutoscaler(
        head,
        [NodeTypeConfig("base", {"CPU": 1}, min_workers=1, max_workers=2)],
        provider=LocalSubprocessProvider(
            head, worker_mode="thread", env=_spawn_env()),
        idle_timeout_s=1.0, update_interval_s=0.25)
    try:
        assert scaler.num_nodes_of_type("base") == 1
        time.sleep(3.5)  # several idle periods
        assert scaler.num_nodes_of_type("base") == 1  # floor holds
    finally:
        scaler.shutdown()
        ray_tpu.shutdown()
