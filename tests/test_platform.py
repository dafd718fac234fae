"""Platform surface tests: placement groups (single node), state API,
metrics/Prometheus, timeline, runtime_env, job submission, CLI."""

import json
import os
import sys
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.util import placement_group, remove_placement_group
from ray_tpu.util import metrics as rm


@pytest.fixture(autouse=True)
def _rt(ray_start_regular):
    yield


def test_placement_group_single_node_reserve_release():
    pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="PACK")
    assert pg.wait(5)
    avail = ray_tpu.available_resources()
    assert avail["CPU"] == 2.0  # 4 total - 2 reserved
    remove_placement_group(pg)
    assert ray_tpu.available_resources()["CPU"] == 4.0


def test_placement_group_infeasible_raises():
    with pytest.raises(ValueError):
        placement_group([{"CPU": 100}])


def test_state_api_lists():
    from ray_tpu.util import state

    @ray_tpu.remote
    def f():
        return 1

    refs = [f.remote() for _ in range(5)]
    ray_tpu.get(refs)

    @ray_tpu.remote
    class A:
        def ping(self):
            return "pong"

    a = A.remote()
    ray_tpu.get(a.ping.remote())

    tasks = state.list_tasks()
    assert any(t.name == "f" and t.state == "FINISHED" for t in tasks)
    actors = state.list_actors()
    assert any(x.class_name == "A" and x.state == "ALIVE" for x in actors)
    objs = state.list_objects()
    assert len(objs) >= 5
    summary = state.summarize_tasks()
    assert summary.get("FINISHED", 0) >= 5
    filtered = state.list_tasks(filters=[("state", "=", "FINISHED")])
    assert all(t.state == "FINISHED" for t in filtered)


def test_timeline_chrome_trace():
    from ray_tpu.util.state import get_timeline

    @ray_tpu.remote
    def traced():
        time.sleep(0.01)
        return 1

    ray_tpu.get([traced.remote() for _ in range(3)])
    # get() returns when outputs land; the FINISHED event records a hair
    # later on the executor thread — poll briefly.
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        trace = get_timeline()
        if len(trace) >= 3:
            break
        time.sleep(0.05)
    assert len(trace) >= 3
    ev = trace[0]
    assert ev["ph"] == "X" and "ts" in ev and "dur" in ev


def test_metrics_prometheus_export():
    rm.clear_registry()
    c = rm.Counter("test_requests_total", "requests", tag_keys=("route",))
    c.inc(3, tags={"route": "/a"})
    c.inc(1, tags={"route": "/b"})
    g = rm.Gauge("test_inflight", "in flight")
    g.set(7)
    h = rm.Histogram("test_latency_s", "latency", boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = rm.export_prometheus()
    assert 'test_requests_total{route="/a"} 3.0' in text
    assert "test_inflight 7.0" in text
    assert 'test_latency_s_bucket{le="0.1"} 1' in text
    assert 'test_latency_s_bucket{le="+Inf"} 3' in text
    assert "test_latency_s_count 3" in text


def test_metrics_http_endpoint():
    rm.clear_registry()
    rm.Gauge("scrape_me", "").set(42)
    host, port = rm.serve_metrics(port=0)
    try:
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=5) as r:
            body = r.read().decode()
        assert "scrape_me 42.0" in body
    finally:
        rm.stop_metrics_server()


def test_runtime_env_env_vars_and_unsupported():
    from ray_tpu.runtime_env import RuntimeEnv

    env = RuntimeEnv(env_vars={"RAY_TPU_TEST_VAR": "on"})
    assert os.environ.get("RAY_TPU_TEST_VAR") is None
    with env.applied():
        assert os.environ["RAY_TPU_TEST_VAR"] == "on"
    assert os.environ.get("RAY_TPU_TEST_VAR") is None
    with pytest.raises(ValueError):
        RuntimeEnv(conda={"dependencies": ["requests"]})


def test_job_submission_lifecycle(tmp_path):
    from ray_tpu.job_submission import JobStatus, JobSubmissionClient

    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=f"{sys.executable} -c \"print('job ran ok')\"")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if client.get_job_status(job_id) in (JobStatus.SUCCEEDED,
                                             JobStatus.FAILED):
            break
        time.sleep(0.1)
    assert client.get_job_status(job_id) == JobStatus.SUCCEEDED
    assert "job ran ok" in client.get_job_logs(job_id)

    bad = client.submit_job(
        entrypoint=f"{sys.executable} -c \"raise SystemExit(3)\"")
    while client.get_job_status(bad) == JobStatus.RUNNING:
        time.sleep(0.1)
    assert client.get_job_status(bad) == JobStatus.FAILED


def test_cli_status_and_list(capsys):
    from ray_tpu.scripts.cli import main

    @ray_tpu.remote
    def f():
        return 1

    ray_tpu.get(f.remote())
    main(["status"])
    out = capsys.readouterr().out
    data = json.loads(out)
    assert "cluster_resources" in data and "tasks" in data
    # The FINISHED event lands a hair after get() returns — poll briefly.
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        main(["list", "tasks", "--limit", "5"])
        out = capsys.readouterr().out
        if "FINISHED" in out:
            break
        time.sleep(0.05)
    assert "FINISHED" in out


def test_device_profile_trace(tmp_path):
    """xplane capture: a jitted computation inside profile_trace produces
    TensorBoard-loadable trace files with our annotations."""
    import jax.numpy as jnp

    from ray_tpu.util.profiling import annotate, profile_trace, trace_files

    logdir = str(tmp_path / "trace")
    with profile_trace(logdir):
        with annotate("ray_tpu_test_span"):
            x = jnp.arange(1024.0)
            (x * 2 + 1).sum().block_until_ready()
    files = trace_files(logdir)
    assert files, "no .xplane.pb produced"


def test_a_capture_has_a_clock_and_the_programs_spans_on_it(tmp_path):
    """``profile_trace`` leaves the epoch of the capture's start beside the
    capture, and a program built inside the block comes back from
    ``host_spans`` on the capture's own axis, inside its bounds; one built
    before the block does not come back."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.util.profiling import (CLOCK_FILE, build_log, host_spans,
                                        profile_trace)

    x = jnp.arange(96.0)

    def built_before_the_capture(v):
        return v * 5 - 2

    def built_in_the_capture(v):
        return (v * 3 + 1).sum()

    build_log()                         # the log listens from here on
    jax.jit(built_before_the_capture)(x).block_until_ready()
    logdir = str(tmp_path / "trace")
    before = time.time_ns()
    with profile_trace(logdir):
        jax.jit(built_in_the_capture)(x).block_until_ready()
    after = time.time_ns()
    with open(os.path.join(logdir, CLOCK_FILE)) as f:
        clock = json.load(f)
    assert before <= clock["epoch_ns_at_start"] \
        <= clock["epoch_ns_at_stop"] <= after
    length = clock["epoch_ns_at_stop"] - clock["epoch_ns_at_start"]
    spans = host_spans(logdir)
    mine = [s for s in spans if s[0] == "build:jit(built_in_the_capture)"]
    assert len(mine) == 1
    _, start_ns, duration_ns = mine[0]
    assert 0 <= start_ns and duration_ns > 0
    assert start_ns + duration_ns <= length
    assert all(s[1] < length and s[1] + s[2] > 0 for s in spans)
    assert "jit(built_before_the_capture)" in {
        r["name"] for r in build_log()}
    assert "build:jit(built_before_the_capture)" not in {
        s[0] for s in spans}
    assert [s[1] for s in spans] == sorted(s[1] for s in spans)
