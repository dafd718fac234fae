"""Distributed-tracing plane tests.

Fast units: context inject/extract round-trips, the span ring bound,
the TaskEventBuffer terminal-state eviction bound, the object-pull
``meta`` frame shape with and without tracing, and OFF-mode inertness
(zero spans, no payload keys, no extra frame elements). The e2e suite
spins a real head + two node daemons (process worker mode) and proves
ONE trace stitches across driver → head-attached daemons → worker
processes, that node task events ship home on existing completion
batches (cluster ``list_tasks`` with zero new steady-state head RPCs),
and that the cluster metrics scrape carries node-tagged series.
"""

import os
import subprocess
import sys
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private import tracing
from ray_tpu._private.ids import TaskID
from ray_tpu._private.task_events import TaskEventBuffer

_BASE_X = TaskID(b"x" * 24)
_BASE_Y = TaskID(b"y" * 24)
_BASE_A = TaskID(b"a" * 24)
_BASE_B = TaskID(b"b" * 24)
_BASE_C = TaskID(b"c" * 24)


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracing.uninstall()
    yield
    tracing.uninstall()


# ---------------------------------------------------------------- fast units
def test_off_mode_is_inert():
    assert not tracing.active()
    assert tracing.inject() is None
    assert tracing.extract(("a", "b")) is None
    assert tracing.local_spans() == []
    assert tracing.begin("x") is None
    tracing.finish(None)  # no-op
    tracing.event("x")    # dropped silently
    assert tracing.new_trace() is None
    assert tracing.take_cold_start() is None
    with tracing.start_span("y") as s:
        assert s is None


def test_inject_extract_roundtrip():
    tracing.install()
    with tracing.start_span("root") as s:
        wire = tracing.inject()
        assert wire == (s.ctx.trace_id, s.ctx.span_id)
        ctx = tracing.extract(wire)
        assert ctx.trace_id == s.ctx.trace_id
        assert ctx.span_id == s.ctx.span_id
        # msgpack round trip delivers tuples/bytes variants
        ctx2 = tracing.extract((wire[0].encode(), wire[1].encode()))
        assert ctx2.trace_id == s.ctx.trace_id
    assert tracing.extract(None) is None
    assert tracing.extract("garbage") is None


def test_span_ring_is_bounded():
    t = tracing.install(capacity=32)
    with tracing.start_span("root"):
        for i in range(200):
            tracing.event(f"e{i}")
    assert len(t.dump(include_dir=False)) <= 32
    assert t.spans_recorded >= 200


def test_worker_spill_file_is_bounded(tmp_path, monkeypatch):
    """A long-lived traced worker must not grow its spill file without
    bound: the file rotates at ring capacity, so on-disk spans (and the
    daemon's dump-side re-read) stay O(capacity), not O(run)."""
    monkeypatch.setenv(tracing.ENV_DIR, str(tmp_path))
    t = tracing.install(component="worker", capacity=32, spill=True)
    with tracing.start_span("root"):
        for i in range(200):
            tracing.event(f"e{i}")
    t._spill_file.flush()
    lines = sum(1 for _ in open(t._spill_path))
    assert 0 < lines <= 32
    spans = tracing._read_spill_dir(str(tmp_path), exclude_pid=None)
    assert all(s["component"] == "worker" for s in spans)


def test_nested_spans_parent_and_error_status():
    tracing.install()
    with tracing.start_span("outer") as outer:
        with pytest.raises(ValueError):
            with tracing.start_span("inner"):
                raise ValueError("boom")
    spans = {s["name"]: s for s in tracing.local_spans()}
    assert spans["inner"]["parent_id"] == outer.ctx.span_id
    assert spans["inner"]["status"] == "error"
    assert spans["outer"]["status"] == "ok"
    assert spans["inner"]["trace_id"] == spans["outer"]["trace_id"]


def test_cold_start_stash_and_env_parent(monkeypatch):
    tracing.install()
    with tracing.start_span("req") as s:
        tracing.stash_cold_start()
    ctx = tracing.take_cold_start()
    assert ctx is not None and ctx.trace_id == s.ctx.trace_id
    assert tracing.take_cold_start() is None  # one-shot
    monkeypatch.setenv(tracing.ENV_PARENT, s.ctx.encode())
    parent = tracing.cold_start_parent()
    assert parent.trace_id == s.ctx.trace_id
    assert parent.span_id == s.ctx.span_id
    # Expiry rides the encoded value (pooled worker processes keep
    # their env copy for hours): a past deadline yields no parent.
    val = tracing.encode_cold_start_parent(s.ctx)
    monkeypatch.setenv(tracing.ENV_PARENT, val)
    assert tracing.cold_start_parent().trace_id == s.ctx.trace_id
    head, _, _ = val.rpartition(":")
    monkeypatch.setenv(tracing.ENV_PARENT, head + ":1.0")
    assert tracing.cold_start_parent() is None
    # A launch-less wake clears ITS stash, and only its own.
    with tracing.start_span("wake") as w:
        tracing.stash_cold_start()
        tracing.clear_cold_start(w.ctx)
    assert tracing.take_cold_start() is None
    with tracing.start_span("other") as o:
        tracing.stash_cold_start()
    tracing.clear_cold_start(tracing.TraceContext("deadbeef", "x"))
    assert tracing.take_cold_start().trace_id == o.ctx.trace_id
    # A failed launch re-parks with the ORIGINAL deadline: repeated
    # failures must not keep a dead trace adoptable past the window.
    with tracing.start_span("retry") as r:
        tracing.stash_cold_start()
    ctx2, deadline = tracing.take_cold_start_timed()
    tracing.stash_cold_start(ctx2, deadline=deadline)
    assert tracing.take_cold_start_timed()[1] == deadline
    tracing.stash_cold_start(r.ctx, deadline=0.0)  # long expired
    assert tracing.take_cold_start_timed() is None


def test_object_pull_meta_frame_traced_and_untraced():
    """The peer pull's ``meta`` request gains a trace element ONLY when
    tracing is armed with an ambient context — off means the 2-element
    frame, byte-identical to the pre-tracing wire."""
    from ray_tpu._private.object_server import PeerPool

    class _FakeConn:
        def __init__(self):
            self.sent = []

        def send(self, msg):
            self.sent.append(msg)

        def recv(self):
            return ("ok", None)  # absent: pull returns None promptly

    conn = _FakeConn()
    assert PeerPool._pull_on_lane(conn, b"oid1") is None
    assert conn.sent == [("meta", b"oid1")]

    tracing.install()
    conn2 = _FakeConn()
    with tracing.start_span("pull") as s:
        assert PeerPool._pull_on_lane(conn2, b"oid1") is None
    assert conn2.sent == [
        ("meta", b"oid1", (s.ctx.trace_id, s.ctx.span_id))]
    # Armed but NO ambient context: still the bare 2-element frame.
    conn3 = _FakeConn()
    assert PeerPool._pull_on_lane(conn3, b"oid1") is None
    assert conn3.sent == [("meta", b"oid1")]


def test_task_payload_carries_trace_only_when_armed():
    """TaskSpec.trace is captured from the ambient context at submit;
    with tracing off the field stays None (no payload key, pinned by
    the router's conditional insert)."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, worker_mode="thread",
                 ignore_reinit_error=True)
    try:
        @ray_tpu.remote
        def f(x):
            return x

        captured = []
        w = ray_tpu._private.worker.global_worker()
        orig = w.submit_task

        def spy(spec):
            captured.append(spec)
            return orig(spec)

        w.submit_task = spy
        assert ray_tpu.get(f.remote(1)) == 1
        assert captured[-1].trace is None
        tracing.install()
        with tracing.start_span("root") as s:
            assert ray_tpu.get(f.remote(2)) == 2
        assert captured[-1].trace == (s.ctx.trace_id, s.ctx.span_id)
        w.submit_task = orig
    finally:
        ray_tpu.shutdown()


def test_local_task_spans_bridge_from_task_events():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, worker_mode="thread",
                 ignore_reinit_error=True)
    try:
        tracing.install()

        @ray_tpu.remote
        def f(x):
            return x + 1

        with tracing.start_span("root") as s:
            assert ray_tpu.get([f.remote(i) for i in range(4)]) \
                == [1, 2, 3, 4]
        # get() can return a beat before the scheduler records the last
        # FINISHED event (the bridge fires on the record): wait it out.
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline:
            spans = tracing.local_spans(s.ctx.trace_id)
            execs = [sp for sp in spans if sp["name"] == "task.exec"]
            if len(execs) >= 4:
                break
            time.sleep(0.02)
        assert len(execs) == 4
        assert all(sp["trace_id"] == s.ctx.trace_id for sp in spans)
    finally:
        ray_tpu.shutdown()


def test_tracing_off_records_zero_spans_for_tasks():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, worker_mode="thread",
                 ignore_reinit_error=True)
    try:
        @ray_tpu.remote
        def f(x):
            return x

        assert ray_tpu.get([f.remote(i) for i in range(8)]) \
            == list(range(8))
        assert tracing.tracer() is None
        assert tracing.local_spans() == []
    finally:
        ray_tpu.shutdown()


def test_streaming_item_report_carries_trace_locally():
    """Streaming item trace events: the consumer side stamps
    ``stream.item`` under the producer task's context (unit-level via
    the router's _on_item_done payload contract is covered e2e; here
    the local plane proves the generator path keeps the exec span)."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, worker_mode="thread",
                 ignore_reinit_error=True)
    try:
        tracing.install()

        @ray_tpu.remote
        def gen(n):
            for i in range(n):
                yield i

        with tracing.start_span("root") as s:
            out = [ray_tpu.get(r) for r in
                   gen.options(num_returns="streaming").remote(5)]
        assert out == [0, 1, 2, 3, 4]
        deadline = time.monotonic() + 3.0
        spans = []
        while time.monotonic() < deadline:
            spans = tracing.local_spans(s.ctx.trace_id)
            if any(sp["name"] == "task.exec" for sp in spans):
                break
            time.sleep(0.02)
        assert any(sp["name"] == "task.exec" for sp in spans)
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------- task-event buffer bound
def test_task_event_terminal_eviction_is_deterministic_and_bounded():
    """Satellite fix: the _latest_state index evicts terminal states
    deterministically on terminal record — churn far past capacity
    keeps the index at (live + capacity), never unbounded."""
    buf = TaskEventBuffer(capacity=64)
    for i in range(64 * 5):
        tid = TaskID.of(_BASE_X, i)
        buf.record(tid, "RUNNING", name="t")
        buf.record(tid, "FINISHED", name="t")
    assert buf.index_size() <= 64
    # Live (non-terminal) entries are NEVER evicted by churn.
    live = [TaskID.of(_BASE_Y, i) for i in range(10)]
    for tid in live:
        buf.record(tid, "RUNNING", name="live")
    for i in range(64 * 5, 64 * 10):
        tid = TaskID.of(_BASE_X, i)
        buf.record(tid, "RUNNING", name="t")
        buf.record(tid, "FINISHED", name="t")
    assert buf.index_size() <= 64 + 10
    states = {ev.task_id: ev.state for ev in buf.list_tasks()}
    for tid in live:
        assert states[tid] == "RUNNING"
    # Re-run after finish (lineage replay): the stale terminal marker
    # must not evict the now-live entry.
    replay = live[0]
    buf.record(replay, "FINISHED", name="live")
    buf.record(replay, "RUNNING", name="live")
    for i in range(64 * 10, 64 * 12):
        tid = TaskID.of(_BASE_X, i)
        buf.record(tid, "FINISHED", name="t")
    assert {ev.state for ev in buf.list_tasks()
            if ev.task_id == replay} == {"RUNNING"}


def test_task_event_drain_since_cursor():
    buf = TaskEventBuffer(capacity=128)
    t1 = TaskID.of(_BASE_A, 1)
    buf.record(t1, "RUNNING", name="t")
    cursor, evs = buf.drain_since(0)
    assert [e.state for e in evs] == ["RUNNING"]
    cursor2, evs2 = buf.drain_since(cursor)
    assert evs2 == [] and cursor2 == cursor
    buf.record(t1, "FINISHED", name="t")
    cursor3, evs3 = buf.drain_since(cursor)
    assert [e.state for e in evs3] == ["FINISHED"]
    # Truncation advances the cursor only to the last shipped event.
    for i in range(10):
        buf.record(TaskID.of(_BASE_B, i), "FINISHED", name="t")
    c, evs = buf.drain_since(cursor3, limit=4)
    assert len(evs) == 4
    c2, evs2 = buf.drain_since(c, limit=100)
    assert len(evs2) == 6


def test_task_event_ingest_merges_with_node_tag():
    buf = TaskEventBuffer(capacity=128)
    t1 = TaskID.of(_BASE_C, 1)
    n = buf.ingest([(t1, "RUNNING", time.time() - 1.0, "remote", None,
                     "node-A"),
                    (t1, "FINISHED", time.time(), "remote", 0.5,
                     "node-A")])
    assert n == 2
    rows = buf.list_tasks()
    assert len(rows) == 1 and rows[0].state == "FINISHED"
    assert rows[0].extra["node"] == "node-A"
    # A stale replayed batch cannot regress a newer state.
    buf.ingest([(t1, "RUNNING", time.time() - 10.0, "remote", None,
                 "node-A")])
    assert buf.list_tasks()[0].state == "FINISHED"


def test_chrome_trace_shapes():
    tracing.install()
    with tracing.start_span("root") as s:
        tracing.event("marker")
    events = tracing.chrome_trace(tracing.local_spans(s.ctx.trace_id))
    assert any(e["ph"] == "X" and e["name"] == "root" for e in events)
    assert all("trace_id" in e["args"] for e in events)


def test_merge_prometheus_valid_exposition():
    """The cluster scrape concatenates SAME-NAME families from every
    node; a valid exposition allows one HELP/TYPE per family and
    requires its samples contiguous — a real Prometheus server rejects
    the whole scrape otherwise."""
    from ray_tpu.util.metrics import merge_prometheus, relabel_prometheus

    src = ("# HELP ray_tpu_tasks_finished doc\n"
           "# TYPE ray_tpu_tasks_finished gauge\n"
           "ray_tpu_tasks_finished 3.0\n"
           "# TYPE other gauge\nother 1.0\n")
    merged = merge_prometheus([
        relabel_prometheus(src, {"node": "head", "component": "head"}),
        relabel_prometheus(src, {"node": "n1", "component": "node"}),
        relabel_prometheus(src, {"node": "n2", "component": "node"}),
    ])
    lines = merged.splitlines()
    for fam in ("ray_tpu_tasks_finished", "other"):
        assert sum(1 for ln in lines
                   if ln.startswith(f"# TYPE {fam} ")) == 1
        sample_at = [i for i, ln in enumerate(lines)
                     if ln.startswith(fam + "{")]
        assert len(sample_at) == 3
        assert sample_at == list(range(sample_at[0], sample_at[0] + 3))
    assert sum(1 for ln in lines
               if ln.startswith("# HELP ray_tpu_tasks_finished ")) == 1


# --------------------------------------------------------------------- e2e
def _spawn_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_TRACE"] = "1"
    return env


def test_e2e_one_trace_across_driver_daemon_worker(tmp_path):
    """A real head + two node daemons (PROCESS worker mode) under
    RAY_TPU_TRACE: one traced fan-out assembles into ONE trace whose
    spans cross the driver, both daemons, and the daemons' worker
    processes (>= 4 distinct pids); node task events ship home on the
    existing completion batches (cluster list_tasks, zero new
    steady-state head RPC kinds); the head's cluster /metrics scrape
    serves node-tagged series from every live node."""
    env = _spawn_env()
    os.environ["RAY_TPU_TRACE"] = "1"
    ray_tpu.shutdown()
    procs = []
    try:
        head = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.head_service",
             "--port", "0", "--metrics-port", "0"],
            stdout=subprocess.PIPE, text=True, env=env)
        procs.append(head)
        line = head.stdout.readline()
        assert "listening" in line, line
        address = line.strip().rsplit(" ", 1)[-1]
        mline = head.stdout.readline()
        assert "metrics" in mline, mline
        maddr = mline.strip().rsplit(" ", 1)[-1]
        for _ in range(2):
            n = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.node_daemon",
                 "--address", address, "--num-cpus", "1"],
                stdout=subprocess.PIPE, text=True, env=env)
            procs.append(n)
            line = n.stdout.readline()
            assert "joined" in line, line
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=address)
        assert tracing.active()
        w = ray_tpu._private.worker.global_worker()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            nodes = w.head_client.node_list()
            if len(nodes) == 2 and all(x.get("peer_addr")
                                       for x in nodes):
                break
            time.sleep(0.1)

        @ray_tpu.remote
        def traced(x):
            return x * 3

        # Warm (functions ship, workers spawn) BEFORE the RPC baseline.
        assert ray_tpu.get([traced.remote(i) for i in range(4)],
                           timeout=120) == [0, 3, 6, 9]
        stats_before = w.head_client.head_stats()

        with tracing.start_span("e2e.fanout") as s:
            out = ray_tpu.get([traced.remote(i) for i in range(12)],
                              timeout=120)
        assert out == [i * 3 for i in range(12)]
        time.sleep(1.5)  # node report batches + worker spill flush

        from ray_tpu.util.state import list_tasks, trace_summary

        # Satellite FIRST (before any explicit trace_dump pulls): node
        # task events ship home on existing completion batches — the
        # cluster task view appears with ZERO new steady-state head
        # RPC kinds vs the pre-fan-out snapshot.
        deadline = time.monotonic() + 8.0
        rows = []
        while time.monotonic() < deadline:
            rows = [t for t in list_tasks() if t.name == "traced"]
            if len(rows) >= 12 and all(t.state == "FINISHED"
                                       for t in rows):
                break
            time.sleep(0.25)
        assert len(rows) >= 12
        assert all(t.state == "FINISHED" for t in rows), rows
        nodes_seen = {t.node for t in rows if t.node}
        assert len(nodes_seen) == 2, nodes_seen
        stats_after = w.head_client.head_stats()
        for kind in ("trace_dump", "node_trace_dump", "task_done",
                     "object_announce", "metrics_dump",
                     "node_metrics_dump"):
            assert (stats_after["rpc_counts"].get(kind, 0)
                    == stats_before["rpc_counts"].get(kind, 0)), kind
        assert (stats_after["object_plane_rpcs"]
                == stats_before["object_plane_rpcs"])

        summ = trace_summary(s.ctx.trace_id)
        names = {sp["name"] for sp in summ["spans"]}
        assert "task.accept" in names      # submit→accept hop
        assert "task.exec" in names        # daemon-side exec span
        assert "worker.exec" in names      # worker-process span
        assert "task.done" in names        # driver-side completion
        comps = set(summ["components"])
        assert {"driver", "node", "worker"} <= comps
        assert summ["num_processes"] >= 4, summ["processes"]
        assert summ["errors"] == 0
        # Every span's parent resolves inside the assembled trace.
        ids = {sp["span_id"] for sp in summ["spans"]}
        orphans = [sp for sp in summ["spans"]
                   if sp["parent_id"] and sp["parent_id"] not in ids]
        assert not orphans, orphans

        # The no-arg index (what /api/traces lists) assembles the same
        # trace from O(traces) per-source aggregates, not span dumps.
        idx = trace_summary()["traces"]
        assert s.ctx.trace_id in idx
        assert idx[s.ctx.trace_id]["num_processes"] >= 4
        assert idx[s.ctx.trace_id]["root"] == "e2e.fanout"
        assert idx[s.ctx.trace_id]["errors"] == 0

        # Cluster /metrics: tagged series from every live node.
        import re
        import urllib.request

        text = urllib.request.urlopen(
            f"http://{maddr}/metrics", timeout=15).read().decode()
        tagged_nodes = set(re.findall(r'node="([^"]+)"', text))
        node_ids = {n["client_id"]
                    for n in w.head_client.node_list()}
        assert node_ids <= tagged_nodes, (node_ids, tagged_nodes)
        assert "ray_tpu_tasks_finished" in text
        assert 'component="node"' in text

        # Chrome export round-trips through the public API.
        path = ray_tpu.timeline(trace_id=s.ctx.trace_id,
                                filename=str(tmp_path / "t.json"))
        assert os.path.getsize(path) > 0
    finally:
        ray_tpu.shutdown()
        os.environ.pop("RAY_TPU_TRACE", None)
        os.environ.pop(tracing.ENV_DIR, None)
        for p in reversed(procs):
            p.kill()
            p.wait(timeout=5)


def test_e2e_streaming_trace_events(tmp_path):
    """A traced cross-node streaming generator stamps stream.item
    events on the consumer and the producer's exec span on the node —
    the item_done report carries the context."""
    env = _spawn_env()
    os.environ["RAY_TPU_TRACE"] = "1"
    ray_tpu.shutdown()
    procs = []
    try:
        head = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.head_service",
             "--port", "0"],
            stdout=subprocess.PIPE, text=True, env=env)
        procs.append(head)
        line = head.stdout.readline()
        assert "listening" in line, line
        address = line.strip().rsplit(" ", 1)[-1]
        n = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.node_daemon",
             "--address", address, "--num-cpus", "1",
             "--worker-mode", "thread"],
            stdout=subprocess.PIPE, text=True, env=env)
        procs.append(n)
        assert "joined" in n.stdout.readline()
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=address)
        w = ray_tpu._private.worker.global_worker()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            nodes = w.head_client.node_list()
            if nodes and all(x.get("peer_addr") for x in nodes):
                break
            time.sleep(0.1)

        @ray_tpu.remote
        def gen(k):
            for i in range(k):
                yield os.urandom(200_000)  # big: announce + p2p pull

        with tracing.start_span("e2e.stream") as s:
            items = [ray_tpu.get(r) for r in gen.options(
                num_returns="streaming").remote(4)]
        assert [len(b) for b in items] == [200_000] * 4
        time.sleep(1.0)
        from ray_tpu.util.state import trace_summary

        summ = trace_summary(s.ctx.trace_id)
        names = {sp["name"] for sp in summ["spans"]}
        assert "stream.item" in names, names
        assert summ["num_processes"] >= 2
    finally:
        ray_tpu.shutdown()
        os.environ.pop("RAY_TPU_TRACE", None)
        os.environ.pop(tracing.ENV_DIR, None)
        for p in reversed(procs):
            p.kill()
            p.wait(timeout=5)
