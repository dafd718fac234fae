"""Flight-recorder (black-box postmortem) plane tests.

Fast units: disarmed inertness (zero threads, zero counters, facade
no-ops), folded-stack correctness against a known synthetic stack, the
event ring / profile-aggregate bounds, lock-hold outlier events from
the sanitizer's tracked locks, watchdog-fires-on-deliberate-deadlock
capturing an automatic local dump, and the worker bundle spill's
rotate-at-capacity + stale-expiry hardening. The e2e suite spins a
real head + two node daemons (PROCESS worker mode) fully armed and
proves ``ray_tpu.debug_dump()`` assembles one incident archive from
>= 4 distinct processes with ZERO new steady-state head RPCs, that a
deliberately hung worker auto-dumps without operator action, and that
a forced bench SLO-gate failure auto-captures an archive.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private import flight
from ray_tpu._private.config import GlobalConfig
from ray_tpu.util import sanitizer


@pytest.fixture(autouse=True)
def _clean_flight():
    flight.uninstall()
    yield
    flight.uninstall()
    GlobalConfig.reset()


# ---------------------------------------------------------------- fast units
def test_disarmed_is_inert(monkeypatch):
    monkeypatch.delenv(flight.ENV_VAR, raising=False)
    monkeypatch.delenv(flight.ENV_PROFILE, raising=False)
    assert flight.install_from_env() is None
    assert not flight.active()
    assert flight.recorder() is None
    # Every facade entry point is a one-branch no-op.
    flight.record_event("x", a=1)
    flight.beat("hb")
    flight.note_lock_acquired("l")
    flight.note_lock_released("l")
    flight.note_task_started("t")
    flight.note_task_finished()
    flight.note_watchdog_fire("k", "m")
    flight.add_section("s", lambda: {})
    flight.note_artifact("/tmp/x")
    assert flight.local_bundle() is None
    assert flight.auto_dump("r") is None
    assert flight.set_profiling(True) is False
    assert flight.collapsed_stacks() == []
    # No recorder threads exist while disarmed.
    assert not [t for t in threading.enumerate()
                if t.name.startswith("ray_tpu_flight")]
    # Tracked locks pay only the `is None` branch: no hold state
    # accumulates anywhere.
    lk = sanitizer.tracked_lock("flight_inert_lock")
    with lk:
        pass
    rl = sanitizer.tracked_rlock("flight_inert_rlock")
    with rl:
        with rl:
            pass


def test_disarmed_samples_zero_extra_frames():
    """Profiler inertness, the counter form: with the recorder off, a
    burst of work records zero samples and zero events anywhere."""
    assert flight.recorder() is None
    for _ in range(100):
        flight.record_event("never")
    rec = flight.install(component="late")  # arm AFTER the burst
    assert rec.events_recorded == 0
    assert rec.sampler is None  # profile not requested -> no sampler
    assert rec.local_bundle()["profile"]["samples_taken"] == 0


def _leaf_park(stop: threading.Event):
    stop.wait(30)


def _mid_hop(stop):
    _leaf_park(stop)


def _outer_entry(stop):
    _mid_hop(stop)


def test_folded_stack_matches_synthetic_stack():
    rec = flight.install(component="t", profile=True)
    stop = threading.Event()
    t = threading.Thread(target=_outer_entry, args=(stop,),
                         name="synthetic_stack", daemon=True)
    t.start()
    time.sleep(0.05)
    try:
        rec.sampler.sample_once()
        lines = rec.sampler.collapsed()
        syn = [ln for ln in lines if ln.startswith("synthetic_stack;")]
        assert syn, lines
        stack, count = syn[0].rsplit(" ", 1)
        assert int(count) >= 1
        # Root→leaf order with the exact synthetic frames, module-
        # qualified as file:function.
        i_outer = stack.find("test_flight.py:_outer_entry")
        i_mid = stack.find("test_flight.py:_mid_hop")
        i_leaf = stack.find("test_flight.py:_leaf_park")
        assert 0 < i_outer < i_mid < i_leaf, stack
        # Speedscope export round-trips the same frames.
        doc = rec.sampler.speedscope()
        names = {f["name"] for f in doc["shared"]["frames"]}
        assert "test_flight.py:_leaf_park" in names
        assert doc["profiles"][0]["type"] == "sampled"
        assert len(doc["profiles"][0]["samples"]) == \
            len(doc["profiles"][0]["weights"])
    finally:
        stop.set()
        t.join(5)


def test_sampler_excludes_itself_and_bounds_distinct_stacks():
    rec = flight.install(component="t", profile=True)
    rec.sampler.sample_once()
    assert not any("ray_tpu_flight_sampler" in ln
                   for ln in rec.sampler.collapsed())
    # At the distinct-stack cap, new stacks count into stacks_dropped
    # instead of growing the aggregate.
    s = rec.sampler
    with s._lock:
        s._agg.clear()
        for i in range(s.max_stacks):
            s._agg[f"synthetic;stack{i}"] = 1
    before = len(s._agg)
    s.sample_once()
    assert len(s._agg) == before
    assert s.stacks_dropped >= 1


def test_event_ring_bounded_and_gc_hook():
    import gc

    rec = flight.install(component="t", event_capacity=32)
    GlobalConfig.set("flight_gc_ms", 0.0)
    rec._gc_min_s = 0.0
    for i in range(100):
        flight.record_event("e", i=i)
    # >= not ==: an incidental gc.pause mid-loop also lands in the ring.
    assert rec.events_recorded >= 100
    assert len(rec.events()) <= 32
    gc.collect()
    kinds = [e["kind"] for e in rec.events()]
    assert "gc.pause" in kinds


def test_lock_hold_outlier_event():
    rec = flight.install(component="t")
    GlobalConfig.set("flight_lock_hold_ms", 1.0)
    lk = sanitizer.tracked_lock("outlier_lock")
    with lk:
        time.sleep(0.01)
    assert rec.lock_hold_outliers == 1
    ev = [e for e in rec.events() if e["kind"] == "lock.hold"]
    assert ev and ev[0]["data"]["lock"] == "outlier_lock"
    # Re-entrant: only the outermost 1→0 release times the hold.
    rl = sanitizer.tracked_rlock("outlier_rlock")
    with rl:
        with rl:
            time.sleep(0.01)
    assert rec.lock_hold_outliers == 2


def test_watchdog_fires_on_deliberate_deadlock(tmp_path):
    """Two threads take two tracked locks in opposite orders and
    deadlock for real (sanitizer disarmed — nothing raises first).
    The lock-hold watchdog fires WITHOUT operator action, writes an
    incident dump whose stacks show the deadlocked threads, and the
    fire lands in the framework metrics gauge."""
    GlobalConfig.set("flight_watchdog_period_s", 0.1)
    GlobalConfig.set("flight_lock_watchdog_s", 0.3)
    GlobalConfig.set("flight_dump_min_interval_s", 0.0)
    rec = flight.install(component="t")
    rec.dump_dir = str(tmp_path)
    la = sanitizer.tracked_lock("deadlock_A")
    lb = sanitizer.tracked_lock("deadlock_B")
    b1 = threading.Barrier(2)

    def one():
        with la:
            b1.wait(5)
            with lb:
                pass

    def two():
        with lb:
            b1.wait(5)
            with la:
                pass

    # Deliberately deadlocked forever: daemon threads, never joined.
    threading.Thread(target=one, name="deadlock_one",
                     daemon=True).start()
    threading.Thread(target=two, name="deadlock_two",
                     daemon=True).start()
    # Poll for a COMPLETE incident file (the fire counter increments
    # before the dump finishes writing).
    deadline = time.monotonic() + 5
    bundle = None
    while time.monotonic() < deadline and bundle is None:
        for f in os.listdir(tmp_path):
            if f.startswith("incident-") and f.endswith(".json"):
                try:
                    bundle = json.loads((tmp_path / f).read_text())
                    break
                except ValueError:
                    pass  # still being written
        time.sleep(0.05)
    assert rec.watchdog_fires >= 1
    kinds = {k for _, k, _ in rec.watchdog_last}
    assert "lock-hold" in kinds, kinds
    assert bundle is not None, os.listdir(tmp_path)
    stacks = "\n".join("\n".join(v) for v in bundle["stacks"].values())
    assert "test_flight.py" in stacks  # the deadlocked frames are in
    assert any(name.startswith("deadlock_")
               for name in bundle["stacks"])
    # faulthandler sidecar landed too (the assembly-proof fallback).
    assert any(f.endswith(".stacks.txt") for f in os.listdir(tmp_path))
    # The fire is a framework metrics gauge.
    from ray_tpu.util.metrics import (
        export_prometheus,
        framework_metrics,
        refresh_framework_metrics,
    )

    framework_metrics()
    refresh_framework_metrics(type("W", (), {
        "scheduler": type("S", (), {"backlog_size": lambda s: 0})(),
        "store": type("St", (), {"_entries": {}})()})())
    text = export_prometheus()
    import re

    m = re.search(r"ray_tpu_watchdog_fires (\d+)", text)
    assert m and int(m.group(1)) >= 1, text


def test_heartbeat_gap_watchdog_one_fire_per_episode(tmp_path):
    GlobalConfig.set("flight_watchdog_period_s", 0.1)
    GlobalConfig.set("flight_heartbeat_gap_s", 0.3)
    GlobalConfig.set("flight_dump_min_interval_s", 0.0)
    rec = flight.install(component="t")
    rec.dump_dir = str(tmp_path)
    flight.beat("hb")
    time.sleep(1.2)
    assert rec.watchdog_fires == 1  # exactly one per gap episode
    flight.beat("hb")  # resuming beats re-arms
    time.sleep(0.8)
    assert rec.watchdog_fires == 2


def test_task_stuck_watchdog():
    GlobalConfig.set("flight_watchdog_period_s", 0.1)
    GlobalConfig.set("flight_task_stuck_s", 0.3)
    GlobalConfig.set("flight_dump_min_interval_s", 0.0)
    rec = flight.install(component="t")
    flight.note_task_started("wedged_task")
    time.sleep(1.0)
    assert rec.watchdog_fires == 1  # one fire per task episode
    assert any(k == "task-stuck" and "wedged_task" in m
               for _, k, m in rec.watchdog_last)
    flight.note_task_finished()
    assert rec.local_bundle()["tasks_in_flight"] == []


def test_stall_watchdog_routes_through_logger_and_escalates():
    """Satellite: the sanitizer's StallWatchdog reports through the
    ray_tpu logger (RAY_TPU_LOG_LEVEL governs it, no bare prints) and
    escalates into a flight auto-dump when the recorder is armed."""
    import logging

    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    # The ray_tpu root logger does not propagate (it owns its stderr
    # handler), so capture with a handler attached directly.
    ray_logger = logging.getLogger("ray_tpu")
    capture = _Capture(level=logging.ERROR)
    ray_logger.addHandler(capture)
    GlobalConfig.set("flight_dump_min_interval_s", 0.0)
    rec = flight.install(component="t")

    class _Sched:
        def backlog_size(self):
            return 3

        def num_running(self):
            return 0

        def num_finished(self):
            return 0

    class _Pool:
        def available(self):
            return {"CPU": 4.0}

    wd = sanitizer.StallWatchdog(_Sched(), _Pool(),
                                 threshold_s=0.1, period_s=0.05)
    try:
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline \
                and rec.watchdog_fires == 0:
            time.sleep(0.05)
        time.sleep(0.2)  # let the report() call land after the fire
    finally:
        wd.stop()
        ray_logger.removeHandler(capture)
    assert rec.watchdog_fires >= 1
    assert any(k == "scheduler-stall"
               for _, k, _ in rec.watchdog_last)
    # Exactly one counter takes the fire (the gauge sums both): with
    # the recorder armed it lands there, NOT in the sanitizer module
    # counter — one stall must read as one fire, not two.
    assert sanitizer.watchdog_fires == 0
    assert any("scheduler-stall" in r.getMessage() for r in records)
    sanitizer.clear()


# ------------------------------------------------------------- bundle spill
def test_spill_rotates_at_capacity(tmp_path, monkeypatch):
    monkeypatch.setenv(flight.ENV_DIR, str(tmp_path))
    GlobalConfig.set("flight_spill_max_records", 3)
    rec = flight.install(component="worker", spill=True)
    rec._stop.set()  # stop the periodic thread; drive spills by hand
    for _ in range(8):
        rec.spill_once()
    files = [f for f in os.listdir(tmp_path) if f.startswith("bundle-")]
    assert len(files) == 1
    lines = [ln for ln in
             (tmp_path / files[0]).read_text().splitlines() if ln]
    # 8 spills at cap 3: rotations keep the file at the newest window.
    assert len(lines) <= 3
    for ln in lines:
        json.loads(ln)


def test_spilled_bundle_merge_newest_and_stale_expiry(tmp_path):
    now = time.time()
    fresh = {"ts": now, "pid": 11, "component": "worker"}
    newest = {"ts": now + 1, "pid": 11, "component": "worker",
              "marker": "newest"}
    stale = {"ts": now - 9999, "pid": 22, "component": "worker"}
    (tmp_path / "bundle-11-aa.jsonl").write_text(
        json.dumps(fresh) + "\n" + json.dumps(newest) + "\n")
    # Stale file from a reused pooled worker that exited long ago.
    (tmp_path / "bundle-22-bb.jsonl").write_text(
        json.dumps(stale) + "\n")
    (tmp_path / "not-a-bundle.txt").write_text("junk")
    got = flight.read_spilled_bundles(str(tmp_path), stale_s=120.0)
    assert len(got) == 1
    assert got[0]["marker"] == "newest"  # newest snapshot per file
    # Self-exclusion: a daemon reading its own spill dir skips files
    # it wrote itself.
    assert flight.read_spilled_bundles(
        str(tmp_path), exclude_pid=11, stale_s=120.0) == []
    # Torn last line (racing writer) is skipped, not fatal.
    (tmp_path / "bundle-33-cc.jsonl").write_text(
        json.dumps({"ts": now, "pid": 33}) + "\n{\"torn")
    got = flight.read_spilled_bundles(str(tmp_path), stale_s=120.0)
    assert {b["pid"] for b in got} == {11}


# ------------------------------------------------------------ bench capture
def test_bench_autocapture_on_forced_gate_failure(tmp_path):
    """bench.maybe_capture_debug: a failed SLO gate with a live
    runtime pulls a debug archive; a passing gate captures nothing."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    try:
        import bench
    finally:
        sys.path.pop(0)
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, worker_mode="thread",
                 ignore_reinit_error=True)
    try:
        assert bench.maybe_capture_debug(
            "forced", ok=True, out_dir=str(tmp_path)) is None
        assert not list(tmp_path.iterdir())
        incident = bench.maybe_capture_debug(
            "forced", ok=False, out_dir=str(tmp_path))
        assert incident and os.path.isdir(incident)
        manifest = json.loads(
            open(os.path.join(incident, "manifest.json")).read())
        assert "driver" in manifest["sources"]
        bundle = json.loads(
            open(os.path.join(incident, "driver.json")).read())
        assert bundle["stacks"]  # all-thread stacks present
        # _slo_assert raises with the archive path appended.
        with pytest.raises(AssertionError, match="debug bundle"):
            bench._slo_assert("forced", False, "floor missed")
    finally:
        ray_tpu.shutdown()


# --------------------------------------------------------------------- e2e
def _spawn_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_FLIGHT"] = "1"
    env["RAY_TPU_PROFILE"] = "1"
    # Fast cadences so worker spills + the hung-worker watchdog land
    # inside test time.
    env["RAY_TPU_FLIGHT_SPILL_PERIOD_S"] = "0.5"
    env["RAY_TPU_FLIGHT_WATCHDOG_PERIOD_S"] = "0.25"
    env["RAY_TPU_FLIGHT_TASK_STUCK_S"] = "2.0"
    env["RAY_TPU_FLIGHT_DUMP_MIN_INTERVAL_S"] = "0.0"
    return env


def test_e2e_debug_dump_two_nodes(tmp_path):
    """A real head + two node daemons (PROCESS worker mode), fully
    armed: one ``ray_tpu.debug_dump()`` writes one incident archive
    with per-process all-thread stacks, event rings, and metrics
    snapshots from >= 4 distinct processes (driver, head, daemon x2,
    + spilled worker bundles), with ZERO new steady-state head RPCs
    (head_stats-asserted); a deliberately hung worker then triggers a
    task-stuck watchdog auto-dump without operator action."""
    env = _spawn_env()
    for var, val in (("RAY_TPU_FLIGHT", "1"), ("RAY_TPU_PROFILE", "1"),
                     ("RAY_TPU_FLIGHT_WATCHDOG_PERIOD_S", "0.25"),
                     ("RAY_TPU_FLIGHT_DUMP_MIN_INTERVAL_S", "0.0")):
        os.environ[var] = val
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
        for _ in range(2):
            node = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.node_daemon",
                 "--address", address, "--num-cpus", "1"],
                stdout=subprocess.PIPE, text=True, env=env)
            procs.append(node)
            line = node.stdout.readline()
            assert "joined" in line, line
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=address)
        assert flight.active()
        w = ray_tpu._private.worker.global_worker()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            nodes = w.head_client.node_list()
            if len(nodes) == 2 and all(x.get("peer_addr")
                                       for x in nodes):
                break
            time.sleep(0.1)

        @ray_tpu.remote
        def probe(x):
            return x * 7

        # Warm (functions ship, worker processes spawn + first spill).
        assert ray_tpu.get([probe.remote(i) for i in range(4)],
                           timeout=120) == [0, 7, 14, 21]
        time.sleep(1.5)

        # Steady state first: a fan-out between two head_stats
        # snapshots moves ZERO flight-plane RPCs — the dump plane
        # costs nothing until someone asks.
        stats_before = w.head_client.head_stats()
        assert ray_tpu.get([probe.remote(i) for i in range(12)],
                           timeout=120) == [i * 7 for i in range(12)]
        stats_after = w.head_client.head_stats()
        for kind in ("debug_dump", "node_debug_dump", "flight_ctl",
                     "node_flight_ctl"):
            assert (stats_after["rpc_counts"].get(kind, 0)
                    == stats_before["rpc_counts"].get(kind, 0)), kind
        assert (stats_after["object_plane_rpcs"]
                == stats_before["object_plane_rpcs"])

        # One command, one incident archive.
        incident = ray_tpu.debug_dump(str(tmp_path))
        manifest = json.loads(
            open(os.path.join(incident, "manifest.json")).read())
        sources = manifest["sources"]
        assert "driver" in sources and "head" in sources
        node_sources = [s for s in sources if s.startswith("node-")]
        assert len(node_sources) == 2, sources
        assert manifest["num_processes"] >= 4
        pids = set()
        comps = set()
        for fname in os.listdir(incident):
            if fname == "manifest.json":
                continue
            bundle = json.loads(
                open(os.path.join(incident, fname)).read())
            pids.add(bundle["pid"])
            comps.add(bundle["component"])
            # Acceptance: every per-process bundle carries all-thread
            # stacks, an event-ring view, and a metrics snapshot.
            assert bundle["stacks"], fname
            assert "events" in bundle, fname
            assert "metrics" in bundle, fname
            assert bundle["profile"]["armed"], fname
        assert len(pids) >= 4, pids
        assert {"driver", "head", "node"} <= comps, comps
        # Worker processes surfaced through their hosting daemons'
        # merged spill (PROCESS worker mode).
        assert "worker" in comps, comps
        node_bundle = json.loads(open(os.path.join(
            incident, f"{node_sources[0]}.json")).read())
        assert "node" in node_bundle["sections"], \
            node_bundle["sections"].keys()

        # Deliberately hang a worker: the task-stuck watchdog (2s
        # bound via env) auto-dumps WITHOUT any operator action; the
        # incident surfaces in the daemon's next bundle.
        @ray_tpu.remote
        def hang():
            time.sleep(600)

        hang.remote()  # never consumed — wedges one node's worker
        from ray_tpu.util.state import collect_debug_bundles

        deadline = time.monotonic() + 20
        incidents = []
        while time.monotonic() < deadline:
            bundles = collect_debug_bundles()
            incidents = [
                inc for name, b in bundles.items()
                if name.startswith("node-")
                for inc in b.get("incidents", [])
                if "task-stuck" in inc]
            if incidents:
                break
            time.sleep(0.5)
        assert incidents, "hung worker never auto-dumped"
    finally:
        ray_tpu.shutdown()
        for var in ("RAY_TPU_FLIGHT", "RAY_TPU_PROFILE",
                    "RAY_TPU_FLIGHT_WATCHDOG_PERIOD_S",
                    "RAY_TPU_FLIGHT_DUMP_MIN_INTERVAL_S",
                    flight.ENV_DIR, flight.ENV_NODE):
            os.environ.pop(var, None)
        for p in reversed(procs):
            p.kill()
            p.wait(timeout=5)


def test_e2e_cluster_profiling_toggle(tmp_path):
    """flight_ctl round trip: set_cluster_profiling pauses/resumes
    samplers on the driver and every node (the flight_overhead bench's
    A/B verb)."""
    env = _spawn_env()
    os.environ["RAY_TPU_FLIGHT"] = "1"
    os.environ["RAY_TPU_PROFILE"] = "1"
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
        node = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.node_daemon",
             "--address", address, "--num-cpus", "1",
             "--worker-mode", "thread"],
            stdout=subprocess.PIPE, text=True, env=env)
        procs.append(node)
        line = node.stdout.readline()
        assert "joined" in line, line
        ray_tpu.init(num_cpus=0, num_tpus=0, worker_mode="thread",
                     address=address)
        w = ray_tpu._private.worker.global_worker()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            nodes = w.head_client.node_list()
            if nodes and all(x.get("peer_addr") for x in nodes):
                break
            time.sleep(0.1)
        from ray_tpu.util.state import set_cluster_profiling

        on = set_cluster_profiling(True)
        assert on["driver"] is True
        assert on.get("head") is True, on
        assert any(k.startswith("node-") and v
                   for k, v in on.items()), on
        off = set_cluster_profiling(False)
        assert off["driver"] is False
        # A successful PAUSE still reports per node — running False
        # is an answer, not an unreachable source.
        assert off.get("head") is False, off
        assert any(k.startswith("node-") for k in off), off
        assert all(v is False for k, v in off.items()
                   if k.startswith("node-")), off
        assert flight.recorder().sampler.running is False
        set_cluster_profiling(True)
        assert flight.recorder().sampler.running is True
    finally:
        ray_tpu.shutdown()
        for var in ("RAY_TPU_FLIGHT", "RAY_TPU_PROFILE",
                    flight.ENV_DIR, flight.ENV_NODE):
            os.environ.pop(var, None)
        for p in reversed(procs):
            p.kill()
            p.wait(timeout=5)
