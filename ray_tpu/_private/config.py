"""Typed config/flag registry with environment-variable override.

TPU-native rebuild of the reference's RayConfig flag system (reference:
src/ray/common/ray_config_def.h [unverified]): every knob is declared once
with a type and default, overridable via ``RAY_TPU_<NAME>`` environment
variables or a ``_system_config`` dict passed to ``init()``.
"""

from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict

_ENV_PREFIX = "RAY_TPU_"


def _parse_bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


_PARSERS: Dict[type, Callable[[str], Any]] = {
    bool: _parse_bool,
    int: int,
    float: float,
    str: str,
    dict: json.loads,
    list: json.loads,
}


@dataclass
class _Flag:
    name: str
    type: type
    default: Any
    doc: str = ""


class ConfigRegistry:
    """Declare-once flag registry; values resolve env > override > default."""

    def __init__(self):
        self._flags: Dict[str, _Flag] = {}
        self._overrides: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def declare(self, name: str, type_: type, default: Any, doc: str = ""):
        with self._lock:
            if name in self._flags:
                raise ValueError(f"flag {name!r} declared twice")
            self._flags[name] = _Flag(name, type_, default, doc)

    def get(self, name: str) -> Any:
        flag = self._flags[name]
        env_val = os.environ.get(_ENV_PREFIX + name.upper())
        if env_val is not None:
            return _PARSERS[flag.type](env_val)
        with self._lock:
            if name in self._overrides:
                return self._overrides[name]
        return flag.default

    def set(self, name: str, value: Any):
        flag = self._flags[name]
        if not isinstance(value, flag.type):
            value = _PARSERS[flag.type](str(value))
        with self._lock:
            self._overrides[name] = value

    def apply_system_config(self, system_config: Dict[str, Any]):
        for k, v in (system_config or {}).items():
            if k not in self._flags:
                raise ValueError(f"unknown system config flag {k!r}")
            self.set(k, v)

    def reset(self):
        with self._lock:
            self._overrides.clear()

    def describe(self) -> Dict[str, Any]:
        return {
            name: {"type": f.type.__name__, "default": f.default,
                   "value": self.get(name), "doc": f.doc}
            for name, f in sorted(self._flags.items())
        }

    def __getattr__(self, name: str) -> Any:
        # Attribute-style access for declared flags.
        if name.startswith("_"):
            raise AttributeError(name)
        try:
            return self.get(name)
        except KeyError:
            raise AttributeError(name) from None


GlobalConfig = ConfigRegistry()

# --- Core runtime flags (mirrors the role of ray_config_def.h) -------------
_D = GlobalConfig.declare
_D("task_max_retries", int, 3, "Default max retries for retriable tasks.")
_D("actor_max_restarts", int, 0, "Default max actor restarts.")
_D("inline_object_max_bytes", int, 100 * 1024,
   "Objects at or under this size are stored inline in the task reply.")
_D("object_store_memory_bytes", int, 512 * 1024 * 1024,
   "In-process object store soft cap before spilling to disk.")
_D("object_spill_dir", str, "",
   "Directory for spilled objects ('' = <session_dir>/spill).")
_D("worker_pool_size", int, 0,
   "Thread workers for local task execution (0 = num_cpus).")
_D("get_timeout_warning_s", float, 10.0,
   "Warn if a blocking get waits longer than this.")
_D("wave_executor_max_args", int, 4,
   "Max padded arg slots per task in the JAX wave executor.")
_D("wave_executor_dynamic", bool, False,
   "Use dynamic frontier while_loop instead of static level schedule.")
_D("channel_buffer_bytes", int, 1024 * 1024,
   "Default mutable-channel buffer size.")
_D("channel_read_timeout_s", float, 60.0, "Channel read timeout.")
_D("health_check_period_s", float, 1.0, "Control-plane health check period.")
_D("health_check_failure_threshold", int, 5,
   "Missed health checks before a node is marked dead.")
_D("metrics_export_port", int, 0, "Prometheus scrape port (0 = disabled).")
_D("task_events_max_buffer", int, 100_000,
   "Ring-buffer capacity for task state events (state API/timeline).")
_D("scheduler_spread_threshold", float, 0.5,
   "Hybrid policy: pack until node utilization passes this, then spread.")
_D("scheduler_top_k_fraction", float, 0.2,
   "Hybrid policy: random tie-break among top-k fraction of nodes.")
_D("lineage_pinning_enabled", bool, True,
   "Keep task specs for lineage reconstruction of lost objects.")
_D("enable_timeline", bool, True, "Record task profile events for timeline.")
_D("shm_store_bytes", int, 256 * 1024 * 1024,
   "Shared-memory store segment size for the native object store.")
_D("shm_store_slots", int, 4096,
   "Max concurrent objects in the native shared-memory store.")
_D("worker_mode", str, "process",
   "Task execution plane: 'process' (spawned worker processes over the shm "
   "store — the default, matching the reference's process-isolated "
   "workers) or 'thread' (in-process pool; used automatically when the "
   "native layer is unavailable).")
_D("memory_monitor_threshold", float, 0.95,
   "System memory-used fraction above which the monitor kills the "
   "youngest running process task (OutOfMemoryError, retriable). "
   "0 disables the monitor.")
_D("spill_backlog_factor", float, 4.0,
   "Route tasks to remote node daemons when the local backlog exceeds "
   "factor times num_cpus and a feasible node is less loaded.")
_D("dep_wait_s", float, 300.0,
   "Bound on waiting for a task's dependency to be produced: the "
   "driver-side wait before inlining a local value, and the node-side "
   "pull-wait for a pending pull-ref shipped ahead of its producer. "
   "Raises GetTimeoutError past it (RAY_TPU_DEP_WAIT_S).")
_D("direct_dispatch", bool, True,
   "Push tasks peer-to-peer to the target node daemon's direct server "
   "(batched over the framed transport), falling back to a head-relayed "
   "task_push only when the direct dial fails.")
_D("locality_min_bytes", int, 64 * 1024,
   "Locality-aware placement: prefer the feasible node already holding "
   "at least this many bytes of a task's ref args over the least-loaded "
   "node (pending deps count as presence at their target node).")
_D("locality_load_slack", float, 8.0,
   "Locality-aware placement: the bytes-resident node wins only while "
   "its load is within this many backlog-per-CPU units of the "
   "least-loaded feasible node (past it, spread wins over locality).")
_D("external_pull_ttl_s", float, 600.0,
   "Bound on post-completion pull retries for remote actor-task results "
   "(mirrors the ActorHost result-pin TTL): past it the object is "
   "declared lost instead of retrying forever.")
_D("generator_backpressure_items", int, 0,
   "Consumer-driven backpressure for num_returns='streaming' generator "
   "tasks: the producer's yield loop pauses while this many committed "
   "items remain unconsumed, resuming on consumption acks "
   "(RAY_TPU_GENERATOR_BACKPRESSURE_ITEMS; 0 = unlimited).")
_D("transport_handshake_timeout_s", float, 5.0,
   "Server-side bound on the transport HMAC handshake: a connect-then-"
   "hang or half-open peer is dropped after this many seconds instead "
   "of pinning its handshake thread (the accept loop itself is never "
   "blocked — handshakes run per-connection).")
_D("peer_pull_attempts", int, 3,
   "Direct peer chunk pulls retry (re-dialing a fresh lane) up to this "
   "many times with jittered exponential backoff before the puller "
   "gives up on the peer and falls back / declares the object lost — "
   "bounded reconnect under chaos-induced resets.")
_D("peer_pull_backoff_s", float, 0.05,
   "Base backoff between peer pull attempts (doubled per attempt, "
   "jittered x0.5-1.5 so synchronized pullers don't stampede a "
   "recovering peer).")
_D("worker_channel_bytes", int, 1024 * 1024,
   "Request/reply channel buffer size per worker process (4 channels per "
   "worker are resident in the shm store; larger blobs are staged as "
   "regular shm objects instead of widening the channels).")
_D("log_level", str, "warning",
   "Threshold for the ray_tpu logger hierarchy (debug/info/warning/"
   "error). Daemon loops log swallowed transient failures at debug; "
   "survivable-but-unexpected conditions at warning.")
_D("head_client_timeout_s", float, 5.0,
   "Per-request timeout for short head-service RPCs issued by "
   "tooling/state clients (the CLI, dashboards); the long-lived "
   "HeadClient channels use their own reconnect-and-resume protocol.")
_D("workflow_storage", str, "",
   "Default workflow storage root URI ('' = ~/.ray_tpu/workflows; "
   "supports local paths, memory://, and fsspec URIs).")
_D("runtime_env_cache", str, "",
   "Directory for built runtime-env (pip) environments "
   "('' = ~/.cache/ray_tpu/runtime_envs).")
_D("native_cache", str, "",
   "Directory for compiled native-layer artifacts "
   "('' = ~/.cache/ray_tpu).")
_D("coordinator_address", str, "",
   "Multi-process device-plane coordinator address for "
   "parallel.distributed.initialize ('' = single-process mesh).")
_D("head_log_compact_records", int, 50000,
   "Compact the head's append-only state log once it holds this many "
   "records (snapshot + truncate; 0 disables compaction).")
_D("autoscaler_launch_retries", int, 3,
   "Provider node launches retry up to this many times (jittered "
   "exponential backoff) before the autoscaler surfaces a typed "
   "NodeLaunchFailedError instead of silent membership absence.")
_D("autoscaler_launch_backoff_s", float, 0.5,
   "Base backoff between node-launch attempts (doubled per attempt, "
   "jittered x0.5-1.5 so concurrent launch storms spread).")
_D("autoscaler_launch_grace_s", float, 60.0,
   "Grace window for a LAUNCHING node: from process start until this "
   "many seconds pass, a node absent from head membership is treated "
   "as still cold-starting, never as dead — slow engine/runtime init "
   "must not be reaped by the liveness plane mid-boot.")
_D("autoscaler_drain_timeout_s", float, 15.0,
   "Drain-before-reap bound: an idle node chosen for reap waits up to "
   "this long for in-flight tasks to finish and node-held result "
   "bytes to lease-transfer (object_offload to their owner + "
   "object_transfer re-point of head fallback entries) before the "
   "provider terminates it.")
_D("trace_max_spans", int, 65536,
   "Per-process span ring capacity for the distributed tracing plane "
   "(RAY_TPU_TRACE arms tracing; off = zero spans, zero wire bytes).")
_D("trace_cold_start_window_s", float, 180.0,
   "How long a launched node daemon keeps RAY_TPU_TRACE_PARENT in its "
   "environment: worker processes spawned inside the window parent "
   "their init spans to the launching request's trace; past it the "
   "variable is dropped so later unrelated scale-ups on a long-lived "
   "node aren't misattributed to a finished trace.")
_D("profile_hz", float, 19.0,
   "Flight-recorder stack sampler frequency (RAY_TPU_PROFILE arms the "
   "sampler; the interval is jittered ±50% so periodic work isn't "
   "phase-locked out of the profile). The default budgets an always-"
   "on sampler under ~1% of one core at typical control-plane thread "
   "counts (one sweep over ~40 threads measures ~0.5 ms, and the GIL "
   "serializes the sweep against user code).")
_D("profile_max_stacks", int, 2048,
   "Bound on DISTINCT folded stacks the sampler aggregates; overflow "
   "counts into stacks_dropped instead of growing memory.")
_D("flight_event_capacity", int, 4096,
   "Per-process flight-recorder event ring capacity (state "
   "transitions, queue depths, lock-hold outliers, GC pauses).")
_D("flight_dir", str, "",
   "Directory for flight bundles: watchdog auto-dumps and worker-"
   "process bundle spills ('' = <session_dir>/flight, injected into "
   "spawned processes via RAY_TPU_FLIGHT_DIR).")
_D("flight_gc_ms", float, 20.0,
   "GC pauses at or above this many milliseconds become gc.pause "
   "events in the flight ring (gc.callbacks hook; a classic "
   "invisible source of tail latency).")
_D("flight_lock_hold_ms", float, 50.0,
   "Tracked-lock hold time above which the release records a "
   "lock.hold outlier event in the flight ring.")
_D("flight_lock_watchdog_s", float, 10.0,
   "Tracked-lock hold time above which the lock-hold watchdog fires "
   "an automatic local dump (the observable shape of a deadlock or a "
   "lock held across blocking I/O).")
_D("flight_heartbeat_gap_s", float, 30.0,
   "Gap since the last flight.beat() above which the heartbeat-gap "
   "watchdog fires an automatic local dump (one fire per gap "
   "episode; beats resuming re-arm it).")
_D("flight_loop_lag_s", float, 2.0,
   "Watchdog-loop wake overshoot above which the event-loop-lag "
   "watchdog fires: no thread getting scheduled for this long is a "
   "process-wide stall (GIL hog, swap storm, SIGSTOP).")
_D("flight_watchdog_period_s", float, 1.0,
   "Flight watchdog check period (also the event-loop-lag probe's "
   "expected sleep).")
_D("flight_dump_min_interval_s", float, 5.0,
   "Rate limit between watchdog auto-dumps: a flapping watchdog must "
   "not fill the disk with incident files.")
_D("flight_spill_period_s", float, 5.0,
   "Worker-process bundle spill period (jittered ±20%): nothing can "
   "dial a worker, so its hosting daemon merges these snapshots into "
   "its own debug_dump answer.")
_D("flight_spill_max_records", int, 8,
   "Rotate-at-capacity bound on a worker's bundle spill file: past "
   "this many snapshot lines the file restarts at the newest window, "
   "so a long-lived pooled worker spills O(capacity), not O(run). "
   "Merge reads only the NEWEST snapshot; the short history exists "
   "for manual forensics on a worker that died mid-incident, so keep "
   "this small — every line is a full bundle.")
_D("flight_task_stuck_s", float, 300.0,
   "An executing task (worker process / executor thread) past this "
   "bound fires the task-stuck watchdog — a hung worker auto-dumps "
   "without operator action (diagnostics only, never a kill; one "
   "fire per task).")
_D("flight_bundle_stale_s", float, 120.0,
   "Spilled worker bundles older than this are expired at merge "
   "time: a file left by an exited or re-leased pooled worker must "
   "not masquerade as a live process in an assembled incident.")
_D("serve_wake_timeout_s", float, 30.0,
   "Scale-to-zero wake bound: a request arriving at a deployment with "
   "zero replicas queues while the controller scales it back up, and "
   "fails typed only past this many seconds.")
_D("head_addresses", str, "",
   "Comma-separated head addresses, primary first then standbys "
   "(RAY_TPU_HEAD_ADDRESSES). Merged into every HeadClient's dial "
   "list and inherited by spawned node daemons, so the whole process "
   "tree learns the standby list and fails over without restarts "
   "('' = only the address passed to init/--address).")
_D("head_standby_probe_period_s", float, 1.0,
   "Warm-standby probe period: how often the standby head probes the "
   "primary's request channel before deciding it is dead.")
_D("head_standby_misses_to_promote", int, 3,
   "Consecutive failed standby probes before the standby promotes "
   "itself over the shared state log (promotion still waits on the "
   "log's flock fence — a stalled-but-alive primary blocks it).")
_D("head_dial_timeout_s", float, 5.0,
   "Per-address TCP dial bound when (re)connecting to a head: a "
   "client failing over walks its address list paying at most this "
   "much per unreachable standby (the heartbeat loop's re-dial budget "
   "rides the same bound).")
_D("head_failover_wait_s", float, 20.0,
   "How long in-flight head RPCs retry across a head blackout: the "
   "request coalescer replays unacked idempotent batches against "
   "re-dials (standby promotion window) up to this bound before "
   "failing callers; non-replayable relays fail immediately with "
   "HeadFailedOverError.")
_D("llm_kv_publish_ttl_s", float, 30.0,
   "Disaggregated serving publish TTL: a prefill replica's exported KV "
   "blocks (held for a decode replica's p2p pull) free automatically "
   "this many seconds after publication if never acked — a crashed or "
   "rerouted decode side can never leak prefill-pool blocks.")
_D("llm_disagg_pull_timeout_s", float, 10.0,
   "Disaggregated serving p2p pull bound: how long a decode replica "
   "waits for a published KV payload before abandoning the graft and "
   "transparently re-prefilling locally (typed fallback, not a hang).")
_D("llm_disagg_prefill_timeout_s", float, 30.0,
   "Disaggregated serving prefill RPC bound: how long the pairing "
   "layer waits for a prefill replica's ticket before falling back to "
   "the colocated path on the decode pool.")
