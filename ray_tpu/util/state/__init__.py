"""State API (reference role: ray/util/state — `ray list tasks/actors/...`,
summaries; backed there by GCS task events, here by the in-process
task-event buffer + worker registries)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from ray_tpu._private.worker import global_worker


@dataclass
class TaskState:
    task_id: str
    name: str
    state: str
    duration_s: Optional[float]
    node: str = ""  # shipping node's client id ("" = this runtime)


@dataclass
class ActorState:
    actor_id: str
    class_name: str
    name: Optional[str]
    state: str
    num_restarts: int


@dataclass
class WorkflowState:
    workflow_id: str
    status: str
    root: str
    updated_at: Optional[float]


@dataclass
class LLMEngineState:
    """One live inference engine's operational counters (the cache-
    effectiveness view operators watch: park/preemption pressure, block
    occupancy, prefix-cache hit rate and prefill tokens saved)."""

    engine_id: int
    tp_size: int
    steps: int
    running: int
    waiting: int
    generated_tokens: int
    prefill_tokens: int
    blocks_in_use: int
    free_blocks: int
    cached_free_blocks: int
    park_events: int
    num_preempted: int
    prefix_cache_queries: int
    prefix_cache_hits: int
    prefill_tokens_saved: int
    prefix_cache_hit_rate: float
    cow_copies: int
    max_prefill_tokens_per_step: int


@dataclass
class ObjectState:
    object_id: str
    ready: bool
    size_bytes: int
    local_refs: int
    submitted_refs: int
    spilled: bool


def list_tasks(filters: Optional[List] = None,
               limit: int = 1000) -> List[TaskState]:
    worker = global_worker()
    out: List[TaskState] = []
    for ev in worker.task_events.list_tasks(limit=limit * 4):
        st = TaskState(task_id=ev.task_id.hex(), name=ev.name,
                       state=ev.state, duration_s=ev.duration,
                       node=ev.extra.get("node", ""))
        if _matches(st, filters):
            out.append(st)
        if len(out) >= limit:
            break
    return out


def list_actors(filters: Optional[List] = None,
                limit: int = 1000) -> List[ActorState]:
    worker = global_worker()
    out = []
    for actor_id, runtime in list(worker.actors.items()):
        st = ActorState(
            actor_id=actor_id.hex(), class_name=runtime.class_name,
            name=runtime.actor_name,
            state="DEAD" if runtime.dead else "ALIVE",
            num_restarts=runtime.restarts_used)
        if _matches(st, filters):
            out.append(st)
        if len(out) >= limit:
            break
    return out


def list_objects(filters: Optional[List] = None,
                 limit: int = 1000) -> List[ObjectState]:
    worker = global_worker()
    out = []
    for oid, ready, size, lrefs, srefs, spilled in (
            worker.store.entries_snapshot()):
        st = ObjectState(object_id=oid.hex(), ready=ready, size_bytes=size,
                         local_refs=lrefs, submitted_refs=srefs,
                         spilled=spilled)
        if _matches(st, filters):
            out.append(st)
        if len(out) >= limit:
            break
    return out


def list_workflows(filters: Optional[List] = None,
                   limit: int = 1000) -> List[WorkflowState]:
    """Durable workflows under the process-global workflow storage
    root (set by ``workflow.init`` or the first run/resume)."""
    from ray_tpu.workflow.api import _ensure_storage

    out: List[WorkflowState] = []
    for rec in _ensure_storage(None).list_workflows():
        st = WorkflowState(
            workflow_id=rec.get("workflow_id", "?"),
            status=rec.get("status", "?"),
            root=rec.get("root", ""),
            updated_at=rec.get("updated_at"))
        if _matches(st, filters):
            out.append(st)
        if len(out) >= limit:
            break
    return out


def list_llm_engines(limit: int = 100) -> List[LLMEngineState]:
    """Inference engines alive in this process (`ray list llm-engines`
    role): the PR 5 scheduler counters (parks, preemptions, block
    occupancy) plus the prefix-cache effectiveness counters (hit rate,
    prefill tokens saved, copy-on-write copies) — what the dashboard's
    /api/llm endpoint serves."""
    try:
        from ray_tpu.llm.engine import live_engines
    except Exception:  # noqa: BLE001 — llm layer optional (needs jax)
        return []
    out: List[LLMEngineState] = []
    for eng in live_engines()[:limit]:
        st = eng.stats()
        out.append(LLMEngineState(
            engine_id=st["engine_id"],
            tp_size=st["tp_size"],
            steps=st["steps"],
            running=st["running"],
            waiting=st["waiting"],
            generated_tokens=st["generated_tokens"],
            prefill_tokens=st["prefill_tokens"],
            blocks_in_use=st["blocks_in_use"],
            free_blocks=st["free_blocks"],
            cached_free_blocks=st["cached_free_blocks"],
            park_events=st["park_events"],
            num_preempted=st["num_preempted"],
            prefix_cache_queries=st["prefix_cache_queries"],
            prefix_cache_hits=st["prefix_cache_hits"],
            prefill_tokens_saved=st["prefill_tokens_saved"],
            prefix_cache_hit_rate=st["prefix_cache_hit_rate"],
            cow_copies=st["cow_copies"],
            max_prefill_tokens_per_step=st["max_prefill_tokens_per_step"],
        ))
    return out


def summarize_llm_engines(
        engines: Optional[List[LLMEngineState]] = None) -> Dict[str, Any]:
    """Fleet-level cache-effectiveness rollup (dashboard panel)."""
    rows = engines if engines is not None else list_llm_engines()
    saved = sum(e.prefill_tokens_saved for e in rows)
    computed = sum(e.prefill_tokens for e in rows)
    return {
        "num_engines": len(rows),
        "running": sum(e.running for e in rows),
        "waiting": sum(e.waiting for e in rows),
        "generated_tokens": sum(e.generated_tokens for e in rows),
        "blocks_in_use": sum(e.blocks_in_use for e in rows),
        "park_events": sum(e.park_events for e in rows),
        "num_preempted": sum(e.num_preempted for e in rows),
        "prefill_tokens_saved": saved,
        "prefix_cache_hit_rate": (
            saved / (saved + computed) if (saved + computed) else 0.0),
    }


def summarize_workflows(
        workflows: Optional[List[WorkflowState]] = None) -> Dict[str, int]:
    """Per-status workflow counts; pass an existing ``list_workflows``
    result to avoid a second storage scan."""
    counts: Dict[str, int] = {}
    for wf in (workflows if workflows is not None else list_workflows()):
        counts[wf.status] = counts.get(wf.status, 0) + 1
    return counts


def chaos_summary() -> Dict[str, Any]:
    """Chaos + load-shedding panel (`/api/chaos` role): the active
    wire-fault config and per-site injected-fault counters, every kill
    recorded by NodeKillers in this process, and shed/admission stats
    from both shedding tiers — serve deployments (priority admission in
    the router) and LLM engines (waitqueue eviction). Always safe to
    call; all-zero/empty when chaos never ran and nothing shed."""
    from ray_tpu._private import chaos as _chaos

    out: Dict[str, Any] = _chaos.snapshot()

    # Serve-tier shedding: per-deployment admission stats off the live
    # controller singleton (never constructs one just to report zeros).
    serve_shedding: Dict[str, Any] = {}
    try:
        from ray_tpu.serve import controller as _controller

        ctl = _controller._controller
        if ctl is not None:
            with ctl._lock:
                infos = list(ctl._deployments.values())
            for info in infos:
                serve_shedding[info.name] = \
                    info.replica_set.admission_stats()
    except Exception:  # noqa: BLE001 — panel must not fail the API
        pass
    out["serve_shedding"] = serve_shedding
    out["serve_shed_total"] = sum(
        s.get("shed_total", 0) for s in serve_shedding.values())

    # LLM-tier shedding: waitqueue evictions per engine. Only consulted
    # when the llm layer is already loaded — the panel must not drag jax
    # into processes that never served a model.
    llm_shedding: Dict[int, Any] = {}
    try:
        import sys

        live_engines = (
            sys.modules["ray_tpu.llm.engine"].live_engines
            if "ray_tpu.llm.engine" in sys.modules else lambda: [])
        for eng in live_engines():
            st = eng.stats()
            llm_shedding[st["engine_id"]] = {
                "shed_requests": st.get("shed_requests", 0),
                "shed_by_class": st.get("shed_by_class", {}),
                "submitted_by_class": st.get("submitted_by_class", {}),
            }
    except Exception:  # noqa: BLE001 — llm layer optional (needs jax)
        pass
    out["llm_shedding"] = llm_shedding
    out["llm_shed_total"] = sum(
        s.get("shed_requests", 0) for s in llm_shedding.values())
    return out


def autoscaler_summary() -> Dict[str, Any]:
    """Elasticity panel (`/api/elastic` role): every live
    ClusterAutoscaler's launch/drain counters and scale-up events
    (``launch_attempts``/``launch_failures`` are the provider-level
    tries behind the typed ``NodeLaunchFailedError`` surface;
    ``scale_events`` carry join latency — the node half of the
    cold-start SLO), plus each serve deployment's scale/wake record
    (the replica half). Safe in any process; empty sections when
    nothing autoscales here."""
    scalers = []
    try:
        import sys

        live = (sys.modules["ray_tpu.autoscaler"].live_autoscalers
                if "ray_tpu.autoscaler" in sys.modules else lambda: [])
        for sc in live():
            scalers.append(sc.summary())
    except Exception:  # noqa: BLE001 — panel must not fail the API
        pass
    out: Dict[str, Any] = {
        "autoscalers": scalers,
        "launch_attempts": sum(s.get("launch_attempts", 0)
                               for s in scalers),
        "launch_failures": sum(s.get("launch_failures", 0)
                               for s in scalers),
        "launch_errors": sum(s.get("launch_errors", 0)
                             for s in scalers),
        "drained_nodes": sum(s.get("drained_nodes", 0)
                             for s in scalers),
        "drain_transferred_objects": sum(
            s.get("drain_transferred_objects", 0) for s in scalers),
    }
    serve_scaling: Dict[str, Any] = {}
    try:
        from ray_tpu.serve import controller as _controller

        ctl = _controller._controller
        if ctl is not None:
            for name, st in ctl.status().items():
                serve_scaling[name] = {
                    "replicas": st["replicas"],
                    "target_replicas": st["target_replicas"],
                    "scale_events": st["scale_events"],
                    "wake_events": st["wake_events"],
                    "last_wake_latency_s": st["last_wake_latency_s"],
                }
    except Exception:  # noqa: BLE001 — panel must not fail the API
        pass
    out["serve_scaling"] = serve_scaling
    try:
        router = getattr(global_worker(), "remote_router", None)
    except Exception:  # noqa: BLE001 — uninitialized process: the
        router = None  # summary stays safe (documented contract)
    if router is not None:
        out["drain_reroutes"] = router.drain_reroutes
        out["offloaded_objects"] = router.offloaded_objects
        out["fn_preship_sent"] = router.fn_preship_sent
    return out


def ownership_summary() -> Dict[str, Any]:
    """Ownership-directory panel (`/api/head` role): the head's
    steady-state RPC + FT-log-append counters — the PRODUCTION
    observables behind the "head stays O(membership), not O(objects)"
    claim — plus this runtime's owner/resolver counters (locations
    tracked, owner-direct locates/pulls served, head-fallback pulls).
    Safe without a head (local-only runtimes report their side only)."""
    w = global_worker()
    out: Dict[str, Any] = {}
    router = w.remote_router
    if router is not None:
        directory = router.owner_directory
        with router._lock:
            tracked = len(router._oid_owner)
        out["owner"] = {
            "locations_tracked": tracked,
            "locates_served": directory.locates_served,
            "notifies_sent": directory.notifies_sent,
            "owner_table_pulls": router.owner_table_pulls,
            "direct_done_reports": router.direct_done_reports,
            "relayed_done_reports": router.relayed_done_reports,
        }
    resolver = getattr(w, "owner_resolver", None)
    if resolver is not None:
        out["resolver"] = resolver.counters()
    if w.head_client is not None:
        try:
            out["head"] = w.head_client.head_stats()
        except Exception as exc:  # noqa: BLE001 — head down: local view
            out["head"] = {"error": repr(exc)}
    return out


def list_placement_groups(limit: int = 1000) -> List[Dict[str, Any]]:
    from ray_tpu.util.placement_group import placement_group_table

    return list(placement_group_table().values())[:limit]


def summarize_tasks() -> Dict[str, int]:
    return global_worker().task_events.summary()


def summarize_actors() -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for a in list_actors():
        counts[a.state] = counts.get(a.state, 0) + 1
    return counts


def summarize_objects() -> Dict[str, Any]:
    rows = global_worker().store.entries_snapshot()
    return {
        "num_objects": len(rows),
        "num_ready": sum(1 for r in rows if r[1]),
        "total_bytes": sum(r[2] for r in rows),
        "num_spilled": sum(1 for r in rows if r[5]),
    }


def get_timeline() -> List[dict]:
    """Chrome-tracing events (`ray timeline` parity)."""
    return global_worker().task_events.to_chrome_trace()


# ------------------------------------------------------------ tracing
def _pull_live_nodes(direct_msg: tuple, relay_fn) -> List[tuple]:
    """One payload from every live node, pulled CONCURRENTLY: direct
    object-server call (``direct_msg``) first, head relay
    (``relay_fn(client_id)``) as the fallback. Returns ``(node,
    payload)`` pairs; a node that answers neither way is skipped — its
    data is simply absent from this assembly, and the concurrent fan-
    out bounds a UI request's wall time to the slowest single node
    instead of the sum of every dead dial."""
    from concurrent.futures import ThreadPoolExecutor

    w = global_worker()
    hc = w.head_client
    router = w.remote_router
    if hc is None:
        return []
    nodes = [n for n in (router.nodes(refresh=True)
                         if router is not None else [])
             if n.get("alive")]
    if not nodes:
        return []

    def fetch(n):
        addr = n.get("peer_addr")
        if addr:
            try:
                return hc._peers.call((str(addr[0]), int(addr[1])),
                                      direct_msg)
            except Exception:  # noqa: BLE001 — NAT/dead dial
                pass
        try:
            return relay_fn(n["client_id"])
        except Exception:  # noqa: BLE001 — node mid-death: skipped
            return None

    with ThreadPoolExecutor(
            max_workers=min(8, len(nodes)),
            thread_name_prefix="state-node-pull") as pool:
        results = list(pool.map(fetch, nodes))
    return [(n, r) for n, r in zip(nodes, results) if r]


def collect_trace_spans(trace_id: Optional[str] = None) -> List[dict]:
    """Cluster-wide span collection (pull-based): this process's ring
    (+ its worker processes' spilled spans), the head's ring, and every
    live node's ``trace_dump`` — direct object-server call first, head
    relay as the fallback. Deduped by span id. Empty when tracing is
    off everywhere."""
    from ray_tpu._private import tracing

    spans: List[dict] = list(tracing.local_spans(trace_id))
    hc = global_worker().head_client
    if hc is not None:
        try:
            spans.extend(hc.trace_dump(trace_id or ""))
        except Exception:  # noqa: BLE001 — head down: local view only
            pass
        for _n, dumped in _pull_live_nodes(
                ("trace_dump", trace_id or ""),
                lambda cid: hc.node_trace_dump(cid, trace_id or "")):
            spans.extend(dict(s) for s in dumped)
    seen = set()
    out = []
    for s in spans:
        key = (s.get("span_id"), s.get("t0"), s.get("name"))
        if key in seen:
            continue
        seen.add(key)
        out.append(s)
    out.sort(key=lambda s: s.get("t0", 0.0))
    return out


def collect_trace_index() -> Dict[str, Dict[str, Any]]:
    """Cluster-wide per-trace aggregates (the ``/api/traces`` listing):
    same pull topology as :func:`collect_trace_spans`, but each source
    ships O(traces) aggregates instead of its full span ring."""
    from ray_tpu._private import tracing

    merged: Dict[str, Dict[str, Any]] = {}

    def fold(idx):
        for tid, r in (idx or {}).items():
            rec = merged.setdefault(tid, {
                "num_spans": 0, "processes": set(), "components": set(),
                "first_t0": r.get("first_t0", 0.0), "errors": 0,
                "root": ""})
            rec["num_spans"] += int(r.get("num_spans", 0))
            rec["processes"].update(r.get("pids", ()))
            rec["components"].update(r.get("components", ()))
            rec["first_t0"] = min(rec["first_t0"],
                                  r.get("first_t0", rec["first_t0"]))
            rec["errors"] += int(r.get("errors", 0))
            if not rec["root"] and r.get("root"):
                rec["root"] = r["root"]

    t = tracing.tracer()
    if t is not None:
        fold(t.trace_index())
    hc = global_worker().head_client
    if hc is not None:
        try:
            fold(hc.trace_index())
        except Exception:  # noqa: BLE001 — head down: local view only
            pass
        for _n, idx in _pull_live_nodes(
                ("trace_dump", "", True),
                lambda cid: hc.node_trace_index(cid)):
            fold(idx)
    return merged


def trace_summary(trace_id: Optional[str] = None) -> Dict[str, Any]:
    """Assembled cluster trace view. With ``trace_id``: every span of
    that trace (sorted), the distinct processes/components/nodes it
    crossed, and Chrome-tracing JSON. Without: an index of every trace
    any process currently holds spans for."""
    from ray_tpu._private import tracing

    if trace_id is None:
        traces = collect_trace_index()
        return {
            "num_traces": len(traces),
            "traces": {
                tid: {
                    "num_spans": r["num_spans"],
                    "num_processes": len(r["processes"]),
                    "components": sorted(r["components"]),
                    "first_t0": r["first_t0"],
                    "errors": r["errors"],
                    "root": r["root"],
                } for tid, r in traces.items()
            },
        }
    spans = collect_trace_spans(trace_id)
    # Node-qualified process identity: bare pids collide across hosts.
    procs = sorted({tracing.process_key(s.get("node", ""),
                                        s.get("pid", 0)) for s in spans})
    t0s = [s["t0"] for s in spans]
    ends = [s["t0"] + s.get("dur", 0.0) for s in spans]
    return {
        "trace_id": trace_id,
        "num_spans": len(spans),
        "spans": spans,
        "processes": procs,
        "num_processes": len(procs),
        "components": sorted({s.get("component", "")
                              for s in spans}),
        "nodes": sorted({s.get("node", "") for s in spans
                         if s.get("node")}),
        "errors": sum(1 for s in spans
                      if s.get("status") == "error"),
        "wall_span_s": (max(ends) - min(t0s)) if spans else 0.0,
        "chrome_trace": tracing.chrome_trace(spans),
    }


def trace_waterfall(trace_id: str) -> List[Dict[str, Any]]:
    """Per-request waterfall rows: spans of one trace sorted by start,
    with offsets from the trace's first span (the dashboard's
    per-request view)."""
    spans = collect_trace_spans(trace_id)
    if not spans:
        return []
    t0 = min(s["t0"] for s in spans)
    return [{
        "offset_s": s["t0"] - t0,
        "dur_s": s.get("dur", 0.0),
        "name": s["name"],
        "component": s.get("component", ""),
        "pid": s.get("pid", 0),
        "node": s.get("node", ""),
        "status": s.get("status", "ok"),
        "span_id": s.get("span_id", ""),
        "parent_id": s.get("parent_id", ""),
    } for s in spans]


def cluster_metrics() -> str:
    """One Prometheus text blob for the cluster, assembled from this
    process's registry plus every live node's ``metrics_dump`` (tagged
    ``node``/``component`` per source) — the driver-side twin of the
    head's ``/metrics`` scrape endpoint."""
    from ray_tpu.util.metrics import (
        export_prometheus,
        merge_prometheus,
        refresh_framework_metrics,
        relabel_prometheus,
    )

    w = global_worker()
    refresh_framework_metrics(w)
    parts = [relabel_prometheus(
        export_prometheus(), {"node": "driver", "component": "driver"})]
    if w.head_client is not None:
        hc = w.head_client
        for n, text in _pull_live_nodes(
                ("metrics_dump",),
                lambda cid: hc.node_metrics_dump(cid)):
            parts.append(relabel_prometheus(
                str(text), {"node": n["client_id"],
                            "component": "node"}))
    return merge_prometheus(parts)


# ------------------------------------------------------- flight recorder
def collect_debug_bundles() -> Dict[str, dict]:
    """Every live process's flight bundle, keyed by a cluster-unique
    source name: this driver (plus its own worker processes' spilled
    bundles), the head, and every live node (each node bundle nests
    its hosted workers under ``workers``). Pull-based over the same
    topology as span collection — direct object-server call first,
    head relay fallback — so steady state costs ZERO head RPCs.
    Sources with the recorder disarmed are absent."""
    from ray_tpu._private import flight
    from ray_tpu.util.metrics import refresh_framework_metrics

    out: Dict[str, dict] = {}
    try:
        # Register + refresh the framework gauges so the driver's
        # bundle carries a current metrics snapshot (daemons refresh
        # inside their own debug_dump handlers).
        refresh_framework_metrics(global_worker())
    except Exception:  # noqa: BLE001 — metrics are best-effort here
        pass
    local = flight.local_bundle(include_dir=True)
    if local:
        out["driver"] = local
    hc = global_worker().head_client
    if hc is not None:
        try:
            head_bundle = hc.debug_dump()
            if head_bundle:
                out["head"] = head_bundle
        except Exception:  # noqa: BLE001 — head down: partial incident
            pass
        for n, bundle in _pull_live_nodes(
                ("debug_dump",),
                lambda cid: hc.node_debug_dump(cid)):
            if bundle:
                out[f"node-{n['client_id']}"] = dict(bundle)
    return out


def cluster_dump(out_dir: Optional[str] = None) -> str:
    """One-command postmortem collection: pull every live process's
    flight bundle and write ONE directory-per-incident archive —
    ``<out_dir>/debug-<stamp>-<id>/`` holding one ``<source>.json``
    per process (worker bundles split out of their daemon's answer as
    ``<source>.worker-<pid>.json``) plus a ``manifest.json`` index.
    Returns the incident directory path."""
    import json
    import os
    import time
    import uuid

    bundles = collect_debug_bundles()
    root = out_dir or os.path.join(
        global_worker().session_dir, "debug_dumps")
    incident = os.path.join(
        root, f"debug-{time.strftime('%Y%m%d-%H%M%S')}-"
              f"{uuid.uuid4().hex[:6]}")
    os.makedirs(incident, exist_ok=True)
    manifest = {"ts": time.time(), "sources": {}}
    for source, bundle in sorted(bundles.items()):
        workers = bundle.pop("workers", []) or []
        with open(os.path.join(incident, f"{source}.json"), "w") as f:
            json.dump(bundle, f, indent=1)
        files = [f"{source}.json"]
        for wb in workers:
            wname = f"{source}.worker-{wb.get('pid', 0)}.json"
            with open(os.path.join(incident, wname), "w") as f:
                json.dump(wb, f, indent=1)
            files.append(wname)
        manifest["sources"][source] = {
            "files": files,
            "pid": bundle.get("pid"),
            "component": bundle.get("component"),
            "node": bundle.get("node"),
            "watchdog_fires": bundle.get("watchdog_fires", 0),
            "num_workers": len(workers),
        }
    manifest["num_processes"] = sum(
        1 + s["num_workers"] for s in manifest["sources"].values())
    with open(os.path.join(incident, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return incident


def set_cluster_profiling(on: bool) -> Dict[str, bool]:
    """Pause/resume the stack sampler on THIS process, the head, and
    every live node daemon (the in-session A/B the flight_overhead
    bench runs, and the operator's live-toggle). Returns {source:
    running} per REACHED sampler — every answer is a dict on the
    wire, so a successful pause is distinguishable from an
    unreachable node (absent from the result). Worker processes are
    not dialable and keep their samplers running; their cost is
    bounded by profile_hz either way (the flight_overhead probe uses
    thread-mode nodes, so its A/B legs carry no hidden worker
    sampling)."""
    from ray_tpu._private import flight

    out = {"driver": flight.set_profiling(on)}
    hc = global_worker().head_client
    if hc is not None:
        try:
            head_state = hc.flight_ctl_head(on)
            if head_state:
                out["head"] = bool(head_state.get("running"))
        except Exception:  # noqa: BLE001 — head down: partial toggle
            pass
        for n, state in _pull_live_nodes(
                ("flight_ctl", "profile", bool(on)),
                lambda cid: hc.node_flight_ctl(cid, on)):
            if isinstance(state, dict):
                out[f"node-{n['client_id']}"] = \
                    bool(state.get("running"))
    return out


def _matches(item, filters) -> bool:
    if not filters:
        return True
    for key, op, value in filters:
        actual = getattr(item, key, None)
        if op in ("=", "=="):
            if str(actual) != str(value):
                return False
        elif op == "!=":
            if str(actual) == str(value):
                return False
        else:
            raise ValueError(f"unsupported filter op {op!r}")
    return True
