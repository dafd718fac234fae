"""ray_tpu: a TPU-native distributed execution framework.

A from-scratch rebuild of the capability surface of Ray (reference:
Nicolaus93/ray — see SURVEY.md) designed TPU-first: dynamic tasks and actors
with ObjectRef futures and an ownership-based local runtime, plus a compiled
dataflow-graph executor that lowers static DAGs to a single JAX program where
dependency resolution and argument movement run as batched ops over an
HBM-resident task/object table (the north star of BASELINE.json), and a
jax-native parallelism layer (DP/FSDP/TP/PP/SP-CP/EP) in place of external
NCCL integrations.

Public API parity map (reference python/ray/__init__.py [unverified]):
init/shutdown, @remote, get/put/wait/cancel/kill, ObjectRef, ActorHandle,
get_actor, runtime context, plus subpackages dag/, data/, train/, tune/,
serve/, rl/ (rllib), workflow/ (durable crash-resumable step DAGs),
collective/, util/.
"""

from ray_tpu._private.compile_cache import ensure_compile_cache
from ray_tpu._private.config import GlobalConfig as _config  # noqa: F401
from ray_tpu._private.worker import (
    ObjectRef,
    ObjectRefGenerator,
    cancel,
    get,
    init,
    is_initialized,
    put,
    shutdown,
    wait,
)
from ray_tpu.actor import ActorClass, ActorHandle, get_actor, kill
from ray_tpu.remote_function import RemoteFunction, method, remote
from ray_tpu.runtime_context import get_runtime_context
from ray_tpu import exceptions

__version__ = "0.1.0"

# Every process that compiles imports this package first (drivers, worker
# processes, node daemons), so this is the one call site.
ensure_compile_cache()


def announce_object(ref) -> None:
    """Publish an object to the head's object directory so OTHER attached
    drivers can ``ray_tpu.get`` it (requires init(address=...))."""
    from ray_tpu._private.worker import global_worker

    global_worker().announce_object(ref)

__all__ = [
    "ActorClass",
    "ActorHandle",
    "ObjectRef",
    "ObjectRefGenerator",
    "RemoteFunction",
    "announce_object",
    "cancel",
    "debug_dump",
    "exceptions",
    "get",
    "get_actor",
    "get_runtime_context",
    "init",
    "is_initialized",
    "kill",
    "method",
    "put",
    "remote",
    "shutdown",
    "timeline",
    "wait",
    "__version__",
]


def timeline(trace_id=None, filename=None):
    """Chrome-tracing JSON (``ray.timeline`` parity). Without
    ``trace_id``: this driver's task-event timeline (which now includes
    node-shipped events). With ``trace_id`` (tracing armed via
    RAY_TPU_TRACE): the CLUSTER-WIDE assembled trace — spans pulled
    from every process the request crossed. ``filename`` writes the
    JSON for chrome://tracing / Perfetto and returns the path."""
    if trace_id is not None:
        from ray_tpu.util.state import trace_summary

        events = trace_summary(trace_id)["chrome_trace"]
    else:
        from ray_tpu.util.state import get_timeline

        events = get_timeline()
    if filename is not None:
        import json as _json

        with open(filename, "w") as f:
            _json.dump(events, f)
        return filename
    return events


def debug_dump(out_dir=None):
    """One-command postmortem collection (flight-recorder plane, armed
    via ``RAY_TPU_FLIGHT`` / ``RAY_TPU_PROFILE``): pull every live
    process's debug bundle — all-thread stacks, event rings, profile
    aggregates, metrics/chaos snapshots, subsystem sections — over the
    direct object-server plane (head relay fallback) and write one
    directory-per-incident archive. Returns the incident directory."""
    from ray_tpu.util.state import cluster_dump

    return cluster_dump(out_dir)


def available_resources():
    from ray_tpu._private.worker import global_worker

    return global_worker().resource_pool.available()


def cluster_resources():
    from ray_tpu._private.worker import global_worker

    return global_worker().resource_pool.total
