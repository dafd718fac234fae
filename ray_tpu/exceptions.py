"""Typed error surface.

Mirrors the reference's exception hierarchy (reference:
python/ray/exceptions.py [unverified]) so users migrating from it find the
same failure vocabulary: remote task errors carry the reconstructed remote
traceback; object loss / worker death / timeouts are distinct types.
"""

from __future__ import annotations

import traceback
from typing import Optional


class RayTpuError(Exception):
    """Base class for all framework errors."""


class RayTaskError(RayTpuError):
    """A task raised an exception remotely; re-raised at `get`.

    Holds the original exception class, message, and remote traceback, and
    re-raises as a subclass of the original type where possible so user
    ``except`` clauses still match.
    """

    def __init__(self, function_name: str, traceback_str: str,
                 cause: Optional[BaseException] = None):
        self.function_name = function_name
        self.traceback_str = traceback_str
        self.cause = cause
        super().__init__(
            f"task {function_name!r} failed:\n{traceback_str}"
        )

    @classmethod
    def from_exception(cls, function_name: str, exc: BaseException):
        tb = "".join(
            traceback.format_exception(type(exc), exc, exc.__traceback__)
        )
        return cls(function_name, tb, cause=exc)

    def __reduce__(self):
        # Cross-process transport: keep the cause when it pickles (typed
        # re-raise via as_instanceof_cause), drop it otherwise — default
        # Exception reduction would call __init__ with the formatted
        # message only and fail.
        import pickle as _pickle

        cause = self.cause
        if cause is not None:
            try:
                _pickle.dumps(cause)
            except Exception:  # noqa: BLE001 — unpicklable cause
                cause = None
        return (RayTaskError,
                (self.function_name, self.traceback_str, cause))

    def as_instanceof_cause(self) -> BaseException:
        """Return an exception that is `isinstance` of the original type."""
        if self.cause is None:
            return self
        cause_cls = type(self.cause)
        if isinstance(self.cause, RayTaskError):
            # Double wrap (a stage re-wrapped an already-typed remote
            # error): surface the innermost original type.
            return self.cause.as_instanceof_cause()
        if issubclass(cause_cls, RequestSheddedError):
            # Shed-by-policy must stay matchable (`except
            # RequestSheddedError`) and keep its priority/retry_after_s
            # even when the shed happened inside a process-backed
            # replica and crossed the wire wrapped as a task error —
            # overload is policy, not a task failure, so the client
            # retry contract depends on the exact type surviving.
            return self.cause
        if issubclass(cause_cls, RayTpuError):
            return self
        try:
            derived = type(
                "RayTaskError(" + cause_cls.__name__ + ")",
                (RayTaskError, cause_cls),
                {"__init__": lambda s: None},
            )()
            derived.function_name = self.function_name
            derived.traceback_str = self.traceback_str
            derived.cause = self.cause
            derived.args = (str(self),)
            return derived
        except TypeError:
            return self


class RayActorError(RayTpuError):
    """The actor died before or while executing the task."""

    def __init__(self, actor_id=None, message: str = ""):
        self.actor_id = actor_id
        super().__init__(message or f"actor {actor_id} is dead")


class ActorDiedError(RayActorError):
    pass


class ActorUnavailableError(RayActorError):
    """Actor is temporarily unreachable (restarting)."""


class TaskCancelledError(RayTpuError):
    def __init__(self, task_id=None):
        self.task_id = task_id
        super().__init__(f"task {task_id} was cancelled")


class GetTimeoutError(RayTpuError, TimeoutError):
    pass


class ObjectLostError(RayTpuError):
    def __init__(self, object_ref=None, message: str = ""):
        self.object_ref = object_ref
        super().__init__(message or f"object {object_ref} was lost")


class ObjectReconstructionFailedError(ObjectLostError):
    pass


class OwnerDiedError(ObjectLostError):
    pass


class WorkerCrashedError(RayTpuError):
    pass


class WorkerPoolExhaustedError(RayTpuError):
    """No worker process became idle within the lease deadline. System
    condition (pool pressure), not a task failure — treated as retriable."""


class OutOfMemoryError(RayTpuError):
    pass


class RuntimeEnvSetupError(RayTpuError):
    pass


class PendingCallsLimitExceededError(RayTpuError):
    pass


class RequestSheddedError(RayTpuError):
    """The request was refused (or evicted pre-admission) by the load-
    shedding policy under overload — NOT a failure of the request
    itself. Retryable after ``retry_after_s``; the HTTP proxy maps it
    to 503 + Retry-After. ``priority`` is the shed request's class
    (0 = most important; higher classes shed first)."""

    def __init__(self, message: str = "", priority: int = 0,
                 retry_after_s: float = 1.0):
        self.priority = priority
        self.retry_after_s = retry_after_s
        super().__init__(
            message or f"request (priority class {priority}) shed by "
                       f"load-shedding policy; retry after "
                       f"{retry_after_s:.1f}s")


class PlacementInfeasibleError(RayTpuError, ValueError):
    """No local capacity and no feasible cluster node for a resource
    demand RIGHT NOW — a capacity condition, not a bug: autoscalers
    read the parked shape and launch for it, and placement retries.
    Subclasses ValueError for pre-existing callers that matched the
    untyped raise."""


class NodeLaunchFailedError(RayTpuError):
    """The autoscaler's provider could not bring a node up within its
    bounded, jittered retry budget — a typed infrastructure failure,
    not silent membership absence. ``attempts`` is how many launches
    were tried; ``node_type`` names the shape that failed."""

    def __init__(self, node_type: str = "", attempts: int = 0,
                 message: str = ""):
        self.node_type = node_type
        self.attempts = attempts
        super().__init__(
            message or f"node type {node_type!r} failed to launch after "
                       f"{attempts} attempt(s)")


class HeadFailedOverError(RayTpuError, ConnectionError):
    """The head failed over (or fenced itself after losing a
    promotion race) while this call was in flight. Surfaced only for
    genuinely non-replayable calls: idempotent head RPCs are replayed
    against the promoted head transparently, but a relayed side effect
    (actor_call/actor_push) whose reply was lost may or may not have
    executed — the caller must decide whether to retry. Also the typed
    refusal a FENCED old primary answers every post-promotion request
    with (its epoch regressed below the cluster's), so a client on a
    stale connection fails over instead of writing into a dead
    incarnation. Subclasses ConnectionError so pre-existing
    reconnect-on-ConnectionError paths keep working."""

    def __init__(self, message: str = "", epoch: int = 0):
        self.epoch = epoch
        super().__init__(
            message or "the head failed over while this call was in "
                       "flight; the call may or may not have executed")


class NodeDrainingError(RayTpuError):
    """A task push landed on a node already chosen for reap: the node
    refused it (drain-before-reap cordon) instead of accepting work it
    would never report. Routers reroute on this — it is a routing
    race, not a task failure."""

    def __init__(self, node_client: str = ""):
        self.node_client = node_client
        super().__init__(
            f"node {node_client!r} is draining for reap and refuses "
            f"new work (rerouted)")


class ChannelError(RayTpuError):
    """Compiled-graph channel failure (closed, timeout, version skew)."""


class ChannelTimeoutError(ChannelError, TimeoutError):
    pass
