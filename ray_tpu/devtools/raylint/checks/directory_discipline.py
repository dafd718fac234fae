"""directory-discipline: the centralized object directory cannot
silently creep back.

PR 10 made the object directory OWNERSHIP-based: locations live with
the driver that created the refs, peers resolve owner-direct over the
p2p plane, and the head keeps only membership + a FALLBACK directory
(relay-path announces, lease-transferred tables of exited drivers).
Every call of a head object-directory RPC —
``object_announce``/``object_announce_many`` (per-object head appends),
``object_locate``/``object_pull``/``object_pull_from`` (head location/
relay reads) and ``object_transfer_many`` (the lease handoff) — is
therefore a deliberate FALLBACK site, enumerated in
``ALLOWED_FALLBACK_SITES`` as (repo-relative path, enclosing scope,
method). A directory RPC anywhere else fires; the committed baseline
for this check starts (and must stay) EMPTY — a new steady-state head
dependency is a gate failure, not a baseline entry.

Matching is by ATTRIBUTE-CALL name (``<recv>.object_announce(...)``),
so the client method *definitions* in ``head_client.py`` and the wire
kind literals (``("object_announce", ...)`` tuples) do not fire.
"""

from __future__ import annotations

import ast
from typing import List, Set, Tuple

from ray_tpu.devtools.raylint.core import Checker, Finding, register
from ray_tpu.devtools.raylint.walker import ModuleInfo

# Head object-directory RPC surface (client-method spellings).
DIRECTORY_RPCS = frozenset({
    "object_announce",
    "object_announce_many",
    "object_locate",
    "object_pull",
    "object_pull_from",
    "object_transfer_many",
})

# The allowlisted fallback set: (path, scope, method). Keep this the
# COMPLETE inventory of legal centralized-directory touches — each with
# the reason it may exist.
ALLOWED_FALLBACK_SITES: Set[Tuple[str, str, str]] = {
    # Node daemon: per-driver RELAY fallback (NAT'd / undialable driver)
    # announces that batch's streamed-item locations so the relayed
    # consumer can resolve them via the head.
    ("ray_tpu/_private/node_daemon.py", "NodeDaemon._report_loop",
     "object_announce_many"),
    # Node daemon drain-before-reap: after offloading node-held result
    # bytes to their owning drivers, the head's FALLBACK directory
    # entries naming this (exiting) node as holder re-point at the new
    # holder — the same lease-handoff RPC the router's shutdown uses.
    ("ray_tpu/_private/node_daemon.py", "NodeDaemon._on_node_drain",
     "object_transfer_many"),
    # Consumer-side resolver: the head IS the fallback directory when
    # the owner is unreachable/ignorant, and the relay-from-named-holder
    # data path for pullers that cannot dial the holder.
    ("ray_tpu/_private/ownership.py", "OwnerResolver.resolve",
     "object_pull"),
    ("ray_tpu/_private/ownership.py", "OwnerResolver.resolve",
     "object_pull_from"),
    # Driver router: recovery pulls (missed task_done across a head
    # restart, lease-transferred entries) + relay-from-holder fallback
    # + the one-shot lease handoff on graceful shutdown.
    ("ray_tpu/_private/remote_router.py", "RemoteRouter.ensure_local",
     "object_pull"),
    ("ray_tpu/_private/remote_router.py", "RemoteRouter.ensure_local",
     "object_pull_from"),
    ("ray_tpu/_private/remote_router.py", "RemoteRouter.shutdown",
     "object_transfer_many"),
    # Worker: the EXPLICIT user announce API, and the owner-less
    # foreign-ref fallback (hex-constructed refs carry no owner).
    ("ray_tpu/_private/worker.py", "Worker.announce_object",
     "object_announce"),
    ("ray_tpu/_private/worker.py", "Worker._maybe_pull_from_head",
     "object_pull"),
    # Cross-driver actor relay plane (head-relayed by design: the
    # caller may not be able to dial the owner): announce-then-pull.
    ("ray_tpu/_private/head_client.py", "HeadClient._handle_event",
     "object_announce"),
    ("ray_tpu/_private/remote_actor.py", "ActorHost._report",
     "object_announce_many"),
    ("ray_tpu/_private/remote_actor.py", "unwire_arg", "object_pull"),
    ("ray_tpu/actor.py", "_CrossDriverMethod.remote._run",
     "object_pull"),
}


@register
class DirectoryDiscipline(Checker):
    name = "directory-discipline"
    description = ("head object-directory RPCs outside the allowlisted "
                   "fallback set (the centralized path must not creep "
                   "back)")

    def run(self, modules: List[ModuleInfo], ctx) -> List[Finding]:
        findings: List[Finding] = []
        for mod in modules:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                fn = node.func
                if not isinstance(fn, ast.Attribute) or \
                        fn.attr not in DIRECTORY_RPCS:
                    continue
                scope = mod.scope_name(node)
                if (mod.relpath, scope, fn.attr) in \
                        ALLOWED_FALLBACK_SITES:
                    continue
                findings.append(Finding(
                    check=self.name,
                    path=mod.relpath,
                    line=node.lineno,
                    scope=scope,
                    detail=f"rpc:{fn.attr}",
                    message=(
                        f"head object-directory RPC {fn.attr!r} outside "
                        f"the allowlisted fallback set — steady-state "
                        f"object traffic must stay owner-direct "
                        f"(ownership directory); add a deliberate "
                        f"fallback to ALLOWED_FALLBACK_SITES with its "
                        f"reason, or resolve through the owner"),
                ))
        return findings
