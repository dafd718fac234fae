"""flag-hygiene: every ``RAY_TPU_*`` flag flows through
``_private/config.py``, is declared exactly once with a doc string,
and is documented in the README flag table.

Sub-checks:

- **env-read-outside-config** — ``os.environ.get("RAY_TPU_X")`` /
  ``os.getenv`` / ``os.environ[...]`` reads anywhere but
  ``_private/config.py``. Config is the single choke point: it gives
  every flag a type, a default, ``_system_config`` override, and one
  place to audit. Bootstrap *identity* flags a process must read
  before config can load (cluster token, platform, spawned-process
  ids, sanitizer/chaos arming) are exempted by the explicit
  ``BOOTSTRAP_ENV_FLAGS`` allowlist — but still must be documented.
- **undeclared-flag** — attribute access ``GlobalConfig.foo`` where no
  ``declare("foo", ...)`` exists (a typo'd flag silently reads as an
  AttributeError at runtime; here it is caught at lint time).
- **undocumented-flag** — a ``declare()`` with an empty ``doc``.
- **flag-not-in-readme** — any surfaced flag (declared or bootstrap)
  missing from README.md's flag table.

Env *writes* are exempt everywhere: parents legitimately inject
``RAY_TPU_*`` into spawned daemons/workers.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from ray_tpu.devtools.raylint.core import Checker, Finding, register
from ray_tpu.devtools.raylint.walker import ModuleInfo

# Flags a process must be able to read before (or without) importing
# _private/config.py: bootstrap identity and tool-arming switches.
# Every entry must be documented in README.md's flag table.
BOOTSTRAP_ENV_FLAGS: Set[str] = {
    "RAY_TPU_CLUSTER_TOKEN",     # transport auth — read pre-handshake
    "RAY_TPU_NUM_PROCESSES",     # multi-process identity, set by launcher
    "RAY_TPU_PROCESS_ID",        # multi-process identity, set by launcher
    "RAY_TPU_PARENT_PID",        # spawner pid for the worker orphan fence
    "RAY_TPU_SESSION_LOG_DIR",   # injected per spawned worker/daemon
    "RAY_TPU_SANITIZE",          # sanitizer arming — must work standalone
    "RAY_TPU_SANITIZE_MODE",     # sanitizer raise-vs-warn
    "RAY_TPU_CHAOS",             # chaos arming — inherited by children
    "RAY_TPU_TRACE",             # tracing arming — inherited by children
    "RAY_TPU_TRACE_DIR",         # span spill dir for worker processes
    "RAY_TPU_TRACE_PARENT",      # cold-start trace ctx for launched nodes
    "RAY_TPU_TRACE_NODE",        # node identity for spawned processes' spans
    "RAY_TPU_FLIGHT",            # flight-recorder arming — inherited
    "RAY_TPU_PROFILE",           # stack-sampler arming — inherited
    "RAY_TPU_FLIGHT_DIR",        # bundle spill/auto-dump dir for children
    "RAY_TPU_FLIGHT_DIR_AUTO",   # marks FLIGHT_DIR as runtime-auto-pointed
    "RAY_TPU_FLIGHT_NODE",       # node identity for spawned processes' bundles
}

_FLAG_RE = re.compile(r"RAY_TPU_[A-Z0-9_]+")
_CONFIG_API = {"get", "set", "declare", "apply_system_config", "reset",
               "describe"}


def _parse_declared(config_path: str) -> Tuple[Dict[str, Tuple[int, str]],
                                               Optional[str]]:
    """{flag_name: (lineno, doc)} parsed from config.py, plus an error
    message when the file is unreadable."""
    try:
        with open(config_path, "r", encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=config_path)
    except (OSError, SyntaxError) as exc:
        return {}, str(exc)
    declared: Dict[str, Tuple[int, str]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        is_declare = (isinstance(func, ast.Name) and func.id == "_D") or \
            (isinstance(func, ast.Attribute) and func.attr == "declare")
        if not is_declare or not node.args:
            continue
        first = node.args[0]
        if not (isinstance(first, ast.Constant) and
                isinstance(first.value, str)):
            continue
        doc = ""
        if len(node.args) >= 4 and isinstance(node.args[3], ast.Constant):
            doc = str(node.args[3].value)
        for kw in node.keywords:
            if kw.arg == "doc" and isinstance(kw.value, ast.Constant):
                doc = str(kw.value.value)
        declared[first.value] = (node.lineno, doc)
    return declared, None


@register
class FlagHygiene(Checker):
    name = "flag-hygiene"
    description = ("RAY_TPU_* env reads outside config.py; undeclared / "
                   "undocumented flags")

    def run(self, modules: List[ModuleInfo], ctx) -> List[Finding]:
        findings: List[Finding] = []
        config_relpath = getattr(ctx, "config_relpath",
                                 "ray_tpu/_private/config.py")
        config_path = os.path.join(ctx.root, config_relpath)
        declared, err = _parse_declared(config_path)
        if err is not None:
            findings.append(Finding(
                check=self.name, path=config_relpath, line=1,
                scope="<module>", detail="config-unreadable",
                message=f"cannot parse flag registry: {err}"))
        declared_env = {"RAY_TPU_" + name.upper() for name in declared}
        surfaced: Set[str] = set(declared_env) | set(BOOTSTRAP_ENV_FLAGS)

        for name, (lineno, doc) in sorted(declared.items()):
            if not doc.strip():
                findings.append(Finding(
                    check=self.name, path=config_relpath, line=lineno,
                    scope="<module>", detail=f"undocumented:{name}",
                    message=f"flag {name!r} declared without a doc "
                            f"string"))

        for mod in modules:
            if mod.relpath == config_relpath:
                continue
            self._scan_module(mod, declared_env, findings)

        findings.extend(self._readme_findings(ctx, surfaced))
        return findings

    # ------------------------------------------------------------- per-module
    def _scan_module(self, mod: ModuleInfo, declared_env: Set[str],
                     findings: List[Finding]) -> None:
        for node in ast.walk(mod.tree):
            env_name, lineno = self._env_read(mod, node)
            if env_name is None:
                continue
            if env_name in BOOTSTRAP_ENV_FLAGS:
                continue
            hint = "declare it in _private/config.py and read it via " \
                   "GlobalConfig" if env_name not in declared_env else \
                   "read it via GlobalConfig so _system_config " \
                   "overrides apply"
            findings.append(Finding(
                check=self.name, path=mod.relpath, line=lineno,
                scope=mod.scope_name(node),
                detail=f"env-read:{env_name}",
                message=(f"direct os.environ read of {env_name} outside "
                         f"_private/config.py — {hint}")))

        # GlobalConfig.<attr> accesses against the declared set
        declared_attrs = {e[len("RAY_TPU_"):].lower()
                          for e in declared_env}
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Attribute):
                continue
            base = node.value
            if not (isinstance(base, ast.Name) and
                    base.id == "GlobalConfig"):
                continue
            attr = node.attr
            if attr.startswith("_") or attr in _CONFIG_API:
                continue
            if attr not in declared_attrs:
                findings.append(Finding(
                    check=self.name, path=mod.relpath, line=node.lineno,
                    scope=mod.scope_name(node),
                    detail=f"undeclared:{attr}",
                    message=(f"GlobalConfig.{attr} is not declared in "
                             f"_private/config.py — typo or missing "
                             f"declare()")))

    def _env_read(self, mod: ModuleInfo, node: ast.AST):
        """(env_name, lineno) when ``node`` reads a RAY_TPU_* env var,
        else (None, 0)."""
        if isinstance(node, ast.Call):
            canonical = mod.canonical(node.func)
            if canonical.endswith("environ.get") or \
                    canonical == "os.getenv" or \
                    canonical.endswith(".getenv"):
                if node.args and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str) and \
                        node.args[0].value.startswith("RAY_TPU_"):
                    return node.args[0].value, node.lineno
        elif isinstance(node, ast.Subscript) and \
                isinstance(node.ctx, ast.Load):
            canonical = mod.canonical(node.value)
            if canonical.endswith("os.environ"):
                sl = node.slice
                if isinstance(sl, ast.Constant) and \
                        isinstance(sl.value, str) and \
                        sl.value.startswith("RAY_TPU_"):
                    return sl.value, node.lineno
        return None, 0

    # ---------------------------------------------------------------- readme
    def _readme_findings(self, ctx, surfaced: Set[str]) -> List[Finding]:
        readme_path = getattr(ctx, "readme_path", None)
        if not readme_path or not os.path.exists(readme_path):
            return []
        with open(readme_path, "r", encoding="utf-8") as f:
            readme = f.read()
        documented = set(_FLAG_RE.findall(readme))
        out = []
        for env_name in sorted(surfaced - documented):
            out.append(Finding(
                check=self.name, path=os.path.basename(readme_path),
                line=1, scope="<readme>",
                detail=f"not-in-readme:{env_name}",
                message=(f"{env_name} is a live flag but is missing from "
                         f"the README flag table")))
        return out
