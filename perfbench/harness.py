"""What every driver shares: the manifest, the device, the result line.

``BENCHMARK.json`` names everything; the files are found by those names
(``configs/<config>.json`` by the manifest's ``file``, ``traffic/<mix>.json``,
``layer_metrics/<metric>.py``, ``drivers/<driver>.py``), so a later PR adds
a cell, a mix, a metric or a driver by adding files and entries.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
OUT_DIR = os.path.join(ROOT, "perfbench_out")        # in .gitignore
LINE_KEYS = ("correct", "attempted", "failed", "metrics", "device")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def find(paths: list, *parts: str) -> str:
    """The first ``<path>/<parts...>`` that exists among the manifest's
    ``paths``: where a mix, a reader or a driver of that name lives."""
    for base in paths:
        candidate = os.path.join(ROOT, base, *parts)
        if os.path.exists(candidate):
            return candidate
    raise SystemExit(f"perfbench: no {os.path.join(*parts)} under {paths}")


def load_cell(workload: str, manifest_path: str = MANIFEST) -> dict:
    """Everything one run needs, gathered by name from the manifest."""
    manifest = load_json(manifest_path)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; have {sorted(cells)}")
    entry = cells[workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[entry["config"]]
    paths = manifest["paths"]
    return {
        "name": workload, "chips": int(entry["chips"]), "paths": paths,
        "config": load_json(os.path.join(ROOT, cfg_entry["file"])),
        "traffic": load_json(find(paths, "traffic",
                                  entry["traffic"] + ".json")),
        "end_to_end": [m for m in manifest["end_to_end"]
                       if applies(m, workload)],
        "per_layer": [m for m in manifest["per_layer"]
                      if applies(m, workload)],
    }


def set_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    the environment names one. Called before jax is imported; the program
    (ray_tpu/_private/compile_cache.py) resolves the same path."""
    path = os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    return path


def require_chips(chips: int) -> list:
    """The accelerator devices of this process, or an error: a
    measurement never falls back to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise SystemExit("perfbench: JAX found no accelerator "
                         f"(platform {devices[0].platform!r}); not measuring")
    if len(devices) < chips:
        raise SystemExit(f"perfbench: the cell asks for {chips} chip(s), "
                         f"JAX sees {len(devices)}")
    return devices[:chips]


def peak(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table or not isinstance(table[kind], dict):
        raise SystemExit(f"perfbench: device kind {kind!r} is not in "
                         f"peaks.json; add it with its source, no default")
    return table[kind]


def device_block(devices: list) -> dict:
    """platform, kind, count as JAX reports them, and the peak bytes on
    the fullest chip. Read after the window, before any reference runs."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": int(max(peaks))}


def reader(paths: list, metric: str):
    """The ``read(ctx)`` of ``layer_metrics/<metric>.py``."""
    path = find(paths, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_layer_metric_" + metric.replace(".", "_").replace(
            "-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_layer_metrics(cell: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell through its own reader; one that
    finds nothing to read returns None and is left out of the line."""
    out = {}
    for m in cell["per_layer"]:
        value = reader(cell["paths"], m["name"])(ctx)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def driver(name: str):
    return importlib.import_module("perfbench.drivers." + name)


def build_line(cell: dict, trace: bool, result: dict) -> dict:
    """The last line: the contract's keys first, ``checks`` last."""
    if trace:
        metrics = result["per_layer"]
    else:
        missing = [m["name"] for m in cell["end_to_end"]
                   if m["name"] not in result["end_to_end"]]
        if missing:
            raise SystemExit(f"perfbench: the window gave no {missing}")
        metrics = {m["name"]: {"value": float(result["end_to_end"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": result["device"]}
    if trace and result.get("breakdown"):
        line["breakdown"] = result["breakdown"]
    line["workload"] = cell["name"]
    line["checks"] = result["checks"]
    return line


def line_faults(line: dict, cell: dict, trace: bool) -> list:
    """Why ``line`` is not the line the contract asks for; [] if it is."""
    faults = [f"missing key {k}" for k in LINE_KEYS if k not in line]
    if faults:
        return faults
    if not isinstance(line["correct"], bool):
        faults.append("correct is not a boolean")
    for k in ("attempted", "failed"):
        if not isinstance(line[k], int) or line[k] < 0:
            faults.append(f"{k} is not a count")
    wanted = cell["per_layer"] if trace else cell["end_to_end"]
    units = {m["name"]: m["unit"] for m in wanted}
    for name, m in line["metrics"].items():
        if not isinstance(m, dict):
            faults.append(f"metric {name} is not a value with its unit")
            continue
        if name not in units:
            faults.append(f"metric {name} is not one of this cell's")
        elif m.get("unit") != units[name]:
            faults.append(f"metric {name} has unit {m.get('unit')!r}")
        v = m.get("value")
        if not isinstance(v, (int, float)) or not math.isfinite(v):
            faults.append(f"metric {name} has no finite value")
    if not trace:
        faults += [f"metric {n} is missing" for n in units
                   if n not in line["metrics"]]
    elif not line["metrics"]:
        faults.append("a traced line with no per-layer metric")
    dev = line["device"]
    for k, t in (("platform", str), ("kind", str), ("count", int),
                 ("memory_peak_bytes", int)):
        if not isinstance(dev.get(k), t):
            faults.append(f"device.{k} is missing or not {t.__name__}")
    if trace:
        b, w = dev.get("busy_s"), dev.get("window_s")
        if not (isinstance(b, float) and isinstance(w, float)
                and 0 < b <= w):
            faults.append(f"device.busy_s {b!r} / window_s {w!r}: busy has "
                          f"to be above 0 and at most the window")
        for k, rows in (line.get("breakdown") or {}).items():
            if k not in ("device_ops", "idle_gaps") or len(rows) > 10:
                faults.append(f"breakdown.{k}: unknown or over 10 entries")
    if list(line)[-1] != "checks":
        faults.append("checks is not the last key")
    return faults


def print_checks(checks: dict) -> None:
    """Each number compared beside its limit, as the last lines of
    standard error."""
    for name, c in checks.items():
        print(f"perfbench check {name}: value {c['value']!r} "
              f"limit {c['limit']!r} {'ok' if c['ok'] else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()


def check(value: float, limit: float) -> dict:
    ok = bool(math.isfinite(value) and value <= limit)
    return {"value": float(value), "limit": float(limit), "ok": ok}
