"""From a profiler trace to busy time, kernel time, top operations, gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
tuples; everything after that works on those tuples, so the arithmetic is
checked in the tests on a small recorded trace kept as JSON beside them.

An event is ``(name, start_ns, duration_ns)``. A device's operations are
the events of its ``XLA Ops`` line; busy time is the length of the union
of their intervals inside the window, averaged over the devices used.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Tuple

Event = Tuple[str, int, int]
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "/host:"
TOP = 10


def load(trace_dir: str) -> Dict[str, Dict[str, List[Event]]]:
    """plane name -> line name -> events, from the newest capture under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events)
    return out


def device_ops(planes: dict) -> Dict[str, List[Event]]:
    """Each device's operations: device plane name -> events."""
    return {name: lines[OPS_LINE] for name, lines in sorted(planes.items())
            if name.startswith(DEVICE_PREFIX) and lines.get(OPS_LINE)}


def union(events: Iterable[Event]) -> List[Tuple[int, int]]:
    """The events' intervals merged, sorted."""
    merged: List[List[int]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return [(a, b) for a, b in merged]


def busy_seconds(events: Iterable[Event]) -> float:
    return sum(b - a for a, b in union(events)) / 1e9


def short(name: str) -> str:
    """An operation's own name out of the HLO text the trace gives it."""
    return name.split(" = ", 1)[0].lstrip("%")[:64]


def self_times(events: Iterable[Event]) -> Dict[str, int]:
    """name -> ns not covered by operations nested inside (a ``while``
    holds its body's operations on the same line)."""
    total: Dict[str, int] = {}
    stack: List[Tuple[str, int]] = []           # (name, end)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            total[stack[-1][0]] -= dur
        name = short(name)
        total[name] = total.get(name, 0) + dur
        stack.append((name, start + dur))
    return total


def top_ops(per_device: Dict[str, List[Event]], n: int = TOP) -> list:
    """[name, seconds] of the operations with most device time of their
    own, averaged over the devices."""
    total: Dict[str, int] = {}
    for events in per_device.values():
        for name, ns in self_times(events).items():
            total[name] = total.get(name, 0) + ns
    k = max(1, len(per_device))
    return [[name, ns / 1e9 / k] for name, ns in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def kernel_seconds(per_device: Dict[str, List[Event]], needles) -> float:
    """Device time of the operations whose text holds one of ``needles``,
    averaged over the devices."""
    k = max(1, len(per_device))
    return sum(dur for events in per_device.values()
               for name, _, dur in events
               if any(n in name for n in needles)) / 1e9 / k


def _host_events(planes: dict) -> List[Event]:
    return [ev for name, lines in planes.items()
            if name.startswith(HOST_PREFIX)
            for events in lines.values() for ev in events]


def idle_gaps(planes: dict, n: int = TOP) -> list:
    """[what the host was doing, seconds] for the longest gaps between
    operations on the first device: the host event that covers most of
    the gap, by the trace's own names, or "no host event"."""
    per_device = device_ops(planes)
    if not per_device:
        return []
    spans = union(next(iter(per_device.values())))
    gaps = sorted(((b0, a1) for (_, b0), (a1, _) in zip(spans, spans[1:])),
                  key=lambda g: g[0] - g[1])[:n]
    host = sorted(_host_events(planes), key=lambda e: e[1])
    out = []
    for lo, hi in gaps:
        best, best_cover, best_dur = "no host event", 0, 0
        for name, start, dur in host:
            if start >= hi:
                break
            cover = min(hi, start + dur) - max(lo, start)
            # The innermost event that covers the gap says most: prefer
            # full cover by the shortest event.
            if cover > best_cover or (cover == best_cover and cover > 0
                                      and dur < best_dur):
                best, best_cover, best_dur = short(name), cover, dur
        out.append([best, (hi - lo) / 1e9])
    return out


def reduce(planes: dict, window_s: float) -> dict:
    """What the result line's ``device`` and ``breakdown`` take from a
    trace. ``window_s`` is the traced window by the host's clock, from a
    device at rest to ``block_until_ready``; busy time is reported as the
    trace gives it, and a line whose busy time passes its window is
    refused (``harness.line_faults``), never cut to fit."""
    per_device = device_ops(planes)
    if not per_device:
        raise ValueError(
            f"no {OPS_LINE!r} line on a {DEVICE_PREFIX}* plane; planes: "
            f"{sorted(planes)}")
    busy = sum(busy_seconds(ev) for ev in per_device.values()) \
        / len(per_device)
    return {"busy_s": busy, "window_s": window_s,
            "devices_traced": len(per_device),
            "device_ops": top_ops(per_device),
            "idle_gaps": idle_gaps(planes)}
