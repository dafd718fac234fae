"""Which records of the program's build log are set-up's, and which of
them is the step's: the two rules the ``setup.*`` readers share.

The program keeps one record for each program it builds
(``ray_tpu/util/profiling.py:build_log``; a record's fields are listed
there). The readers run after the window, and after the joins of
``perfbench/segments.py`` and ``families/*/join.py`` have lowered and
compiled the step again, and ``ctx`` carries no time. So:

**The window is the log's last silence** (the end of one record to the
start of the next, records that overlap merged) of at least ``0.9 *
ctx["seconds"]``: nothing is built inside the window (the driver logs
"compilations inside the window: 0"), and after it the joins build at
once. Set-up's records are those that end before it. A silence that long
earlier in set-up (a balancing pass that builds nothing) is harmless,
being earlier. Where no silence is that long (a build inside the window
splits it, and the driver's own line reports that) there is no set-up to
read and every reader returns None: a wrong set-up number must not stand
beside that line.

**The step's record** is the one with the largest ``trace_s + lower_s``
among set-up's: what the program pays for a build from a warm cache and
from an empty one alike, so the same record is picked in both, and the
step's in every cell by five times or more (my chip runs, PR 43). Not the
largest ``backend_s``: from an empty cache ``change_norms``' programs
compile for 8 to 11 s each in the dense cell where the step takes 8.5, and
a balancing pass's program for 37 s where the step takes 30. The log
starts when the program's ``ops/backend.py`` is first imported, which one
family does in ``build_step``, after its weights are made; the step's
record and everything after it are inside the log in every family, which
is why the readers are defined from the step's record on and none sums
"all of set-up".
"""

from __future__ import annotations

import sys
from typing import List, Optional, Tuple

from perfbench import trace_reduce

WINDOW_SHARE = 0.9


def say(*words) -> None:
    print("perfbench set-up:", *words, file=sys.stderr, flush=True)


def traced_lowered(record: dict) -> float:
    return record["trace_s"] + record["lower_s"]


def union(records: List[dict]) -> List[Tuple[float, float]]:
    """The records' ``[t0, t1]`` merged, sorted."""
    return trace_reduce.union(
        (r["name"], r["t0"], r["t1"] - r["t0"]) for r in records)


def union_s(records: List[dict]) -> float:
    return sum(b - a for a, b in union(records))


def silences(records: List[dict]) -> List[Tuple[float, float]]:
    """(start, end) of each stretch between the records' merged spans."""
    spans = union(records)
    return [(b0, a1) for (_, b0), (a1, _) in zip(spans, spans[1:])]


def window(records: List[dict], seconds: float
           ) -> Optional[Tuple[float, float]]:
    """The log's last silence of at least ``WINDOW_SHARE * seconds``, None
    where there is none."""
    long = [(a, b) for a, b in silences(records)
            if b - a >= WINDOW_SHARE * seconds]
    return long[-1] if long else None


def set_up(ctx: dict) -> Optional[dict]:
    """``{"records": set-up's, "step": the step's record, "after": those
    that close after it}``, or None (and why, on standard error) where the
    program keeps no build log or the log shows no window."""
    from ray_tpu.util import profiling

    if not hasattr(profiling, "build_log"):
        return None                       # a program older than its log
    log = profiling.build_log()
    found = window(log, ctx["seconds"])
    if found is None:
        longest = max((b - a for a, b in silences(log)), default=0.0)
        say(f"no silence of {WINDOW_SHARE} x {ctx['seconds']:.3f}s among "
            f"{len(log)} records (the longest {longest:.3f}s): something "
            f"was built inside the window, no set-up metric is read")
        return None
    records = [r for r in log if r["t1"] <= found[0]]
    if not records:
        say("the log starts after the window: no set-up metric is read")
        return None
    ranked = sorted(records, key=traced_lowered, reverse=True)
    step = ranked[0]
    say(f"the window is the silence of {found[1] - found[0]:.3f}s "
        f"(the readers' seconds {ctx['seconds']:.3f}) after record "
        f"{records[-1]['seq']} of {len(log)}; the step's record is "
        f"{step['seq']} {step['name']}, {traced_lowered(step):.3f}s traced "
        f"and lowered"
        + (f", the runner-up {ranked[1]['seq']} {ranked[1]['name']} "
           f"{traced_lowered(ranked[1]):.3f}s" if len(ranked) > 1 else ""))
    return {"records": records, "step": step,
            "after": [r for r in records if r["seq"] > step["seq"]]}
