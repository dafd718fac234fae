"""A Ling cell's device time by segment: the join of
``perfbench/segments.py`` with one rule more.

That join gives a fusion whose operations carry several classes to its
matmul's or kernel's, and to nobody where it holds neither. In this
family's step that left a quarter of the busy time with nobody (27.09 %
in PR 39's first traced run): the AdamW passes of the large stacked
leaves, each fused with the ``pad`` and ``add_any`` that finish its
gradient and with one stray scalar of another segment, some 25
operations of the optimizer's beside one or two of a layer's; the
embedding's scatter-add fused with the residual stream's last
``add_any``; and hundreds of small elementwise fusions that hold one
constant another segment also reads. So here:

- a fusion of several classes that no matmul or kernel decides is the
  class of ``SHARE`` (two thirds) or more of the operations in it that say
  where they belong, if there is one; a fusion more evenly split than
  that stays ``unattributed``, and so does one whose matmuls or kernels
  disagree;
- everything else is ``segments.attribute``'s, the inheritance of
  nameless instructions from their consumers included, which runs after
  the rule and so sees its result.

The eleven ``train.seg.*.ling3`` readers, ``train.seg.unattributed_share
.ling3`` and ``kernel.kda_roofline.ling3`` read this join, so they still
sum to the busy time; a traced line's ``breakdown.device_ops`` carries
its segments too. The kernels' times are the accepted join's (a kernel's
fusion is never of two minds). The other cells' readers keep the accepted
rule: PERF.md section 7, Sixth (a), says what a ``benchmark`` PR would do
with this one.
"""

from __future__ import annotations

import collections
import sys
import time
from typing import Optional

from perfbench import segments, trace_reduce

SHARE = 2.0 / 3.0
KEY = "segments.ling3"


def classify(operations: list, names: tuple) -> tuple:
    """``segments.classify`` and, where that finds several classes and no
    matmul or kernel among them, the class most of the operations carry."""
    segment, way = segments.classify(operations, names)
    if segment != segments.UNATTRIBUTED:
        return segment, way
    found = segments._placed(operations, names)
    if not found or any(decides for _, _, decides in found):
        return segment, way
    top, count = collections.Counter(
        c for c, _, _ in found).most_common(1)[0]
    if count < SHARE * len(found):
        return segment, way
    return top, segments._way({w for c, w, _ in found if c == top})


def attribute(text: str, names: tuple, kernels: tuple) -> dict:
    """``segments.attribute`` of ``text`` under this family's rule: the
    instructions that join left with nobody although they name their
    classes are classified again, the nameless ones are put back to what
    they were before they inherited, and they inherit once more."""
    table = segments.attribute(text, names, kernels)
    if not table:
        return table
    comps = segments.parse(text)
    called = {c for instrs in comps.values() for i in instrs
              if i.opcode != "call" for c in i.calls}
    own = {c: instrs for c, instrs in comps.items() if c not in called}
    for instr in (i for instrs in own.values() for i in instrs):
        row = table[instr.name[:segments.NAME_LIMIT]]
        ops = segments._operations(comps, instr)
        if not row["named"]:
            row["segment"], row["way"] = segments.classify(ops, names)
        elif row["segment"] == segments.UNATTRIBUTED:
            row["segment"], row["way"] = classify(ops, names)
    segments._inherit(own, table)
    return table


def join(ctx: dict) -> Optional[dict]:
    """As ``segments.join``: the traced steps' device time by segment, way
    and kernel; None where that join finds nothing to read. Kept in
    ``ctx``, and put in the accepted join's place there, so that the
    line's ``breakdown`` names an instruction as the metrics count it. The
    accepted join keeps its table and not the text it read, so the step is
    lowered and its text loaded a second time (16 s of a traced run)."""
    if KEY in ctx:
        return ctx[KEY]
    ctx[KEY] = segments.join(ctx)
    if ctx[KEY] is None or not ctx.get("segment_table"):
        return ctx[KEY]     # nothing to read, or a text that names nothing
    t0 = time.perf_counter()
    table = attribute(segments.compiled_text(ctx["cell"]),
                      *segments.vocabulary())
    per_device = trace_reduce.device_ops(ctx["planes"])
    ctx[KEY] = segments.reduce(per_device, table, ctx["traced_steps"])
    moved = sum(table[name]["segment"] != row["segment"]
                for name, row in ctx["segment_table"].items())
    ctx["segments"], ctx["segment_table"] = ctx[KEY], table
    print(f"perfbench ling3 join: {moved} of {len(table)} instructions "
          f"placed by the majority of their operations or after it, in "
          f"{time.perf_counter() - t0:.2f}s", file=sys.stderr, flush=True)
    _log_unplaced(per_device, table, ctx["traced_steps"])
    return ctx[KEY]


def _log_unplaced(per_device: dict, table: dict, steps: int,
                  most: int = 12) -> None:
    """The largest instructions no segment got, ms a step, for the log."""
    left = collections.Counter()
    for events in per_device.values():
        for name, ns in trace_reduce.self_times(events).items():
            row = table.get(name)
            if row is None or row["segment"] == segments.UNATTRIBUTED:
                left[name] += ns / (1e6 * steps * len(per_device))
    print("perfbench ling3 join: unattributed still, ms a step: "
          + ", ".join(f"{n} {ms:.3f}" for n, ms in left.most_common(most)),
          file=sys.stderr, flush=True)


def segment_ms(ctx: dict, segment: str) -> Optional[float]:
    """Milliseconds a traced step spent under ``segment``."""
    joined = join(ctx)
    return None if joined is None else \
        1e3 * joined["segment"].get(segment, 0.0)


def unattributed_share(ctx: dict) -> Optional[float]:
    """Percent of the device's busy time that no segment got."""
    joined = join(ctx)
    if joined is None or joined["busy_s"] <= 0:
        return None
    return 100.0 * joined["segment"].get(segments.UNATTRIBUTED, 0.0) \
        / joined["busy_s"]
