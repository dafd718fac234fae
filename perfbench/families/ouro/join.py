"""What an Ouro cell's step makes twice: the device time of the instructions
that the checkpoints of a looped stack (every layer application, every
pass's head and loss) make again in the backward pass.

JAX names such an operation by its place under the checkpoint's transpose:
``transpose(jvp(...))/.../checkpoint/rematted_computation/seg.mlp/
dot_general`` (the recomputed work keeps its segment, and the join of
``perfbench/segments.py`` counts it as backward). This module reads the same
compiled step as that join (``segments.compiled_text``: the second build
loads the first's entry from the persistent cache) and gives an instruction
to the recomputation where its matmuls or kernels all lie under
``rematted_computation``, or, with none, where every operation in it does:
a fusion that joins recomputed elementwise work with the backward pass's
own is left out, so the number is the least the recomputation costs.
"""

from __future__ import annotations

import sys
import time
from typing import Optional

from perfbench import segments, trace_reduce

REMAT = "/rematted_computation/"


def recomputed(text: str) -> set:
    """The names (cut as the trace cuts them) of the instructions of a
    compiled step that hold the checkpoints' recomputation."""
    comps = segments.parse(text)
    called = {c for instrs in comps.values() for i in instrs
              if i.opcode != "call" for c in i.calls}
    out = set()
    for instrs in (v for c, v in comps.items() if c not in called):
        for instr in instrs:
            ops = segments._operations(comps, instr)
            deciding = [p for p, decides in ops if decides] \
                or [p for p, _ in ops]
            if deciding and all(REMAT in p for p in deciding):
                out.add(instr.name[:segments.NAME_LIMIT])
    return out


def recompute_ms(ctx: dict) -> Optional[float]:
    """Milliseconds a traced step spent making checkpointed work again;
    None where there is no trace to read."""
    if "recompute_ms" in ctx:
        return ctx["recompute_ms"]
    ctx["recompute_ms"] = None
    planes, steps = ctx.get("planes"), ctx.get("traced_steps")
    per_device = trace_reduce.device_ops(planes) if planes else {}
    if not per_device or not steps:
        return None
    t0 = time.perf_counter()
    again = recomputed(segments.compiled_text(ctx["cell"]))
    ns = sum(t for events in per_device.values()
             for name, t in trace_reduce.self_times(events).items()
             if name in again)
    print(f"perfbench recompute: {len(again)} instructions of the compiled "
          f"step make checkpointed work again (found in "
          f"{time.perf_counter() - t0:.2f}s)", file=sys.stderr, flush=True)
    ctx["recompute_ms"] = 1e3 * ns / 1e9 / steps / len(per_device)
    return ctx["recompute_ms"]
