"""Device time of a traced train step by the program's own names.

The program names its work (``ray_tpu/util/profiling.py``: ``SEGMENTS``,
the ``jax.named_scope``s of ``models/transformer.py``, and ``KERNELS``,
the names of the Pallas kernels in ``ops/flash_attention.py``). XLA keeps
a scope as ``metadata={op_name="jit(step)/jvp(seg.mlp)/dot_general"}`` on
every instruction of the compiled step, and the profiler's ``XLA Ops``
line gives each instruction's device time under the instruction's name
(``%fusion.200``). The driver keeps an event's name, start and duration
and nothing else of the trace, so the join goes through the compiled
step: build the step the window ran, compile it, parse ``as_text()`` into
instruction -> operations, and give each instruction to one segment:

- the outermost segment on an operation's path decides (``seg.mlp/norm``
  is ``seg.mlp``); a path with ``transpose(`` in it is the backward pass;
- an operation with no segment that lies outside the differentiated
  function (no ``jvp(`` on its path) is the ``update``'s: the optimizer's
  pass, ``apply_updates``, the batch draw. Inside it, it is the layer
  loop's plumbing (the slices of the stacked weights, the residuals kept
  for the backward pass), which goes with whatever it is fused with and to
  ``update`` when alone;
- a fusion holds many operations. One class among them: that one. More
  than one: the class of its matmuls or kernels if they agree, else
  ``unattributed`` (so the ``lm_head`` gradient matmul that XLA fuses with
  that leaf's AdamW update is ``seg.head_loss``, and the embedding table's
  update fused with the cast of its gradient is nobody's);
- an instruction the compiler made and left without a name (the layer
  weights' casts hoisted out of the loop, the zeros gradients are summed
  into, copies between memories) takes the segment of what consumes its
  value (``_inherit``);
- an instruction the text does not hold is ``unattributed``. So is
  everything when the text names no segment or one that is not the
  vocabulary's, so a text from another commit reads as a high
  ``train.seg.unattributed_share`` and never as a wrong segment. JAX
  leaves metadata out of its persistent cache's key, so the window's own
  cache entry may be such a text: ``compiled_text`` compiles with
  metadata in the key.

A program without the vocabulary (the parent of the PR that brought it)
gives ``None`` everywhere, and the readers leave their metrics out.
"""

from __future__ import annotations

import functools
import os
import re
import sys
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

from perfbench import trace_reduce

UPDATE = "update"
UNATTRIBUTED = "unattributed"
NAME_LIMIT = 64          # trace_reduce.short cuts an instruction's name here
MATMUL_OPCODES = ("convolution", "dot")
# No work of their own, so no consumer to look for: the value's producer
# or consumer has the event that takes the time.
NO_WORK = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
            "while")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'

_HEADER = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$")
_INSTR = re.compile(r"^\s+(ROOT )?%([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"^([\w\-]+)\(([^)]*)\)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply)=%([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_INDEX = re.compile(r", index=(\d+)")
_BODY = re.compile(r", body=%([\w.\-]+)")


class Instr(NamedTuple):
    name: str
    opcode: str
    paths: tuple          # the op_name, split where XLA joined several
    calls: tuple          # computations inside it: a fusion's, a reduce's
    kernel: bool          # a Pallas kernel's custom call
    operands: tuple
    index: Optional[int]  # of a get-tuple-element
    body: Optional[str]   # of a while
    root: bool


def vocabulary() -> Optional[Tuple[tuple, tuple]]:
    """(segments, kernels) as the program names them, or None where the
    program has no such names."""
    try:
        from ray_tpu.util import profiling
        return tuple(profiling.SEGMENTS), tuple(profiling.KERNELS)
    except (ImportError, AttributeError):
        return None


def parse(text: str) -> Dict[str, List[Instr]]:
    """computation name -> its instructions, out of ``compiled.as_text()``."""
    comps: Dict[str, List[Instr]] = {}
    current = None
    for line in text.splitlines():
        head = _HEADER.match(line)
        if head:
            current = comps.setdefault(head.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTR.match(line) if current is not None else None
        if not m:
            continue
        rest = m.group(3)
        op = _OPCODE.match(_past_type(rest))
        named, index, body = (r.search(rest) for r in
                              (_OP_NAME, _INDEX, _BODY))
        current.append(Instr(
            name=m.group(2), opcode=op.group(1) if op else "",
            # XLA joins the names of operations it merged with ";".
            # A bare primitive ("scatter-add") is no path: it says nothing.
            paths=tuple(p for p in named.group(1).split(";") if "/" in p)
            if named else (),
            calls=tuple(_CALLS.findall(rest)), kernel=KERNEL_TARGET in rest,
            operands=tuple(_OPERAND.findall(op.group(2))) if op else (),
            index=int(index.group(1)) if index else None,
            body=body.group(1) if body else None, root=bool(m.group(1))))
    return comps


def _past_type(rest: str) -> str:
    """An instruction's text behind its result type: one token, or for a
    tuple one balanced pair of brackets."""
    if not rest.startswith("("):
        return rest.split(" ", 1)[-1]
    depth = 0
    for at, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rest[at + 2:]
    return ""


def _operations(comps: dict, instr: Instr, seen: frozenset = frozenset()
                ) -> List[Tuple[str, bool]]:
    """(op_name path, decides) of an instruction and of everything inside
    the computations it calls; ``decides`` marks a matmul or a kernel. A
    ``while``'s body is not inside it: its instructions have events of
    their own on the trace's line."""
    decides = instr.kernel or instr.opcode in MATMUL_OPCODES
    out = [(p, decides) for p in instr.paths]
    for comp in instr.calls:
        if comp in comps and comp not in seen:
            for inner in comps[comp]:
                out += _operations(comps, inner, seen | {comp})
    return out


def classify(operations: List[Tuple[str, bool]], segments: tuple
             ) -> Tuple[str, str]:
    """(segment, "forward" | "backward" | "both" | "") of one instruction
    from the operations it holds. An operation is its outermost segment's;
    with no segment it is the update's if it lies outside the differentiated
    function (no ``jvp(`` on its path: the optimizer, the batch draw), and
    else the layer loop's plumbing, which follows whatever it is fused
    with and is the update's only when alone."""
    found = _placed(operations, segments)
    if not found:
        return (UPDATE, "") if operations else (UNATTRIBUTED, "")
    if len({c for c, _, _ in found}) > 1:
        found = [f for f in found if f[2]]
        if len({c for c, _, _ in found}) != 1:
            return UNATTRIBUTED, ""
    return found[0][0], _way({w for _, w, _ in found})


@functools.lru_cache(maxsize=None)
def _finder(names: tuple):
    """A name of ``names`` as one whole component of an op_name path."""
    return re.compile(
        r"(?<![\w.])(" + "|".join(map(re.escape, names)) + r")(?![\w.])")


def _placed(operations: List[Tuple[str, bool]], segments: tuple) -> list:
    """(class, way, decides) of the operations that say where they belong:
    all but the layer loop's plumbing."""
    found = []
    for path, decides in operations:
        hit = _finder(segments).search(path)    # leftmost: the outermost
        if hit:
            found.append((hit.group(1), "backward" if "transpose(" in path
                          else "forward", decides))
        elif "jvp(" not in path:
            found.append((UPDATE, "", decides))
    return found


def _way(ways: set) -> str:
    ways = {w for w in ways if w}
    if "both" in ways or len(ways) > 1:
        return "both"
    return ways.pop() if ways else ""


def _inherit(own: Dict[str, List[Instr]], table: dict) -> None:
    """Give each instruction of ``table`` that does not say where it belongs
    the segment of what consumes its value. Such are the instructions the
    compiler made and left without a name (the layer weights' casts hoisted
    out of the layer loop as one ``convert`` of the whole stack, the zeros
    the gradients are summed into, copies between memories) and the layer
    loop's plumbing standing alone (a slice of the stacked weights, a
    residual kept for the backward pass). A value is followed through other
    such instructions, through tuples and get-tuple-elements by their
    index, and into and out of a ``while`` (its body's parameter, its
    body's root, its own result). One model segment among the consumers:
    that one, whatever the update also reads of it (as inside a fusion);
    several: it stays what it was, ``unattributed`` without a name and the
    ``update``'s as plumbing."""
    instrs = [i for comp in own.values() for i in comp]
    uses: Dict[str, list] = {}
    for instr in instrs:
        for pos, operand in enumerate(instr.operands):
            uses.setdefault(operand, []).append((instr, pos))
    loops = [i for i in instrs if i.opcode == "while" and i.body in own]
    param_of = {loop.body: next(i.name for i in own[loop.body]
                                if i.opcode == "parameter") for loop in loops}
    loops_of_root: Dict[str, list] = {}
    for loop in loops:
        root = next(i.name for i in own[loop.body] if i.root)
        loops_of_root.setdefault(root, []).append(loop)

    def consumers(name, index, seen, out):
        if (name, index) in seen:
            return
        seen.add((name, index))
        if index is not None:
            for loop in loops_of_root.get(name, ()):    # round it, and out
                consumers(param_of[loop.body], index, seen, out)
                consumers(loop.name, index, seen, out)
        for user, pos in uses.get(name, ()):
            if user.opcode == "get-tuple-element":
                if user.index == index:
                    consumers(user.name, None, seen, out)
            elif index is not None:     # a tuple goes whole into a while only
                if user in loops:
                    consumers(param_of[user.body], index, seen, out)
                    consumers(user.name, index, seen, out)
            elif user.opcode == "tuple":
                consumers(user.name, pos, seen, out)
            elif table[user.name[:NAME_LIMIT]]["named"]:
                out.append(table[user.name[:NAME_LIMIT]])
            else:
                consumers(user.name, None, seen, out)

    for instr in instrs:
        row = table[instr.name[:NAME_LIMIT]]
        if row["named"] or instr.opcode in NO_WORK:
            continue
        found = []
        consumers(instr.name, None, set(), found)
        found = [r for r in found if r["segment"] != UNATTRIBUTED]
        if any(r["segment"] != UPDATE for r in found):      # as in a fusion
            found = [r for r in found if r["segment"] != UPDATE]
        if len({r["segment"] for r in found}) == 1:
            row["segment"] = found[0]["segment"]
            row["way"] = _way({r["way"] for r in found})


def attribute(text: str, segments: tuple, kernels: tuple) -> dict:
    """instruction name (cut as the trace cuts it) -> {"segment", "way",
    "kernel"} for every instruction of ``text`` that can have an event of
    its own: those outside fused computations. ``kernel`` is the
    vocabulary's name of a Pallas kernel. Empty when the text names no
    segment, or one that is not the vocabulary's."""
    comps = parse(text)
    prefix = os.path.commonprefix(segments)
    scopes = set(re.findall(r"(?<![\w.])" + re.escape(prefix) + r"\w+", text))
    if not scopes or not prefix or scopes - set(segments):
        return {}                   # no names, or another vocabulary's
    called = {c for instrs in comps.values() for i in instrs
              if i.opcode != "call" for c in i.calls}
    own = {c: instrs for c, instrs in comps.items() if c not in called}
    table = {}
    for instr in (i for instrs in own.values() for i in instrs):
        ops = _operations(comps, instr)
        segment, way = classify(ops, segments)
        kernel = None
        if instr.kernel:
            hits = [_finder(kernels).search(p) for p, _ in ops]
            kernel = next((h.group(1) for h in hits if h), None)
        table[instr.name[:NAME_LIMIT]] = {
            "segment": segment, "way": way, "kernel": kernel,
            "named": bool(_placed(ops, segments))}
    _inherit(own, table)
    return table


def compiled_text(cell: dict) -> str:
    """The optimized HLO of the step the cell's window ran, from shapes
    alone: nothing is allocated on the device."""
    import jax

    from perfbench import weights
    from perfbench.drivers import train

    config = cell["config"]
    model = train.run_model(config)
    step, opt = train.build_step(config)
    params = jax.eval_shape(lambda: weights.make_params(model, 0))
    opt_state = jax.eval_shape(opt.init, params)
    # The key and the index as the driver passes them: a typed key array
    # and a Python int, so the lowering is the one the window compiled.
    lowered = step.lower(params, opt_state, weights.seed_key(0), 0)
    # JAX leaves metadata out of the persistent cache's key, so the entry
    # the window wrote (or found) may carry another commit's names. With
    # metadata in the key only this commit's own text can answer: the
    # first traced run in a cache directory compiles, the next ones load.
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update(flag, before)


def join(ctx: dict) -> Optional[dict]:
    """The traced steps' device time by segment, way and kernel, per step
    and in seconds; None where there is nothing to read. Kept in ``ctx``:
    ten readers, one compile."""
    if "segments" in ctx:
        return ctx["segments"]
    ctx["segments"] = None
    planes, steps = ctx.get("planes"), ctx.get("traced_steps")
    names = vocabulary()
    if not planes or not steps or names is None:
        return None
    per_device = trace_reduce.device_ops(planes)
    if not per_device:
        return None
    t0 = time.perf_counter()
    table = attribute(compiled_text(ctx["cell"]), *names)
    ctx["segments"] = reduce(per_device, table, steps)
    print(f"perfbench segments: joined {len(table)} instructions of the "
          f"compiled step in {time.perf_counter() - t0:.2f}s",
          file=sys.stderr, flush=True)
    return ctx["segments"]


def reduce(per_device: dict, table: dict, steps: int) -> dict:
    """Self time of the devices' events by what ``table`` says of their
    instruction, seconds per step, averaged over the devices."""
    per = 1e9 * steps * max(1, len(per_device))
    out = {"segment": {}, "way": {}, "kernel": {},
           "busy_s": sum(trace_reduce.busy_seconds(ev)
                         for ev in per_device.values()) * 1e9 / per}
    for events in per_device.values():
        for name, ns in trace_reduce.self_times(events).items():
            row = table.get(name) or {"segment": UNATTRIBUTED, "way": "",
                                      "kernel": None}
            seg = row["segment"]
            keys = [("segment", seg), ("way", (seg, row["way"]))]
            if row["kernel"]:
                keys.append(("kernel", row["kernel"]))
            for kind, key in keys:
                out[kind][key] = out[kind].get(key, 0.0) + ns / per
    return out


def segment_ms(ctx: dict, segment: str) -> Optional[float]:
    """Milliseconds a traced step spent under ``segment``."""
    joined = join(ctx)
    return None if joined is None else \
        1e3 * joined["segment"].get(segment, 0.0)


def kernel_ms(ctx: dict, kernel: str) -> Optional[float]:
    """Milliseconds a traced step spent in the Pallas kernel ``kernel``;
    None where the step holds no kernel of that name."""
    joined = join(ctx)
    if joined is None or kernel not in joined["kernel"]:
        return None
    return 1e3 * joined["kernel"][kernel]


def unattributed_share(ctx: dict) -> Optional[float]:
    """Percent of the device's busy time that no segment got."""
    joined = join(ctx)
    if joined is None or joined["busy_s"] <= 0:
        return None
    return 100.0 * joined["segment"].get(UNATTRIBUTED, 0.0) / joined["busy_s"]
