"""The TPU-resident DAG executor: lower a static task DAG to one JAX program.

This is the BASELINE.json north star. Where the reference routes every task
through owner→raylet lease loops and per-actor execution loops over plasma
mutable objects (reference: python/ray/dag/compiled_dag_node.py +
src/ray/raylet scheduling stack [unverified]), this executor compiles the
whole DAG into a single XLA program:

- **Object table**: all intermediate values live in one HBM-resident array
  ``obj[num_slots, *payload_shape]`` — the plasma analogue is a buffer pool
  indexed by object slot, never leaving the device.
- **Task table**: per-task op index, padded argument slots, and output slot
  as int32 arrays — the TaskSpec analogue.
- **Static wave schedule** (default): dependency levels are resolved at
  compile time into a ``[num_waves, wave_width]`` schedule; execution is a
  ``lax.fori_loop`` over waves whose body gathers args
  (``obj[arg_slots]``), runs every task in the wave via a vmapped
  ``lax.switch`` over the op table, and scatters outputs — argument
  gather/scatter as batched sparse ops, exactly the north-star phrasing.
- **Dynamic frontier mode** (``dynamic=True``): a ``lax.while_loop`` keeps
  an in-degree vector on device; each iteration executes the ready frontier
  (``indeg == 0 & ~done``) masked across all tasks and decrements consumer
  in-degrees with a segment-sum over the edge list — ObjectRef dependency
  resolution as sparse ops, no host round-trips per wave.

Multi-chip (``mesh=``): the task schedule is partitioned over a Mesh axis
with ``shard_map``; the object table is PARTIALLY replicated — every shard
holds the full-slot buffer in HBM but only its own lanes' outputs and its
imports are ever written/read there (unconsumed remote slots stay stale
zeros). Lane assignment is locality-aware (a task lands on the shard that
produced most of its inputs, balanced to W/n lanes per shard per wave),
and the per-wave exchange ships ONLY cross-shard-consumed outputs — packed
to the compile-time max export count and moved with one tiled
``lax.all_gather`` over ICI. Chain-heavy graphs therefore export nothing
and compile with zero collectives; a fully-connected fan-in degenerates to
a whole-wave gather. The HBM cost of replicating the table
(``num_slots × payload``) is the deliberate trade for static single-pass
scatters; the ICI cost is proportional to actual cross-shard edges, not
wave width. The dynamic frontier mode ships each
shard's top-F chosen outputs + ids per iteration (the in-degree vector
and done mask stay replicated) — unless the graph partitions cleanly
across shards, in which case only the tiny id vectors ride ICI and the
leaves replicate once after the loop with a masked psum.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu._private.config import GlobalConfig
from ray_tpu.dag.dag_node import (
    ClassMethodNode,
    DAGNode,
    FunctionNode,
    InputAttributeNode,
    InputNode,
    MultiOutputNode,
)


class JaxDAGRef:
    """CompiledDAGRef analogue: handle to a completed on-device execution."""

    def __init__(self, arrays, multi: bool):
        self._arrays = arrays
        self._multi = multi

    def get(self):
        if self._multi:
            return [np.asarray(a) for a in self._arrays]
        return np.asarray(self._arrays)

    def device_value(self):
        """The raw jax array(s), still on device (no host transfer)."""
        return self._arrays


class CompiledJaxDAG:
    def __init__(self, fn, num_inputs: int, multi_output: bool,
                 num_tasks: int, num_waves: int, wave_width: int,
                 payload_shape, dtype, dynamic: bool, op_names: List[str],
                 num_shards: int = 1):
        self.num_inputs = num_inputs
        self.multi_output = multi_output
        self.num_tasks = num_tasks
        self.num_waves = num_waves
        self.wave_width = wave_width
        self.payload_shape = tuple(payload_shape)
        self.dtype = dtype
        self.dynamic = dynamic
        self.op_names = op_names
        self.num_shards = num_shards
        # Input staging lives INSIDE the jit: eager jnp.asarray on a host
        # scalar is a blocking device_put of its own, while the same
        # scalar passed as a jit argument rides the dispatch path.
        payload_shape_t = self.payload_shape
        dtype_t = self.dtype

        if num_inputs:
            @jax.jit
            def staged(*raw):
                stacked = jnp.stack(
                    [jnp.asarray(x, dtype=dtype_t).reshape(payload_shape_t)
                     for x in raw])
                return fn(stacked)
        else:
            @jax.jit
            def staged():
                return fn(jnp.zeros((0,) + payload_shape_t, dtype_t))

        self._staged = staged

    def execute(self, *inputs) -> JaxDAGRef:
        if len(inputs) != self.num_inputs:
            raise ValueError(
                f"compiled DAG takes {self.num_inputs} input(s), got "
                f"{len(inputs)}")
        # Non-device inputs normalize to host numpy in the payload dtype —
        # free on host — so every call shares ONE jit signature (a Python
        # int one call and a float the next must not retrace the whole DAG
        # program). Device arrays pass through zero-copy; any dtype cast
        # happens inside the trace.
        prepped = [
            x if isinstance(x, jax.Array)
            else np.asarray(x, dtype=self.dtype) for x in inputs
        ]
        out = self._staged(*prepped)
        return JaxDAGRef(out, self.multi_output)

    def __call__(self, *inputs):
        return self.execute(*inputs).get()

    def teardown(self):
        """API parity with the actor-loop backend; nothing to stop here."""

    def visualize_schedule(self, max_lanes: int = 8) -> str:
        """Render the compiled schedule: per-wave (and per-shard) lane
        tables with output slots, exported lanes marked `*` and each
        wave's cross-shard exchange spelled out (reference role:
        CompiledDAG schedule visualization, SURVEY.md §2.3)."""
        shards = (f", sharded ×{self.num_shards}" if self.num_shards > 1
                  else "")
        header = (
            f"CompiledJaxDAG: {self.num_tasks} tasks, "
            f"{self.num_waves} waves × width {self.wave_width}{shards}, "
            f"{'dynamic frontier' if self.dynamic else 'static levels'}, "
            f"payload {self.payload_shape} {jnp.dtype(self.dtype).name}, "
            f"ops {self.op_names}"
        )
        viz = getattr(self, "_viz", None)
        if not viz:
            return header
        lines = [header]

        def lane_str(entries, exported_flags=None):
            cells = []
            for i, e in enumerate(entries[:max_lanes]):
                ci, name, slot = e[0], e[1], e[2]
                star = "*" if (len(e) > 3 and e[3]) else ""
                cells.append(f"[{ci}]{name}->s{slot}{star}")
            if len(entries) > max_lanes:
                cells.append(f"… +{len(entries) - max_lanes} lanes")
            return "  ".join(cells)

        if viz["mode"] == "static":
            for wi, wave in enumerate(viz["waves"]):
                lines.append(f"wave {wi}: {lane_str(wave)}")
        elif viz["mode"] == "sharded_static":
            for wi, by_shard in enumerate(viz["waves"]):
                lines.append(f"wave {wi}:")
                exports = []
                for sh in range(viz["n_sh"]):
                    entries = by_shard.get(sh, [])
                    if entries:
                        lines.append(f"  shard {sh}: {lane_str(entries)}")
                    for ci, name, slot, exp in entries:
                        if exp:
                            exports.append(f"shard{sh}:[{ci}]->s{slot}")
                if exports:
                    lines.append(
                        "  exchange (all_gather): " + ", ".join(exports))
                else:
                    lines.append("  exchange: none (no collective)")
        elif viz["mode"] == "dynamic":
            lines.append(
                f"dynamic frontier over {len(viz['tasks'])} compiled "
                f"tasks, {viz['n_edges']} edges"
                + (f", frontier width {viz['frontier_width']}/shard"
                   if viz.get("frontier_width") else ""))
            for ci, name, slot in viz["tasks"][:max_lanes]:
                lines.append(f"  [{ci}]{name}->s{slot}")
            if len(viz["tasks"]) > max_lanes:
                lines.append(f"  … +{len(viz['tasks']) - max_lanes} tasks")
        return "\n".join(lines)


def compile_jax_dag(
    leaf: DAGNode,
    payload_shape: Sequence[int] = (),
    dtype=jnp.float32,
    dynamic: Optional[bool] = None,
    max_args: Optional[int] = None,
    fuse: bool = True,
    mesh=None,
    mesh_axis: Optional[str] = None,
    frontier_width: Optional[int] = None,
) -> CompiledJaxDAG:
    """Lower a static DAG of jax-traceable FunctionNodes to one XLA program.

    Every task op must map payload-shaped arrays to one payload-shaped array
    (uniform buckets; heterogeneous payloads belong in separate compiled
    graphs or the actor backend — see SURVEY.md §7 'hard parts').

    With ``mesh=`` (a ``jax.sharding.Mesh``), execution is partitioned over
    ``mesh_axis`` (default: the mesh's first axis of size > 1): each shard
    runs its slice of every wave and the wave's outputs cross shards via
    one ``lax.all_gather`` per wave — the multi-chip north-star path.
    """
    if dynamic is None:
        dynamic = GlobalConfig.wave_executor_dynamic
    if max_args is None:
        max_args = GlobalConfig.wave_executor_max_args

    n_sh = 1
    if mesh is not None:
        if mesh_axis is None:
            mesh_axis = next(
                (a for a in mesh.axis_names if mesh.shape[a] > 1),
                mesh.axis_names[0])
        if mesh_axis not in mesh.shape:
            raise ValueError(
                f"mesh has no axis {mesh_axis!r}; axes: {mesh.axis_names}")
        n_sh = mesh.shape[mesh_axis]
        if n_sh == 1:
            mesh = None  # degenerate: single-shard fall-through

    order = leaf.topological_order()

    # ---- classify nodes, assign object slots --------------------------------
    input_keys: List[Any] = []
    slot_of: Dict[int, int] = {}  # id(node) -> object slot
    tasks: List[FunctionNode] = []
    plain_input_used = False

    for node in order:
        if isinstance(node, InputNode):
            continue  # slot assigned via its consumers / attribute nodes
        elif isinstance(node, InputAttributeNode):
            if node._key not in input_keys:
                input_keys.append(node._key)
        elif isinstance(node, FunctionNode):
            tasks.append(node)
        elif isinstance(node, MultiOutputNode):
            if node is not leaf:
                raise ValueError("MultiOutputNode must be the DAG leaf")
        elif isinstance(node, ClassMethodNode):
            raise NotImplementedError(
                "backend='jax' compiles stateless task DAGs; for stateful "
                "actor pipelines use backend='actor' or "
                "ray_tpu.dag.jax_pipeline (jax-state actors)")
        else:
            raise TypeError(f"cannot compile node type {type(node).__name__}")

    consumes_plain_input = any(
        isinstance(a, InputNode)
        for t in tasks
        for a in list(t._bound_args) + list(t._bound_kwargs.values())
    )
    if consumes_plain_input and input_keys:
        raise ValueError(
            "mix of whole-input and projected-input (inp[i]) consumption is "
            "not supported in the jax backend")
    if consumes_plain_input:
        input_keys = [None]
        plain_input_used = True
    else:
        # Positional execute(*inputs) maps to inp[k] by key order, matching
        # the interpreted path's input_values[k] — NOT by topological
        # first-appearance, which depends on graph shape.
        if not all(isinstance(k, int) for k in input_keys):
            raise ValueError(
                "jax backend input projections must use integer keys "
                f"(inp[0], inp[1], ...); got {input_keys!r}")
        input_keys.sort()
        if input_keys != list(range(len(input_keys))):
            raise ValueError(
                f"jax backend requires dense input keys 0..N-1; got "
                f"{input_keys!r}")
    num_inputs = len(input_keys)

    # slots: [inputs..., task outputs...]
    for node in order:
        if isinstance(node, InputNode):
            if plain_input_used:
                slot_of[id(node)] = 0
        elif isinstance(node, InputAttributeNode):
            slot_of[id(node)] = input_keys.index(node._key)
    for i, t in enumerate(tasks):
        slot_of[id(t)] = num_inputs + i
    # Last row is a scratch slot: padding lanes in a wave scatter there so
    # they never collide with a real producer's slot.
    scratch_slot = num_inputs + len(tasks)
    num_slots = scratch_slot + 1

    # ---- per-task IR --------------------------------------------------------
    T = len(tasks)
    if T == 0:
        raise ValueError("DAG contains no tasks")
    task_fns: List[Callable] = []
    task_dep_slots: List[List[int]] = []
    seen_fn_arities: Dict[Tuple[int, int], str] = {}

    for t in tasks:
        if t._bound_kwargs:
            raise ValueError(
                "jax backend requires positional bind() args "
                f"(task {t.function.__name__!r} bound kwargs)")
        deps = list(t._bound_args)
        for a in deps:
            if not isinstance(a, DAGNode):
                raise ValueError(
                    "jax backend requires all bind() args to be DAG nodes; "
                    "close over constants instead")
        if len(deps) > max_args:
            raise ValueError(
                f"task {t.function.__name__!r} has {len(deps)} args > "
                f"max_args={max_args}; raise wave_executor_max_args or use "
                f"dag.reduce_tree")
        task_fns.append(t.function)
        task_dep_slots.append([slot_of[id(a)] for a in deps])
        seen_fn_arities[(id(t.function), len(deps))] = getattr(
            t.function, "__name__", "op")

    # ---- validate op shapes by abstract evaluation --------------------------
    payload_shape = tuple(payload_shape)
    aval = jax.ShapeDtypeStruct(payload_shape, dtype)
    checked = set()
    for fn, deps in zip(task_fns, task_dep_slots):
        key = (id(fn), len(deps))
        if key in checked:
            continue
        checked.add(key)
        out_aval = jax.eval_shape(fn, *([aval] * len(deps)))
        if (tuple(out_aval.shape) != payload_shape
                or out_aval.dtype != jnp.dtype(dtype)):
            raise ValueError(
                f"op {seen_fn_arities[key]!r} maps "
                f"{payload_shape}/{jnp.dtype(dtype).name} -> "
                f"{tuple(out_aval.shape)}/{out_aval.dtype}; all ops must "
                f"preserve the payload bucket")

    # ---- output slots -------------------------------------------------------
    if isinstance(leaf, MultiOutputNode):
        leaf_slots = np.asarray(
            [slot_of[id(a)] for a in leaf._bound_args], np.int32)
        multi_output = True
    else:
        leaf_slots = np.asarray([slot_of[id(leaf)]], np.int32)
        multi_output = False

    # ---- linear-run fusion --------------------------------------------------
    # A maximal chain t1 -> t2 -> ... -> tk where every interior output has
    # exactly one consumer (the next task, arity 1) and is not a DAG output
    # collapses into one macro-op: head fn applied to the head's args, then
    # the tail sequence applied via an unrolled loop / lax.scan. This removes
    # per-task object-table gather/scatter on sequential segments — the
    # scheduler optimization that makes fine-grained chains run at scan
    # speed instead of one wave per task.
    producer_of_slot = {num_inputs + i: i for i in range(T)}
    consumers: List[List[int]] = [[] for _ in range(T)]
    external = [False] * T
    for ti, deps in enumerate(task_dep_slots):
        for s in deps:
            p = producer_of_slot.get(s)
            if p is not None:
                consumers[p].append(ti)
    for s in leaf_slots.tolist():
        p = producer_of_slot.get(int(s))
        if p is not None:
            external[p] = True

    _UNROLL_LIMIT = 16

    def _make_macro(head_fn, head_arity, tail):
        """Compose head + arity-1 tail fns into one payload->payload op."""
        if not tail:
            return head_fn
        same = all(f is tail[0] for f in tail)
        if len(tail) <= _UNROLL_LIMIT:
            def macro(*args):
                x = head_fn(*args)
                for f in tail:
                    x = f(x)
                return x
        elif same:
            f = tail[0]
            k = len(tail)

            def macro(*args):
                x = head_fn(*args)
                # Unroll amortizes per-iteration loop dispatch on fine
                # chains (the op body is tiny by construction here).
                return lax.scan(
                    lambda c, _: (f(c), None), x, None, length=k,
                    unroll=min(2 * _UNROLL_LIMIT, k))[0]
        else:
            uniq: List[Callable] = []
            idx: Dict[int, int] = {}
            seq = []
            for f in tail:
                if id(f) not in idx:
                    idx[id(f)] = len(uniq)
                    uniq.append(f)
                seq.append(idx[id(f)])
            seq_np = np.asarray(seq, np.int32)

            def macro(*args):
                x = head_fn(*args)
                # Trace-time literal, NOT an eager device array: a closure
                # device const becomes a runtime parameter of the
                # program; an HLO literal is free.
                return lax.scan(
                    lambda c, o: (lax.switch(o, uniq, c), None),
                    x, jnp.asarray(seq_np))[0]
        return macro

    fused: List[Tuple[Callable, List[int], int, int, str]] = []
    assigned = [False] * T
    for ti in range(T):  # tasks[] is already topological
        if assigned[ti]:
            continue
        run = [ti]
        assigned[ti] = True
        cur = ti
        while (fuse and not external[cur] and len(consumers[cur]) == 1):
            nxt = consumers[cur][0]
            if assigned[nxt] or len(task_dep_slots[nxt]) != 1:
                break
            run.append(nxt)
            assigned[nxt] = True
            cur = nxt
        head = run[0]
        tail_fns = [task_fns[i] for i in run[1:]]
        macro = _make_macro(task_fns[head], len(task_dep_slots[head]),
                            tail_fns)
        name = getattr(task_fns[head], "__name__", "op")
        if tail_fns:
            name = f"fused[{len(run)}]{name}"
        fused.append((macro, task_dep_slots[head],
                      num_inputs + run[-1], len(run), name))

    # ---- compact op/task tables --------------------------------------------
    C = len(fused)
    op_index: Dict[Any, int] = {}
    op_fns: List[Callable] = []
    op_names: List[str] = []
    arity_of: List[int] = []
    op_ids = np.zeros(C, np.int32)
    arg_slots = np.zeros((C, max_args), np.int32)
    out_slots = np.zeros(C, np.int32)

    for ci, (macro, deps, out_slot, size, name) in enumerate(fused):
        # Fused macros are unique per run; plain ops dedupe by (fn, arity).
        key = (id(macro), len(deps)) if size == 1 else ("run", ci)
        if key not in op_index:
            op_index[key] = len(op_fns)
            op_fns.append(macro)
            op_names.append(name)
            arity_of.append(len(deps))
        op_ids[ci] = op_index[key]
        for ai, s in enumerate(deps):
            arg_slots[ci, ai] = s
        out_slots[ci] = out_slot

    # Branches for lax.switch: stacked args [max_args, *P] -> [*P].
    def _make_branch(fn, arity):
        def branch(stacked):
            return fn(*[stacked[i] for i in range(arity)])
        return branch

    branches = [
        _make_branch(fn, ar) for fn, ar in zip(op_fns, arity_of)
    ]
    single_op = len(branches) == 1
    # Schedule tables stay host numpy until trace time: jnp.asarray inside a
    # trace emits an HLO literal (free), while an eagerly-created device
    # array captured by the jit closure becomes a runtime parameter of
    # every dispatch.

    def _compute_tasks(obj, t_idx):
        """Run tasks t_idx (int32 [W], -1 = padding) → outputs [W, *P]."""
        valid = t_idx >= 0
        t = jnp.where(valid, t_idx, 0)
        a_slots = jnp.asarray(arg_slots)[t]             # [W, A]
        stacked = obj[a_slots]                          # [W, A, *P]
        if single_op:
            outs = jax.vmap(branches[0])(stacked)       # [W, *P]
        else:
            ops = jnp.asarray(op_ids)[t]
            outs = jax.vmap(
                lambda o, s: lax.switch(o, branches, s))(ops, stacked)
        return outs

    def _run_tasks(obj, t_idx):
        """Execute tasks t_idx and scatter outputs into the obj table."""
        outs = _compute_tasks(obj, t_idx)
        valid = t_idx >= 0
        t = jnp.where(valid, t_idx, 0)
        slots = jnp.where(valid, jnp.asarray(out_slots)[t], scratch_slot)
        return obj.at[slots].set(outs)

    # Dependency structure over the compact task list (slot-level).
    compact_producer = {int(s): ci for ci, s in enumerate(out_slots)}

    if not dynamic:
        # ---- static level schedule ------------------------------------------
        levels = np.zeros(C, np.int32)
        for ci, (_, deps, _, _, _) in enumerate(fused):
            lvl = 0
            for s in deps:
                p = compact_producer.get(int(s))
                if p is not None:
                    lvl = max(lvl, levels[p] + 1)
            levels[ci] = lvl
        num_waves = int(levels.max()) + 1
        waves: List[List[int]] = [[] for _ in range(num_waves)]
        for ci in range(C):
            waves[levels[ci]].append(ci)
        wave_width = max(len(w) for w in waves)
        sched = np.full((num_waves, wave_width), -1, np.int32)
        for wi, w in enumerate(waves):
            sched[wi, : len(w)] = w

        viz_names = [f[4] for f in fused]
        viz_out_slots = [int(s) for s in out_slots]

        if mesh is None:
            def program(inputs):
                sched_c = jnp.asarray(sched)   # trace-time literal
                obj = jnp.zeros((num_slots,) + payload_shape, dtype)
                if num_inputs:
                    obj = obj.at[:num_inputs].set(inputs)
                if num_waves == 1:
                    obj = _run_tasks(obj, sched_c[0])
                else:
                    obj = lax.fori_loop(
                        0, num_waves,
                        lambda w, o: _run_tasks(o, sched_c[w]), obj)
                out = obj[jnp.asarray(leaf_slots)]
                return out if multi_output else out[0]

            program.viz = {
                "mode": "static",
                "waves": [[(ci, viz_names[ci], viz_out_slots[ci])
                           for ci in w] for w in waves],
            }

        else:
            # ---- mesh-sharded static waves ----------------------------------
            # The schedule is sharded; the object table is PARTIALLY
            # replicated: every shard holds the full [num_slots] buffer in
            # HBM, but only writes (a) its own lanes' outputs and (b) slots
            # it imports from other shards — slots neither produced nor
            # consumed by a shard hold stale zeros there and are never
            # read. Lane assignment is locality-aware (a task prefers the
            # shard that produced most of its inputs), and the per-wave
            # exchange ships ONLY cross-shard-consumed outputs, packed to
            # the max export count X_max, through one tiled all_gather —
            # not the whole wave. Chain-heavy graphs export nothing and
            # skip the collective entirely; an all-to-all fan-in
            # degenerates to the old whole-wave gather.
            from jax.sharding import PartitionSpec as P

            Wn = -(-wave_width // n_sh)
            waves_list = waves  # [wave] -> [ci...]

            # Locality-aware lane assignment: balance Wn lanes per shard
            # per wave, preferring the shard owning most producers.
            owner = np.zeros(C, np.int32)
            for wi, w in enumerate(waves_list):
                counts = [0] * n_sh
                for ci in w:
                    prefs: Dict[int, int] = {}
                    for s in fused[ci][1]:
                        p = compact_producer.get(int(s))
                        if p is not None:
                            sh = int(owner[p])
                            prefs[sh] = prefs.get(sh, 0) + 1
                    cand = sorted(
                        range(n_sh),
                        key=lambda sh: (-prefs.get(sh, 0), counts[sh]))
                    sh = next(s for s in cand if counts[s] < Wn)
                    owner[ci] = sh
                    counts[sh] += 1

            # Which shards consume each slot (leaf slots: all shards, so
            # the out_specs-P() output is genuinely replicated).
            consumers_of_slot: Dict[int, set] = {}
            for ci, (_, deps, _, _, _) in enumerate(fused):
                for s in deps:
                    consumers_of_slot.setdefault(int(s), set()).add(
                        int(owner[ci]))
            for s in leaf_slots.tolist():
                consumers_of_slot.setdefault(int(s), set()).update(
                    range(n_sh))

            # Per-(wave, shard) lane tables + export sets.
            sched_sh = np.full((n_sh, num_waves, Wn), -1, np.int32)
            lane_of: Dict[int, Tuple[int, int]] = {}  # ci -> (shard, lane)
            for wi, w in enumerate(waves_list):
                fill = [0] * n_sh
                for ci in w:
                    sh = int(owner[ci])
                    sched_sh[sh, wi, fill[sh]] = ci
                    lane_of[ci] = (sh, fill[sh])
                    fill[sh] += 1
            exports: List[List[List[int]]] = [
                [[] for _ in range(num_waves)] for _ in range(n_sh)]
            for wi, w in enumerate(waves_list):
                for ci in w:
                    sh = int(owner[ci])
                    slot = int(out_slots[ci])
                    if consumers_of_slot.get(slot, set()) - {sh}:
                        exports[sh][wi].append(ci)
            X_max = max(
                (len(exports[sh][wi]) for sh in range(n_sh)
                 for wi in range(num_waves)), default=0)

            own_slots_sh = np.full((n_sh, num_waves, Wn), scratch_slot,
                                   np.int32)
            for ci, (sh, lane) in lane_of.items():
                lvl = int(levels[ci])
                own_slots_sh[sh, lvl, lane] = out_slots[ci]
            exp_idx_sh = np.zeros((n_sh, num_waves, max(X_max, 1)),
                                  np.int32)
            exp_slots = np.full((num_waves, n_sh * max(X_max, 1)),
                                scratch_slot, np.int32)
            for sh in range(n_sh):
                for wi in range(num_waves):
                    for k, ci in enumerate(exports[sh][wi]):
                        exp_idx_sh[sh, wi, k] = lane_of[ci][1]
                        exp_slots[wi, sh * max(X_max, 1) + k] = out_slots[ci]

            wave_width = Wn * n_sh

            def _sharded_static(inputs):
                # Every schedule table enters as a trace-time literal,
                # indexed by this shard's axis position — never as a
                # sharded runtime argument or closure device const (see
                # the literal-vs-device-const note at _compute_tasks).
                sh = lax.axis_index(mesh_axis)
                sched_l = jnp.asarray(sched_sh)[sh]      # [num_waves, Wn]
                own_l = jnp.asarray(own_slots_sh)[sh]
                expi_l = jnp.asarray(exp_idx_sh)[sh]
                obj = jnp.zeros((num_slots,) + payload_shape, dtype)
                if num_inputs:
                    obj = obj.at[:num_inputs].set(inputs)

                def wave(w, o):
                    outs = _compute_tasks(o, sched_l[w])       # [Wn, *P]
                    o = o.at[own_l[w]].set(outs)               # own outputs
                    if X_max > 0:
                        exp = outs[expi_l[w]]                  # [X_max, *P]
                        gathered = lax.all_gather(
                            exp, mesh_axis, axis=0, tiled=True)
                        o = o.at[jnp.asarray(exp_slots)[w]].set(gathered)
                    return o

                if num_waves == 1:
                    obj = wave(0, obj)
                else:
                    obj = lax.fori_loop(0, num_waves, wave, obj)
                out = obj[jnp.asarray(leaf_slots)]
                return out if multi_output else out[0]

            sharded_fn = jax.jit(jax.shard_map(
                _sharded_static, mesh=mesh,
                in_specs=(P(),),
                out_specs=P(), check_vma=False))

            def program(inputs):
                return sharded_fn(inputs)

            program.export_width = X_max
            program.lanes_per_shard = Wn
            exported_set = {ci for sh in range(n_sh)
                            for wi in range(num_waves)
                            for ci in exports[sh][wi]}
            program.viz = {
                "mode": "sharded_static",
                "n_sh": n_sh,
                "waves": [
                    {sh: [(int(ci), viz_names[int(ci)],
                           viz_out_slots[int(ci)], int(ci) in exported_set)
                          for ci in sched_sh[sh, wi] if ci >= 0]
                     for sh in range(n_sh)}
                    for wi in range(num_waves)
                ],
            }

    else:
        # ---- dynamic frontier (lax.while_loop) ------------------------------
        # Edge list producer-task -> consumer-task for in-degree updates.
        edges_src: List[int] = []
        edges_dst: List[int] = []
        indeg0 = np.zeros(C, np.int32)
        for ci, (_, deps, _, _, _) in enumerate(fused):
            for s in deps:
                src = compact_producer.get(int(s))
                if src is not None:
                    edges_src.append(src)
                    edges_dst.append(ci)
                    indeg0[ci] += 1
        e_src_np = np.asarray(edges_src, np.int32)
        e_dst_np = np.asarray(edges_dst, np.int32)
        num_waves = 0  # unknown statically
        wave_width = C

        if mesh is None:
            def program(inputs):
                # All tables enter the trace as literals (see the note at
                # _compute_tasks) — never as closure device arrays.
                e_src = jnp.asarray(e_src_np)
                e_dst = jnp.asarray(e_dst_np)
                all_tasks = jnp.arange(C, dtype=jnp.int32)
                obj = jnp.zeros((num_slots,) + payload_shape, dtype)
                if num_inputs:
                    obj = obj.at[:num_inputs].set(inputs)
                indeg = jnp.asarray(indeg0)
                done = jnp.zeros(C, bool)

                def cond(state):
                    _, _, done = state
                    return ~jnp.all(done)

                def body(state):
                    obj, indeg, done = state
                    ready = (indeg == 0) & ~done
                    t_idx = jnp.where(ready, all_tasks, -1)
                    obj = _run_tasks(obj, t_idx)
                    done = done | ready
                    # Frontier expansion: decrement consumers of finished
                    # producers via a segment-sum over the edge list.
                    if e_src_np.shape[0]:
                        fired = ready[e_src].astype(jnp.int32)
                        indeg = indeg - jnp.zeros_like(indeg).at[e_dst].add(
                            fired)
                    return obj, indeg, done

                obj, _, _ = lax.while_loop(cond, body, (obj, indeg, done))
                out = obj[jnp.asarray(leaf_slots)]
                return out if multi_output else out[0]

        else:
            # ---- mesh-sharded dynamic frontier ------------------------------
            # Task ci is owned by shard ci // Cn (contiguous blocks, padded
            # to C_pad = Cn*n_sh). The in-degree vector and done mask stay
            # replicated. Each iteration a shard executes up to F of its
            # ready tasks (lowest index first via top_k) and the exchange
            # ships ONLY those n_sh*F outputs + their ids — the
            # sparse-frontier premise survives sharding: a 10k-task graph
            # with a narrow ready set moves F payloads per shard per
            # iteration, not its whole owned slice.
            from jax.sharding import PartitionSpec as P

            Cn = -(-C // n_sh)
            C_pad = Cn * n_sh
            F = frontier_width or min(Cn, 32)
            F = max(1, min(int(F), Cn))
            out_slots_ext = np.full(C_pad + 1, scratch_slot, np.int32)
            out_slots_ext[:C] = out_slots  # index C_pad = dummy -> scratch
            indeg0_pad = np.zeros(C_pad, np.int32)
            indeg0_pad[:C] = indeg0
            done0_pad = np.zeros(C_pad, bool)
            done0_pad[C:] = True  # padding tasks are born finished
            ids_np = np.arange(C_pad, dtype=np.int32).reshape(n_sh, Cn)
            # Shard-partitioned graphs (every data edge stays inside its
            # owner's contiguous block) skip the per-iteration PAYLOAD
            # all_gather entirely: only the fired task ids (tiny int32
            # vectors) ride ICI each step, and the replicated outputs are
            # assembled ONCE after the loop with a psum over leaf owners.
            cross_payload = any(
                (s // Cn) != (d // Cn)
                for s, d in zip(edges_src, edges_dst))
            # leaf slot j's owner shard (0 for input-slot leaves, which
            # every shard holds identically).
            leaf_prod = [compact_producer.get(int(s))
                         for s in leaf_slots.tolist()]
            leaf_owner_np = np.asarray(
                [(p // Cn if p is not None else 0) for p in leaf_prod],
                np.int32)

            def _sharded_dynamic(inputs):
                # Owned-task ids as a trace-time literal indexed by shard
                # position (see the literal note at _compute_tasks).
                my_ids = jnp.asarray(ids_np)[lax.axis_index(mesh_axis)]
                obj = jnp.zeros((num_slots,) + payload_shape, dtype)
                if num_inputs:
                    obj = obj.at[:num_inputs].set(inputs)
                indeg = jnp.asarray(indeg0_pad)
                done = jnp.asarray(done0_pad)

                def cond(state):
                    _, _, done = state
                    return ~jnp.all(done)

                def body(state):
                    obj, indeg, done = state
                    ready = (indeg == 0) & ~done         # [C_pad]
                    mine = ready[my_ids]                 # [Cn]
                    # Top-F ready owned tasks, lowest index first.
                    scores = jnp.where(
                        mine, -my_ids.astype(jnp.float32), -jnp.inf)
                    _, sel = lax.top_k(scores, F)        # [F] positions
                    chosen = my_ids[sel]                 # [F] global ids
                    valid = mine[sel]
                    t_idx = jnp.where(valid, chosen, -1)
                    outs = _compute_tasks(obj, t_idx)    # [F, *P]
                    my_chosen = jnp.where(valid, chosen, C_pad)
                    g_ids = lax.all_gather(
                        my_chosen, mesh_axis, axis=0, tiled=True)  # [nF]
                    if cross_payload:
                        g_outs = lax.all_gather(
                            outs, mesh_axis, axis=0, tiled=True)  # [nF,*P]
                        obj = obj.at[jnp.asarray(out_slots_ext)[g_ids]].set(
                            g_outs)
                    else:
                        # Consumers are all local: write own outputs only.
                        obj = obj.at[
                            jnp.asarray(out_slots_ext)[my_chosen]].set(outs)
                    fired = (jnp.zeros(C_pad + 1, bool).at[g_ids].set(True)
                             )[:C_pad]
                    done = done | fired
                    if e_src_np.shape[0]:
                        hit = fired[jnp.asarray(e_src_np)].astype(jnp.int32)
                        indeg = indeg - jnp.zeros_like(indeg).at[
                            jnp.asarray(e_dst_np)].add(hit)
                    return obj, indeg, done

                obj, _, _ = lax.while_loop(cond, body, (obj, indeg, done))
                out = obj[jnp.asarray(leaf_slots)]
                if not cross_payload:
                    # Leaves live only on their producer shard; replicate
                    # once with a single masked psum (out_specs is P()).
                    sh = lax.axis_index(mesh_axis)
                    mask = (jnp.asarray(leaf_owner_np) == sh)
                    shape = (mask.shape[0],) + (1,) * (out.ndim - 1)
                    out = lax.psum(
                        jnp.where(mask.reshape(shape), out, 0), mesh_axis)
                return out if multi_output else out[0]

            sharded_fn = jax.jit(jax.shard_map(
                _sharded_dynamic, mesh=mesh,
                in_specs=(P(),),
                out_specs=P(), check_vma=False))

            def program(inputs):
                return sharded_fn(inputs)

            program.export_width = F if cross_payload else 0
            program.frontier_lanes = F
            program.lanes_per_shard = Cn

    fn = program if mesh is not None else jax.jit(program)
    dag = CompiledJaxDAG(
        fn, num_inputs, multi_output, T,
        num_waves, wave_width, payload_shape, dtype, dynamic, op_names,
        num_shards=n_sh if mesh is not None else 1,
    )
    dag.num_compiled_tasks = C
    # Sharded-exchange metadata: lanes run per shard per wave vs payloads
    # actually shipped over ICI per wave (X_max == 0 ⇒ no collective).
    dag.export_width = getattr(program, "export_width", None)
    dag.lanes_per_shard = getattr(program, "lanes_per_shard", None)
    dag._viz = getattr(program, "viz", None)
    if dag._viz is None and dynamic:
        dag._viz = {
            "mode": "dynamic",
            "tasks": [(ci, f[4], int(f[2])) for ci, f in enumerate(fused)],
            "n_edges": len(edges_src),
            "frontier_width": getattr(program, "frontier_lanes", None),
        }
    return dag
