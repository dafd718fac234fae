"""What the sum of T row cotangents into a table's gradient costs on a chip.

``models/transformer.py:_table_rows_grad`` (the backward pass of ``_embed``)
makes that sum; this probe times the forms it could take, one jitted program
each: at the benchmark's five ``(V, D)`` and at Ling's crossed with the
others', and (``--programs sweep``) by width, by rows summed and by the
table's height. ``PERF.md`` section 5 (PR 46) has the readings: they are
why the rows are summed a power-of-two block of columns at a time.

    python3 scripts/probe_embed_grad.py                    # on the chip
    python3 scripts/probe_embed_grad.py --programs sweep   # on the chip
    python3 scripts/probe_embed_grad.py --aot      # here: the v5e compiler's
                                                   # text of each form, no time

Every form takes ``ids [T]`` uniform in ``[0, V)`` and ``g [T, D]`` bfloat16.
A time is the device's: the program's event on the trace's ``XLA Modules``
line (median of the calls), with the host's clock over queued calls beside
it. The compiled text of each program is written to ``--out`` with the line
of its scatter, which holds the layout and the memory space (``S(1)``) the
compiler gave the gradient.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import statistics
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# cell -> (V, D, T): the table and the rows a step draws
CELLS = {"mistral": (32768, 4096, 4096), "lfm2": (8192, 2048, 8192),
         "nemotron3": (16384, 4096, 4096), "joyai": (16160, 2048, 4096),
         "ling3": (19648, 2560, 4096)}
CROSSED = {"ling3.d2048": (19648, 2048, 4096), "ling3.d4096": (19648, 4096, 4096),
           "ling3.v16384": (16384, 2560, 4096), "ling3.v19712": (19712, 2560, 4096)}
BF16, F32 = jnp.bfloat16, jnp.float32


def raw(dt):
    """The instruction alone: zeros in ``dt`` and the scatter-add into them."""
    def form(V, ids, g):
        return jnp.zeros((V, g.shape[1]), dt).at[ids].add(g.astype(dt))
    return form


def as_model(V, ids, g):
    """What JAX transposes ``table.astype(bf16)[ids]`` into: bf16 zeros, the
    scatter-add, the cast's transpose over the whole table."""
    table = jnp.zeros((V, g.shape[1]), F32)
    return jax.vjp(lambda t: t.astype(BF16)[ids], table)[1](g)[0]


def sorted_f32(V, ids, g):
    order = jnp.argsort(ids)
    return jnp.zeros((V, g.shape[1]), F32).at[ids[order]].add(
        g[order].astype(F32), indices_are_sorted=True)


def split_f32(V, ids, g):
    """The row's columns in power-of-two blocks (2560 = 2048 + 512)."""
    out, at, D = jnp.zeros((V, g.shape[1]), F32), 0, g.shape[1]
    while at < D:
        w = 1 << ((D - at).bit_length() - 1)
        out = out.at[ids, at:at + w].add(g[:, at:at + w].astype(F32))
        at += w
    return out


def padded_f32(V, ids, g):
    """At the next power-of-two width, cut back."""
    D = g.shape[1]
    W = 1 << (D - 1).bit_length()
    wide = jnp.zeros((V, W), F32).at[ids].add(
        jnp.pad(g.astype(F32), ((0, 0), (0, W - D))))
    return wide[:, :D]


def one_hot(V, ids, g):
    """``one_hot(ids).T @ g``, bf16 operands, f32 accumulation."""
    hot = jax.nn.one_hot(ids, V, dtype=BF16)
    return lax.dot_general(hot, g, (((0,), (0,)), ((), ())),
                           preferred_element_type=F32)


def padded_to(multiple):
    """At the next multiple of ``multiple`` columns, cut back."""
    def form(V, ids, g):
        D = g.shape[1]
        W = -(-D // multiple) * multiple
        wide = jnp.zeros((V, W), F32).at[ids].add(
            jnp.pad(g.astype(F32), ((0, 0), (0, W - D))))
        return wide[:, :D]
    return form


def two_tables(V, ids, g):
    """The columns' power-of-two blocks each a table of its own, joined."""
    parts, at, D = [], 0, g.shape[1]
    while at < D:
        w = 1 << ((D - at).bit_length() - 1)
        parts.append(raw(F32)(V, ids, g[:, at:at + w]))
        at += w
    return jnp.concatenate(parts, axis=1)


FORMS = {"raw_bf16": raw(BF16), "raw_f32": raw(F32), "as_model": as_model,
         "sorted_f32": sorted_f32, "split_f32": split_f32, "padded_f32": padded_f32, "one_hot": one_hot,
         "padded_1024": padded_to(1024), "padded_512": padded_to(512),
         "two_tables": two_tables}
# every shape takes the first three; the cells' own take the cures too
CURES = ("sorted_f32", "split_f32", "padded_f32", "one_hot")


# The second call's: what a row costs by its width (16384 rows, 4096 ids,
# f32), by the rows summed and by the table's height at 2560 columns, and
# three more cures at Ling's shape.
WIDTHS = (128, 256, 512, 640, 768, 1024, 1280, 1536, 1792, 2048, 2304, 2560,
          2688, 2816, 3072, 3584, 4096, 5120, 6144, 7168, 8192)


def sweep():
    out = [(f"raw_f32.w{D}", FORMS["raw_f32"], 16384, D, 4096)
           for D in WIDTHS]
    out += [(f"raw_f32.t{T}", FORMS["raw_f32"], 19648, 2560, T)
            for T in (512, 1024, 2048, 8192)]
    out += [(f"raw_f32.v{V}", FORMS["raw_f32"], V, 2560, 4096)
            for V in (2048, 4096, 8192)]
    out += [(f"{form}.ling3", FORMS[form], 19648, 2560, 4096)
            for form in ("padded_512", "padded_1024", "padded_f32",
                         "two_tables", "raw_f32")]
    out += [(f"{form}.w{D}", FORMS[form], 16384, D, 4096)
            for D in (1536, 3072, 5120, 6144) for form in ("padded_f32",)]
    return out


def programs(which="cells"):
    """(name, form, V, D, T) of every program the probe runs."""
    if which == "sweep":
        return sweep()
    out = []
    for shape, (V, D, T) in {**CELLS, **CROSSED}.items():
        forms = ("raw_bf16", "raw_f32", "as_model")
        if shape in CELLS:
            forms += CURES
        for form in forms:
            if form in ("split_f32", "padded_f32") and D & (D - 1) == 0:
                continue        # one block, no pad: the program is raw_f32's
            out.append((f"{form}.{shape}", FORMS[form], V, D, T))
    return out


def jitted(name, form, V):
    fn = lambda ids, g: form(V, ids, g)
    fn.__name__ = "probe_" + name.replace(".", "_")
    return jax.jit(fn)


def scatter_lines(text):
    """The compiled text's scatters and products, each cut to its result."""
    return [m.group(0)[:160] for m in re.finditer(
        r"%(scatter|convolution|dot)[.\w-]* = [^ ]+ (scatter|convolution|dot)\(",
        text)]


def aot(args):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    os.makedirs(args.out, exist_ok=True)
    for name, form, V, D, T in programs(args.programs):
        if args.only and not re.search(args.only, name):
            continue
        ids = jax.ShapeDtypeStruct((T,), jnp.int32, sharding=dev)
        g = jax.ShapeDtypeStruct((T, D), BF16, sharding=dev)
        compiled = jitted(name, form, V).lower(ids, g).compile()
        text = compiled.as_text()
        open(os.path.join(args.out, name + ".txt"), "w").write(text)
        m = compiled.memory_analysis()
        print(json.dumps({"program": name, "temp_bytes": m.temp_size_in_bytes,
                          "lines": scatter_lines(text)}), flush=True)


def module_times(trace_dir):
    """program name -> device durations (ns) of its runs, off the trace's
    ``XLA Modules`` line."""
    from perfbench import trace_reduce

    out = {}
    for plane, lines in trace_reduce.load(trace_dir).items():
        if not plane.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        for name, _start, dur in lines.get("XLA Modules", []):
            m = re.match(r"jit_(probe_\w+?)(\(\d+\))?$", name)
            if m:
                out.setdefault(m.group(1), []).append(dur)
        if not out:
            print("probe: no program found on", plane, "lines", sorted(lines),
                  [e[0] for e in lines.get("XLA Modules", [])[:3]],
                  file=sys.stderr)
    return out


def chip(args):
    from ray_tpu.util import profiling

    dev = jax.devices()[0]
    assert dev.platform == "tpu", dev
    os.makedirs(args.out, exist_ok=True)
    built = []
    for name, form, V, D, T in programs(args.programs):
        if args.only and not re.search(args.only, name):
            continue
        key = jax.random.fold_in(jax.random.PRNGKey(args.seed), len(built))
        ids = jax.random.randint(key, (T,), 0, V, jnp.int32)
        g = jax.random.normal(key, (T, D), BF16)
        fn = jitted(name, form, V)
        text = fn.lower(ids, g).compile().as_text()
        open(os.path.join(args.out, name + ".txt"), "w").write(text)
        fn(ids, g).block_until_ready()
        host = []
        for _ in range(3):
            t = time.perf_counter()
            for _ in range(args.queued):
                out = fn(ids, g)
            out.block_until_ready()
            host.append((time.perf_counter() - t) / args.queued)
        del out
        built.append((name, fn, ids, g, min(host), scatter_lines(text)))
    trace_dir = os.path.join(args.out, "trace")
    with profiling.profile_trace(trace_dir):
        for _name, fn, ids, g, _host, _lines in built:
            for _ in range(args.calls):
                fn(ids, g).block_until_ready()
    device = module_times(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    for name, _fn, ids, g, host, lines in built:
        runs = device.get("probe_" + name.replace(".", "_"), [])
        ms = statistics.median(runs) / 1e6 if runs else None
        print(json.dumps({
            "program": name, "device": dev.device_kind, "rows": ids.shape[0],
            "device_ms": ms, "runs": len(runs), "host_ms": host * 1e3,
            "ns_a_row": ms and ms * 1e6 / ids.shape[0], "lines": lines}),
            flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--programs", default="cells", choices=["cells", "sweep"])
    ap.add_argument("--only", default="")
    ap.add_argument("--out", default="chiprun_out/pr46/probe")
    ap.add_argument("--seed", type=int, default=46001)
    ap.add_argument("--queued", type=int, default=4)
    ap.add_argument("--calls", type=int, default=5)
    args = ap.parse_args()
    (aot if args.aot else chip)(args)


if __name__ == "__main__":
    main()
