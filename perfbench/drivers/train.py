"""Training cells: one compiled step in this process, timed and checked.

Everything specific to a model comes from the family that the cell's
configuration names (``families/<family>/``: the seeded parameters, the
compiled step with its state, the plain reference, the counts the readers
divide by); this file is the loop every training cell shares. Set-up
builds the compiled step with its state once, drives it through its first
steps for the comparison that decides ``correct``, and hands that same
object to the window. Each step draws a fresh batch on the device from the
seed and the step's index, so no input pipeline stalls it.
"""

from __future__ import annotations

import gc
import os
import shutil
import sys
import time

from perfbench import flops, harness, segments, trace_reduce
from perfbench.reference import train_check

CHECK_STEPS = 3          # the reference follows these
WARM_STEPS = 2           # then the clock's estimate, before the window
TRACE_STEPS = 8


def run(cell: dict, seed: int, seconds: float, trace: bool, devices: list,
        t_start: float) -> dict:
    import jax

    config = cell["config"]
    family = harness.family(cell["paths"], config["family"])
    model, hp = harness.run_model(config), config["step"]
    log = lambda *a: print("perfbench train:", *a, file=sys.stderr,
                           flush=True)
    key = harness.seed_key(seed)
    params = family.make_params(model, seed)
    step, init = family.build_step(config)
    opt_state = jax.jit(init)(params)

    # The first steps, through the window's own call and feed.
    got = {"loss": []}
    for i in range(CHECK_STEPS):
        params, opt_state, loss = step(params, opt_state, key, i)
        got["loss"].append(float(loss))
        if i == 0:
            # The first gradient as the optimizer got it: mu = (1-b1) g.
            got["grad"] = {n: v / (1.0 - hp["b1"]) for n, v in
                           family.leaf_norms(
                               family.first_moment(opt_state)).items()}
    got["change"] = family.change_norms(model, seed, params)
    index = CHECK_STEPS
    t = time.perf_counter()
    for _ in range(WARM_STEPS):
        params, opt_state, loss = step(params, opt_state, key, index)
        index += 1
    loss.block_until_ready()
    est = (time.perf_counter() - t) / WARM_STEPS
    log(f"first losses {got['loss']}, about {est * 1e3:.1f} ms a step")

    # The window: keep ``in_flight`` steps ahead of the device, no
    # readback, end in block_until_ready. A traced run brackets
    # TRACE_STEPS of it with the profiler; the bracket's seconds and steps
    # are kept apart, so that the per-layer step time is taken over the
    # same plain steps as an untraced run's.
    trace_dir = os.path.join(harness.OUT_DIR, "trace", cell["name"])
    compiles0 = _compilations()
    traced = {}
    pending, gaps, came = [], [], None   # (gap, when) between steps' returns
    first_back, slowest_call = None, (0.0, 0.0)   # where a stall hides
    steps = 0
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    trace_at = int(0.4 * seconds / est) if trace else -1
    while time.perf_counter() - t0 < seconds:
        if steps == trace_at:
            loss.block_until_ready()       # the steps in flight are plain
            t_bracket = time.perf_counter()
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            t_tr = time.perf_counter()
            for _ in range(TRACE_STEPS):
                params, opt_state, loss = step(params, opt_state, key, index)
                index += 1
                steps += 1
            loss.block_until_ready()
            traced["s"] = time.perf_counter() - t_tr
            jax.profiler.stop_trace()
            traced["steps"] = TRACE_STEPS
            traced["bracket_s"] = time.perf_counter() - t_bracket
            pending, came = [], None
            continue
        t_call = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, key, index)
        slowest_call = max(slowest_call,
                           (time.perf_counter() - t_call, t_call - t0))
        index += 1
        steps += 1
        pending.append(loss)
        if len(pending) > hp["in_flight"]:
            pending.pop(0).block_until_ready()
            now = time.perf_counter()
            if came is not None:
                gaps.append((now - came, now - t0))
            elif first_back is None:
                first_back = now - t0
            came = now
    loss.block_until_ready()
    window_s = time.perf_counter() - t0
    _log_spacing(gaps, log)
    log(f"the window's first step was back after {first_back or 0:.3f}s; "
        f"the slowest call of the step took {slowest_call[0] * 1e3:.2f} ms "
        f"({slowest_call[1]:.2f}s into the window)")
    last_loss = float(loss)
    log(f"compilations inside the window: {_compilations() - compiles0}")
    device = harness.device_block(devices)
    tokens = steps * hp["batch"] * hp["seq_len"]
    metrics = {"train_tokens_per_s": tokens / window_s, "setup_s": setup_s}
    log(f"window: {steps} steps in {window_s:.3f}s, {metrics}, "
        f"last loss {last_loss}")
    result = {"attempted": steps, "failed": 0, "end_to_end": metrics,
              "device": device}

    if trace:
        if not traced:
            raise SystemExit("perfbench: the window was too short to trace")
        planes = trace_reduce.load(trace_dir)
        reduced = trace_reduce.reduce(planes, traced["s"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        plain_steps = steps - traced["steps"]
        log(f"traced {traced['steps']} steps in {traced['s']:.4f}s, bracket "
            f"{traced['bracket_s']:.3f}s; {plain_steps} plain steps")
        ctx = {"cell": cell, "model": model,
               "seconds": window_s - traced["bracket_s"],
               "peak": harness.peak(device["kind"]), "chips": len(devices),
               "steps": plain_steps,
               "tokens": plain_steps * hp["batch"] * hp["seq_len"],
               "step_cfg": hp, "traced_steps": traced["steps"],
               "trace": reduced, "planes": planes, "flops": flops,
               "family": family}
        result["per_layer"] = harness.read_layer_metrics(cell, ctx)
        result["breakdown"] = {
            "device_ops": segments.with_segments(reduced["device_ops"], ctx),
            "idle_gaps": reduced["idle_gaps"]}

    # The program's state goes before the reference takes its place.
    del params, opt_state, loss, pending
    gc.collect()
    want = train_check.reference_steps(family, model, hp, seed, CHECK_STEPS,
                                       log=log)
    checks = train_check.compare(got, want, config["correct"], log=log)
    checks["loss_not_finite"] = harness.check(
        0 if last_loss == last_loss and abs(last_loss) < 1e30 else 1, 0)
    result["checks"] = checks
    result["correct"] = all(c["ok"] for c in checks.values())
    return result


def _log_spacing(gaps: list, log) -> None:
    """How evenly the plain steps came back: a stall of the host or the
    device shows as one long gap, a slower step as a longer median. A
    stall before the window's first step is back shows in no gap (PR 30
    saw 2.2 s lost there in one run of the parent): the line after this
    one says when that step was back and how long the slowest call of
    the step held the host."""
    if len(gaps) >= 20:
        # A step that grows through the window (a router that moves, PR 38)
        # shows here and in no median of the whole.
        first, last = (sorted(g for g, _ in part)[5]
                       for part in (gaps[:10], gaps[-10:]))
        log(f"the window's first 10 steps came back {first * 1e3:.2f} ms "
            f"apart at the median, its last 10 {last * 1e3:.2f}")
    gaps = sorted(gaps)
    if gaps:
        median = gaps[len(gaps) // 2][0]
        log(f"steps came back {median * 1e3:.2f} ms apart at the median, "
            f"{gaps[-1][0] * 1e3:.2f} at the most ({gaps[-1][1]:.2f}s into "
            f"the window), {sum(g > 1.5 * median for g, _ in gaps)} gaps "
            f"over 1.5 medians")


def _compilations() -> int:
    from ray_tpu.ops.backend import device_info

    return device_info()["compilations"]
