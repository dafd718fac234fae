"""Training cells: one jitted AdamW step in this process.

The step is the one a ``JaxTrainer`` worker runs (``chip_smoke.py``
``_train_step``): ``value_and_grad`` of the program's ``loss_fn`` with
``optax.adamw``, parameters and optimizer state donated. Each step draws a
fresh batch on the device from the seed and the step's index, so no input
pipeline stalls it. Set-up builds the compiled step with its state once,
drives it through its first steps for the comparison that decides
``correct``, and hands that same object to the window.
"""

from __future__ import annotations

import gc
import os
import shutil
import sys
import time

from perfbench import flops, harness, trace_reduce, weights
from perfbench.reference import train_check

CHECK_STEPS = 3          # the reference follows these
WARM_STEPS = 2           # then the clock's estimate, before the window
TRACE_STEPS = 8


def run_model(config: dict) -> dict:
    """The configuration as it is run: the file's ``model`` (the source's
    values) with what ``assumed`` says runs in a value's place."""
    ran = {k: v["run"] for k, v in config.get("assumed", {}).items()
           if isinstance(v, dict) and "run" in v}
    return {**config["model"], **ran}


def model_config(model: dict):
    """The program's configuration of ``model``. It takes no eps:
    ``rms_norm`` has its own, which the file states under ``assumed``."""
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=model["rope_theta"], dtype=jnp.bfloat16)


def batch_of(key, index, batch: int, seq_len: int, vocab: int):
    """Step ``index``'s rows: tokens and their next tokens, all rows
    different. The reference draws its batches with this same function."""
    import jax

    rows = jax.random.randint(jax.random.fold_in(key, index),
                              (batch, seq_len + 1), 0, vocab)
    return rows[:, :-1], rows[:, 1:]


def build_step(config: dict):
    """The compiled step: (params, opt_state, key, index) -> (params,
    opt_state, loss), state donated. Returns (step, optimizer)."""
    import jax
    import optax

    from ray_tpu.models import loss_fn
    from ray_tpu.ops import backend

    cfg = model_config(config["model"])
    hp = config["step"]
    opt = optax.adamw(hp["learning_rate"], b1=hp["b1"], b2=hp["b2"],
                      eps=hp["eps"], weight_decay=hp["weight_decay"])

    def step(params, opt_state, key, index):
        tokens, targets = batch_of(key, index, hp["batch"], hp["seq_len"],
                                   cfg.vocab_size)
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, targets))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    donate = () if backend.on_cpu() else (0, 1)
    return jax.jit(step, donate_argnums=donate), opt


def first_moment(opt_state):
    """Adam's first moment out of optax's state, whatever wraps it."""
    for part in opt_state:
        if hasattr(part, "mu"):
            return part.mu
    raise ValueError("no Adam state in the optimizer's state")


def run(cell: dict, seed: int, seconds: float, trace: bool, devices: list,
        t_start: float) -> dict:
    import jax

    config = cell["config"]
    model, hp = run_model(config), config["step"]
    log = lambda *a: print("perfbench train:", *a, file=sys.stderr,
                           flush=True)
    key = weights.seed_key(seed)
    params = weights.make_params(model, seed)
    step, opt = build_step(config)
    opt_state = jax.jit(opt.init)(params)

    # The first steps, through the window's own call and feed.
    got = {"loss": []}
    for i in range(CHECK_STEPS):
        params, opt_state, loss = step(params, opt_state, key, i)
        got["loss"].append(float(loss))
        if i == 0:
            # The first gradient as the optimizer got it: mu = (1-b1) g.
            got["grad"] = {n: v / (1.0 - hp["b1"]) for n, v in
                           train_check.leaf_norms(
                               first_moment(opt_state)).items()}
    got["change"] = train_check.layerwise(
        weights.change_norms(model, seed, params))
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
        params, opt_state, loss = step(params, opt_state, key, index)
        index += 1
        steps += 1
        pending.append(loss)
        if len(pending) > hp["in_flight"]:
            pending.pop(0).block_until_ready()
            now = time.perf_counter()
            if came is not None:
                gaps.append((now - came, now - t0))
            came = now
    loss.block_until_ready()
    window_s = time.perf_counter() - t0
    _log_spacing(gaps, log)
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
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        plain_steps = steps - traced["steps"]
        log(f"traced {traced['steps']} steps in {traced['s']:.4f}s, bracket "
            f"{traced['bracket_s']:.3f}s; {plain_steps} plain steps")
        ctx = {"cell": cell, "model": model,
               "seconds": window_s - traced["bracket_s"],
               "peak": harness.peak(device["kind"]), "chips": len(devices),
               "steps": plain_steps,
               "tokens": plain_steps * hp["batch"] * hp["seq_len"],
               "step_cfg": hp, "traced_steps": traced["steps"],
               "trace": reduced, "planes": planes, "flops": flops}
        result["per_layer"] = harness.read_layer_metrics(cell, ctx)

    # The program's state goes before the reference takes its place.
    del params, opt_state, loss, pending
    gc.collect()
    want = train_check.reference_steps(model, hp, seed, CHECK_STEPS,
                                       batch_of, log=log)
    checks = train_check.compare(got, want, config["correct"], log=log)
    checks["loss_not_finite"] = harness.check(
        0 if last_loss == last_loss and abs(last_loss) < 1e30 else 1, 0)
    result["checks"] = checks
    result["correct"] = all(c["ok"] for c in checks.values())
    return result


def _log_spacing(gaps: list, log) -> None:
    """How evenly the plain steps came back: a stall of the host or the
    device shows as one long gap, a slower step as a longer median."""
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
