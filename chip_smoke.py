#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

Drives the three paths that are meant to run on a TPU through the entry
points a user calls, once each, at real widths with seeded random
weights, and checks what comes out by the repo's own means:

    devices   JAX finds the chip; block_until_ready() really blocks
    numerics  compiled Pallas kernels and the paged-cache serving path
              agree with their dense jnp references
    serve     serve.run(build_llm_app(...)) answers eight streamed requests
              from a replica process that owns the chip
    train     JaxTrainer.fit() takes six AdamW steps in a TPU worker
    dag       experimental_compile(backend="jax") equals the interpreted DAG

The parent never imports JAX: each phase is a child process run in turn
with a timeout, so each takes the chip after the last has exited (a chip
belongs to one process at a time). Every phase prints one JSON line with
the platform, device kind, device count and JAX version seen BY THE
PROCESS THAT RAN THE MODEL, plus set-up and compile seconds — no rate,
no utilization. Any failed check, timeout or killed child is a non-zero
exit and no result line; so is a CPU-only JAX.

    python3 chip_smoke.py              # one chip, every default phase
    python3 chip_smoke.py --chips 4    # a four-chip host: serve_tp4 (one
                                       # TP-4 replica), tp4_numerics, spmd_train
                                       # (dp2 x tp2), replicas4 (4 x one chip)
    python3 chip_smoke.py --only serve # one phase (debugging)

The last line of standard output on success:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import math
import os
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "smoke")

# ------------------------------------------------------------------ sizes
# Plain data, so tests/test_tpu_aot.py can compile the same programs
# ahead of time and the parent process needs no JAX to read them.
#
# Serving: the public Llama-3.2-1B shape (dense GQA, 1.50 B parameters),
# bf16 compute over the f32 master weights init_params makes (5.58 GB).
SERVE_MODEL = dict(vocab_size=128256, d_model=2048, n_layers=16, n_heads=32,
                   n_kv_heads=8, d_ff=8192, max_seq_len=4096,
                   rope_theta=500000.0)
# 4096 blocks x 16 tokens is a 2 GiB pool: the scan-carried pool is not
# updated in place (two pool-sized temporaries), and an 8192-block pool
# is refused on one 16 GB chip.
SERVE_ENGINE = dict(num_blocks=4096, block_size=16, max_num_seqs=32,
                    prefill_token_budget=2048)
SERVE_NEW_TOKENS = 32
# bf16 logits of two routes through the same weights (numerics phase).
LOGIT_MAX_TOL, LOGIT_MEAN_TOL = 0.15, 0.02
# Training: a dense 201M model, at 8 x 1024.
TRAIN_MODEL = dict(vocab_size=32768, d_model=1024, n_layers=8, n_heads=16,
                   n_kv_heads=16, d_ff=4096, max_seq_len=1024)
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 6

PHASES = ("devices", "numerics", "serve", "train", "dag")
PHASES_4CHIP = ("devices", "serve_tp4", "tp4_numerics", "spmd_train",
                "replicas4")
PHASE_TIMEOUT_S = {"devices": 120, "numerics": 420, "serve": 780,
                   "train": 420, "dag": 300, "serve_tp4": 780,
                   "tp4_numerics": 420, "spmd_train": 420, "replicas4": 780}


class SmokeFailure(AssertionError):
    pass


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# ---------------------------------------------------------------- helpers
def _device_record() -> dict:
    from ray_tpu.ops.backend import device_info

    return device_info()


def _require_chip(info: dict, where: str) -> None:
    check(info["platform"] != "cpu",
          f"{where}: no TPU — JAX reports platform {info['platform']!r} "
          f"({info['device_kind']}); a CPU result is refused")


def _driver_backends() -> list:
    """Initialised JAX backends of THIS process (importing jax for a
    config object initialises none)."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    return sorted(getattr(bridge, "_backends", {}) or {})


def _serve_config(tp: int = 1):
    import jax.numpy as jnp

    from ray_tpu.llm import EngineConfig
    from ray_tpu.models import TransformerConfig

    return EngineConfig(
        model=TransformerConfig(dtype=jnp.bfloat16, **SERVE_MODEL),
        tp_size=tp, param_seed=0, **SERVE_ENGINE)


def _serve_requests() -> list:
    """Eight seeded greedy requests, prompts of 64 to 2048 tokens: the
    first two share a 1024-token prefix, the third and fourth are
    identical."""
    import random

    rng = random.Random(20260926)
    vocab = SERVE_MODEL["vocab_size"]

    def toks(n):
        return [rng.randrange(1, vocab) for _ in range(n)]

    prefix = toks(1024)
    twin = toks(512)
    prompts = [prefix + toks(64), prefix + toks(64), twin, list(twin),
               toks(64), toks(256), toks(2048), toks(128)]
    return [{"prompt": p, "max_new_tokens": SERVE_NEW_TOKENS,
             "temperature": 0.0} for p in prompts]


def _stream_all(handle, requests) -> list:
    """Stream every request to the end. The first runs alone until its
    first token, so its prompt blocks are registered before the request
    that shares its prefix is admitted; the rest run concurrently
    (continuous batching), the identical pair submitted first so that
    one admission takes both into the same rows of the same programs."""
    import threading

    outs = [None] * len(requests)
    errors = []
    first_token = threading.Event()

    def run(i):
        try:
            got = []
            for tok in handle.options(stream=True).remote(requests[i]):
                got.append(int(tok))
                if i == 0:
                    first_token.set()
            outs[i] = got
        except BaseException as exc:  # noqa: BLE001 — reported below
            errors.append(f"request {i}: {exc!r}")
        finally:
            first_token.set()

    threads = [threading.Thread(target=run, args=(i,), daemon=True)
               for i in range(len(requests))]
    threads[0].start()
    first_token.wait(timeout=600)
    for i in (2, 3, 1, 4, 5, 6, 7):
        threads[i].start()
    for t in threads:
        t.join(timeout=700)
    check(not errors, "; ".join(errors))
    check(all(o is not None for o in outs), "a stream did not finish")
    return outs


def _check_streams(outs: list, stats: list, tp: int) -> dict:
    vocab = SERVE_MODEL["vocab_size"]
    for i, got in enumerate(outs):
        check(len(got) == SERVE_NEW_TOKENS,
              f"request {i} yielded {len(got)} tokens, "
              f"not {SERVE_NEW_TOKENS}")
        check(all(0 <= t < vocab for t in got),
              f"request {i} yielded an out-of-vocabulary id")
    for st in stats:
        _require_chip(st, "serve replica")
        check(st["failed_requests"] == 0,
              f"the engine failed a request: {st['last_failure']}")
        check(st["shed_requests"] == 0, "the engine shed a request")
    pair_equal_until = next(
        (i for i, (a, b) in enumerate(zip(outs[2], outs[3])) if a != b),
        SERVE_NEW_TOKENS)
    if len(stats) == 1:
        # One engine saw every request (several replicas: the router may
        # part the pairs). Request 1 was admitted after request 0's
        # prompt blocks were registered, so it skipped the shared prefix.
        saved = stats[0]["prefill_tokens_saved"]
        check(saved >= 1024,
              "the prefix-hit counter did not move by the shared prefix")
        # The identical pair must agree where the same arithmetic ran for
        # both: one chip, and one admission took both (same rows of the
        # same programs — had the second come a step later it would have
        # reused the first's blocks, the counter shows it, and taken
        # other programs). A bf16 argmax over seeded weights survives
        # nothing less: under TP the all-reduce sums each row's shards in
        # a different order, and identical rows part (measured, PR 21).
        if saved == 1024 and tp == 1:
            check(pair_equal_until == SERVE_NEW_TOKENS,
                  "the identical pair of requests disagree")
    return {"identical_pair_equal_until": pair_equal_until}


def _paged_probe(s_len: int = 256):
    """Two seeded rows of ``s_len`` tokens, block tables that hold them,
    and the index of the last position: prefill everything but the last
    token (the chunk's tail past chunk_lens is padding), then decode the
    last token at position ``s_len - 1``."""
    import jax.numpy as jnp
    import numpy as np

    tokens = np.random.default_rng(1).integers(
        1, SERVE_MODEL["vocab_size"], (2, s_len), dtype=np.int32)
    m = s_len // SERVE_ENGINE["block_size"]
    tables = 1 + np.arange(2 * m, dtype=np.int32).reshape(2, m)
    return (jnp.asarray(tokens), jnp.asarray(tables),
            jnp.full((2,), s_len - 1, jnp.int32))


def _all_replica_stats(handle, n: int) -> list:
    """stats() of each of ``n`` replicas (calls are routed, so ask until
    every replica process has answered)."""
    by_pid = {}
    for _ in range(40 * n):
        st = handle.stats.remote().result(timeout=600)
        by_pid[st["pid"]] = st
        if len(by_pid) == n:
            break
    check(len(by_pid) == n,
          f"{len(by_pid)} of {n} replicas answered stats()")
    return list(by_pid.values())


# ----------------------------------------------------------------- phases
def phase_devices() -> dict:
    """Open the chip; check that block_until_ready() blocks: a long
    dependent matmul chain must take block_until_ready about as long as
    it takes a host readback, and far longer than the dispatch."""
    t0 = time.perf_counter()
    import jax
    import jax.numpy as jnp

    info = _device_record()
    _require_chip(info, "devices")
    setup_s = time.perf_counter() - t0

    x = jnp.ones((4096, 4096), jnp.bfloat16)

    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(
            0, 400, lambda i, c: (c @ x) * (1.0 / 4096), x)

    t0 = time.perf_counter()
    chain(x).block_until_ready()
    compile_s = time.perf_counter() - t0
    dispatch_s, blocked_s, readback_s = [], [], []
    for _ in range(3):  # the least of three: one slow run is noise
        t0 = time.perf_counter()
        y = chain(x)
        dispatch_s.append(time.perf_counter() - t0)
        y.block_until_ready()
        blocked_s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        float(chain(x)[0, 0])
        readback_s.append(time.perf_counter() - t0)
    dispatch_s, blocked_s, readback_s = map(
        min, (dispatch_s, blocked_s, readback_s))
    # On a harness where block_until_ready() returns before the device
    # is done it costs no more than the dispatch, and far less than the
    # readback, which cannot return early.
    check(blocked_s > 20 * dispatch_s and blocked_s > 0.05,
          f"block_until_ready() returned after {blocked_s:.4f}s, dispatch "
          f"took {dispatch_s:.4f}s: it does not wait for the device")
    check(blocked_s > 0.75 * readback_s,
          f"block_until_ready ({blocked_s:.4f}s) returns long before a "
          f"host readback of the same chain ({readback_s:.4f}s)")
    mem = jax.devices()[0].memory_stats() or {}
    return {**info, "setup_s": round(setup_s, 2),
            "compile_s": round(compile_s, 2),
            "block_until_ready_blocks": True,
            "chain_dispatch_s": round(dispatch_s, 5),
            "chain_blocked_s": round(blocked_s, 4),
            "chain_readback_s": round(readback_s, 4),
            "hbm_bytes_limit": mem.get("bytes_limit")}


def phase_numerics() -> dict:
    """Compiled (never interpreted) kernels against the dense jnp form,
    the paged-cache serving path against forward(), and the lowered
    train step must contain the kernels' custom calls."""
    t0 = time.perf_counter()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import (
        TransformerConfig,
        decode_step,
        forward,
        init_kv_cache,
        init_params,
        prefill_chunk,
    )
    from ray_tpu.ops.flash_attention import (
        _fallback,
        _fallback_grouped,
        flash_attention,
        flash_attention_grouped,
    )

    info = _device_record()
    _require_chip(info, "numerics")
    setup_s = time.perf_counter() - t0
    worst = {}

    def rel_err(got, ref):
        ref = np.asarray(ref, np.float32)
        return float(np.max(np.abs(np.asarray(got, np.float32) - ref))
                     / max(float(np.max(np.abs(ref))), 1e-6))

    # -- kernels: bf16 in (what the training path feeds them), reference
    # in f32 at highest matmul precision. Tolerance 1e-2 of the largest
    # reference value: the kernel rounds each probability tile to bf16
    # before the PV matmul and its output to bf16 (eps 2^-8 = 3.9e-3),
    # and the backward chains three such matmuls; measured 2.8e-3 to
    # 4.4e-3 on the v5e. One more rounding to a narrower type fails it.
    for d in (64, 128):
        for s in (1024, 4096):
            ks = jax.random.split(jax.random.PRNGKey(d + s), 3)
            q, k, v = (jax.random.normal(kk, (1, 4, s, d), jnp.bfloat16)
                       for kk in ks)
            kg, vg = k[:, :2], v[:, :2]
            f32 = [a.astype(jnp.float32) for a in (q, k, v)]

            def loss(fn, *a):
                return jnp.sum(fn(*a).astype(jnp.float32) ** 2)

            out = flash_attention(q, k, v, causal=True, interpret=False)
            grads = jax.grad(
                lambda *a: loss(lambda *b: flash_attention(
                    *b, causal=True, interpret=False), *a),
                argnums=(0, 1, 2))(q, k, v)
            outg = flash_attention_grouped(q, kg, vg, causal=True,
                                           interpret=False)
            with jax.default_matmul_precision("highest"):
                ref = _fallback(*f32, True, d ** -0.5)
                ref_grads = jax.grad(
                    lambda *a: loss(lambda *b: _fallback(
                        *b, True, d ** -0.5), *a),
                    argnums=(0, 1, 2))(*f32)
                refg = _fallback_grouped(
                    f32[0], f32[1][:, :2], f32[2][:, :2], True, d ** -0.5)
            errs = {"fwd": rel_err(out, ref), "grouped": rel_err(outg, refg)}
            for name, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads):
                errs[name] = rel_err(g, rg)
            for name, e in errs.items():
                check(np.isfinite(e) and e < 1e-2,
                      f"flash {name} at head_dim {d}, S {s}: relative "
                      f"error {e:.4f} >= 1e-2")
                worst[name] = max(worst.get(name, 0.0), e)

    # -- the train step must lower to the kernels (three custom calls:
    # forward, dq, dk/dv), so the dense path cannot stand in.
    import optax

    tcfg = TransformerConfig(dtype=jnp.bfloat16, **TRAIN_MODEL)
    lowered = _lower_train_step(tcfg, optax.adamw(3e-4))
    n_calls = lowered.as_text().count("tpu_custom_call")
    check(n_calls >= 3, f"the lowered train step holds {n_calls} Pallas "
          f"custom calls, expected the flash forward, dq and dk/dv")

    # -- serving widths: prefill_chunk then decode_step through the paged
    # cache against forward() on the same tokens. Both compute in bf16
    # but by different routes (flash kernel vs gathered paged attention,
    # 16 layers deep), and logits leave the head matmul rounded to bf16:
    # at |logit| in [4, 8) one bf16 step is 2^-5 = 0.031. Tolerance: 0.15
    # absolute at the worst of 2 x 128256 logits (five steps; measured
    # 0.079 on the v5e) and 0.02 on average (measured 0.013).
    cfg = _serve_config().model
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens, tables, last = _paged_probe()  # S = 256: forward() takes
    s_len = tokens.shape[1]                # the flash kernel
    want = jax.jit(lambda p, t: forward(cfg, p, t))(params, tokens)
    cache = init_kv_cache(cfg, 64, SERVE_ENGINE["block_size"])
    got_p, cache = jax.jit(lambda p, c, t, bt: prefill_chunk(
        cfg, p, c, t, jnp.zeros((2,), jnp.int32), last, bt))(
        params, cache, tokens, tables)
    got_d, cache = jax.jit(lambda p, c, t, bt: decode_step(
        cfg, p, c, t, last, bt))(params, cache, tokens[:, -1], tables)
    for name, got, pos in (("prefill", got_p, s_len - 2),
                           ("decode", got_d, s_len - 1)):
        diff = np.abs(np.asarray(got) - np.asarray(want[:, pos]))
        check(np.isfinite(diff).all() and diff.max() < LOGIT_MAX_TOL
              and diff.mean() < LOGIT_MEAN_TOL,
              f"{name} logits differ from forward(): max {diff.max():.4f}"
              f", mean {diff.mean():.5f}")
        worst[f"{name}_logit_max"] = float(diff.max())
        worst[f"{name}_logit_mean"] = float(diff.mean())

    done = _device_record()
    return {**done, "setup_s": round(setup_s, 2),
            "compile_s": done["compile_seconds"],
            "train_step_custom_calls": n_calls,
            "worst_error": {k: round(v, 5) for k, v in worst.items()}}


def _train_step(cfg, opt):
    import jax
    import optax

    from ray_tpu.models import loss_fn

    @jax.jit
    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, targets))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step


def _lower_train_step(cfg, opt, sharding=None):
    """Lower (not compile) the train step on abstract arguments."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import init_params

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    opt_state = jax.eval_shape(opt.init, params)
    batch = jax.ShapeDtypeStruct((TRAIN_BATCH, TRAIN_SEQ), jnp.int32,
                                 sharding=sharding)
    return _train_step(cfg, opt).lower(
        abstract(params), abstract(opt_state), batch, batch)


def phase_serve(tp: int = 1, replicas: int = 1) -> dict:
    """serve.run(build_llm_app(...)) in default process mode: the replica
    process owns the chip(s), the driver initialises no backend."""
    t0 = time.perf_counter()
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app

    worker = ray_tpu.init()
    check(worker.worker_mode == "process",
          f"worker_mode is {worker.worker_mode!r}: the native build failed "
          f"and the runtime degraded to threads; this phase tests "
          f"process mode")
    chips = int(worker.resource_pool.total.get("TPU", 0))
    check(chips >= tp * replicas,
          f"no TPU: ray_tpu.init() counted {chips} chip(s) from the device "
          f"nodes, this phase needs {tp * replicas}")
    try:
        handle = serve.run(build_llm_app(
            _serve_config(tp), num_replicas=replicas,
            ray_actor_options={"num_tpus": tp}))
        stats0 = _all_replica_stats(handle, replicas)
        setup_s = time.perf_counter() - t0
        outs = _stream_all(handle, _serve_requests())
        stats = _all_replica_stats(handle, replicas)
        pair = _check_streams(outs, stats, tp)
        check(_driver_backends() == [],
              f"the driver initialised JAX backends {_driver_backends()}")
        for st in stats:
            check(st["device_count"] == tp,
                  f"a replica sees {st['device_count']} devices, not {tp}")
        check(len({st["visible_chips"] for st in stats}) == replicas,
              "replicas share a chip: "
              f"{[st['visible_chips'] for st in stats]}")
        record = {k: stats[0][k] for k in (
            "platform", "device_kind", "device_count", "jax_version")}
        record.update(
            setup_s=round(setup_s, 2),
            compile_s=round(max(st["compile_seconds"] for st in stats), 2),
            compilations=max(st["compilations"] for st in stats),
            setup_compilations=max(st["compilations"] for st in stats0),
            replicas=[{k: st[k] for k in ("pid", "visible_chips",
                                          "generated_tokens",
                                          "prefill_tokens_saved")}
                      for st in stats],
            driver_backends=_driver_backends(),
            chips_counted=chips, holders=worker.chips.holders(), **pair)
        if tp > 1:
            # Every device holds bytes, and none ever held as much as
            # the whole f32 model: params and pool are born sharded.
            used = stats[0]["device_bytes_in_use"]
            peak = stats[0]["device_peak_bytes"]
            whole = 4 * _param_count(SERVE_MODEL)
            record.update(device_bytes_in_use=used, device_peak_bytes=peak)
            check(len(used) == tp and all(used),
                  f"bytes in use by device: {used}")
            check(all(b < whole for b in peak),
                  f"a device held as much as the whole f32 model "
                  f"({whole} bytes): peaks {peak}")
        return record
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def _param_count(m: dict) -> int:
    hd = m["d_model"] // m["n_heads"]
    per_layer = (2 * m["d_model"]
                 + m["d_model"] * hd * (2 * m["n_heads"] + 2 * m["n_kv_heads"])
                 + 3 * m["d_model"] * m["d_ff"])
    return (2 * m["vocab_size"] * m["d_model"] + m["d_model"]
            + m["n_layers"] * per_layer)


def _train_loop(config: dict) -> None:
    """train_loop_per_worker: runs in the TrainWorker process."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models import TransformerConfig, init_params

    ctx = train.get_context()
    ctx.get_device_info()  # compilations are counted from here on
    cfg = TransformerConfig(dtype=jnp.bfloat16, **config["model"])
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.adamw(3e-4)
    opt_state = opt.init(params)
    shape = (config["batch"], config["seq"])
    tokens = jax.random.randint(jax.random.PRNGKey(1), shape, 0,
                                cfg.vocab_size)
    targets = jax.random.randint(jax.random.PRNGKey(2), shape, 0,
                                 cfg.vocab_size)
    step = _train_step(cfg, opt)
    for i in range(config["steps"]):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        train.report({"step": i, "loss": float(loss),
                      "device": ctx.get_device_info()})


def phase_train() -> dict:
    """JaxTrainer.fit(): one TPU worker, six AdamW steps on one fixed
    batch of the 201M model."""
    t0 = time.perf_counter()
    import ray_tpu
    from ray_tpu.train import JaxTrainer, ScalingConfig

    worker = ray_tpu.init()
    check(worker.worker_mode == "process",
          f"worker_mode is {worker.worker_mode!r}, this phase tests "
          f"process mode")
    try:
        result = JaxTrainer(
            _train_loop,
            train_loop_config={"model": TRAIN_MODEL, "batch": TRAIN_BATCH,
                               "seq": TRAIN_SEQ, "steps": TRAIN_STEPS},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        ).fit()
        wall_s = time.perf_counter() - t0
        hist = result.metrics_history
        check(len(hist) == TRAIN_STEPS,
              f"{len(hist)} of {TRAIN_STEPS} steps reported")
        losses = [h["loss"] for h in hist]
        last = hist[-1]["device"]
        _require_chip(last, "train worker")
        check(all(math.isfinite(l) for l in losses),
              f"a loss is not finite: {losses}")
        check(losses[-1] < losses[0],
              f"the loss did not fall on one fixed batch: {losses}")
        compiles = [h["device"]["compilations"] for h in hist]
        check(compiles[-1] == compiles[1],
              f"compilation after step 2: counts by step {compiles}")
        check(_driver_backends() == [],
              f"the driver initialised JAX backends {_driver_backends()}")
        return {**{k: last[k] for k in ("platform", "device_kind",
                                        "device_count", "jax_version")},
                "setup_s": round(wall_s, 2),
                "compile_s": last["compile_seconds"],
                "compilations_by_step": compiles,
                "losses": [round(l, 4) for l in losses],
                "driver_backends": _driver_backends()}
    finally:
        ray_tpu.shutdown()


def phase_dag() -> dict:
    """chain (1k) and fanout (10k) through experimental_compile(
    backend="jax") in this process, equal to the interpreted DAG."""
    t0 = time.perf_counter()
    import numpy as np

    import ray_tpu
    from ray_tpu.dag import InputNode, reduce_tree

    info = _device_record()
    _require_chip(info, "dag")
    # The interpreted reference runs 11k tasks through the task plane;
    # threads, because this process holds the chip and the tasks are
    # host arithmetic.
    worker = ray_tpu.init(worker_mode="thread", num_tpus=0)
    check(worker.worker_mode == "thread", "asked for thread mode")

    @ray_tpu.remote
    def noop(x):
        return x

    @ray_tpu.remote
    def combine(*xs):
        out = xs[0]
        for x in xs[1:]:
            out = out + x
        return out

    try:
        with InputNode() as inp:
            chain = inp
            for _ in range(1000):
                chain = noop.bind(chain)
        with InputNode() as inp:
            fanout = reduce_tree(
                combine, [noop.bind(inp) for _ in range(10_000)], arity=4)
        setup_s = time.perf_counter() - t0
        got = {}
        for name, node in (("chain_1k", chain), ("fanout_10k", fanout)):
            want = float(ray_tpu.get(node.execute(0.5)))
            compiled = node.experimental_compile(backend="jax")
            out = [float(np.asarray(compiled.execute(0.5).get()))
                   for _ in range(3)]
            check(all(o == want for o in out),
                  f"{name}: compiled {out} != interpreted {want}")
            got[name] = want
        done = _device_record()
        return {**done, "setup_s": round(setup_s, 2),
                "compile_s": done["compile_seconds"], "results": got}
    finally:
        ray_tpu.shutdown()


def phase_tp4_numerics() -> dict:
    """The engine's own TP-4 programs against its one-chip programs on
    the same weights and tokens, in this process (it sees four chips).

    Greedy tokens are the wrong yardstick at these widths: the head
    matmul leaves logits rounded to bf16 (one step is 2^-5 at the top
    logit of a 128256-wide seeded head), the all-reduce sums in another
    order, and an argmax flips within a few tokens, after which the
    streams part for good. Measured on the v5e 2x2 host (PR 21): logits
    differ by 0.080 at most and 0.014 on average — as much as two
    attention algorithms differ on one chip — and four greedy streams
    of 32 tokens stayed equal for 1, 22, 32 and 1 tokens. So the logits
    are held to the tolerance of the numerics phase, the argmax wherever
    the one-chip top-2 gap is wider than twice the error seen, and the
    streams are recorded."""
    t0 = time.perf_counter()
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import InferenceEngine

    info = _device_record()
    _require_chip(info, "tp4_numerics")
    check(info["device_count"] == 4,
          f"this phase needs 4 devices, JAX sees {info['device_count']}")
    small = dict(num_blocks=256)
    one = InferenceEngine(dataclasses.replace(_serve_config(1), **small))
    four = InferenceEngine(dataclasses.replace(_serve_config(4), **small))
    setup_s = time.perf_counter() - t0
    # Recorded, not required (see above): greedy streams of the two.
    prompts = [r["prompt"] for r in _serve_requests()[2:6]]
    streams = {name: [list(eng.generate(p, max_new_tokens=SERVE_NEW_TOKENS))
                      for p in prompts]
               for name, eng in (("one", one), ("four", four))}
    agree = [next((i for i, (a, b) in enumerate(zip(x, y)) if a != b),
                  len(x)) for x, y in zip(streams["one"], streams["four"])]
    tokens, tables, last = _paged_probe()
    logits = {}
    for name, eng in (("one", one), ("four", four)):
        # The engine's own jitted programs (they donate the pool).
        got_p, eng.cache.data = eng._prefill_chunk(
            eng.params, eng.cache.data, tokens, jnp.zeros((2,), jnp.int32),
            last, tables)
        got_d, eng.cache.data = eng._decode(
            eng.params, eng.cache.data, tokens[:, -1], last, tables)
        logits[name] = (np.asarray(got_p), np.asarray(got_d))
    worst = {}
    for i, step in enumerate(("prefill", "decode")):
        a, b = logits["one"][i], logits["four"][i]
        diff = np.abs(a - b)
        check(np.isfinite(diff).all() and diff.max() < LOGIT_MAX_TOL
              and diff.mean() < LOGIT_MEAN_TOL,
              f"TP-4 {step} logits differ from one chip: max "
              f"{diff.max():.4f}, mean {diff.mean():.5f}")
        top2 = np.sort(a, axis=-1)[:, -2:]
        decisive = (top2[:, 1] - top2[:, 0]) > 2 * diff.max()
        check((a.argmax(-1) == b.argmax(-1))[decisive].all(),
              f"TP-4 {step} argmax differs where the one-chip top-2 gap "
              f"is decisive")
        worst[f"{step}_logit_max"] = round(float(diff.max()), 5)
        worst[f"{step}_logit_mean"] = round(float(diff.mean()), 6)
        worst[f"{step}_decisive_rows"] = int(decisive.sum())
    one.shutdown()
    four.shutdown()
    done = _device_record()
    check(all(done["device_bytes_in_use"]),
          f"bytes in use by device: {done['device_bytes_in_use']}")
    return {**done, "setup_s": round(setup_s, 2),
            "compile_s": done["compile_seconds"], "worst_error": worst,
            "greedy_tokens_equal_until": agree}


def phase_spmd_train() -> dict:
    """Three steps of make_spmd_train_step on a real dp2 x tp2 mesh."""
    t0 = time.perf_counter()
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import (
        TransformerConfig,
        init_params,
        make_spmd_train_step,
    )
    from ray_tpu.parallel import MeshConfig, make_mesh

    info = _device_record()
    _require_chip(info, "spmd_train")
    check(info["device_count"] == 4,
          f"this phase needs 4 devices, JAX sees {info['device_count']}")
    mesh = make_mesh(MeshConfig(dp=2, tp=2))
    cfg = TransformerConfig(dtype=jnp.bfloat16, **TRAIN_MODEL)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.adamw(3e-4)
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (TRAIN_BATCH, TRAIN_SEQ), 0, cfg.vocab_size)
    step, _, _ = make_spmd_train_step(cfg, mesh, params, optimizer=opt,
                                      n_microbatches=1)
    setup_s = time.perf_counter() - t0
    opt_state = opt.init(params)
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens, tokens)
        losses.append(float(loss))
    check(all(math.isfinite(l) for l in losses)
          and losses[-1] < losses[0],
          f"dp2 x tp2 losses not finite and falling: {losses}")
    done = _device_record()
    check(all(done["device_bytes_in_use"]),
          f"bytes in use by device: {done['device_bytes_in_use']}")
    return {**done, "setup_s": round(setup_s, 2),
            "compile_s": done["compile_seconds"],
            "losses": [round(l, 4) for l in losses]}


PHASE_FNS = {
    "devices": phase_devices,
    "numerics": phase_numerics,
    "serve": phase_serve,
    "train": phase_train,
    "dag": phase_dag,
    "serve_tp4": lambda: phase_serve(tp=4),
    "tp4_numerics": phase_tp4_numerics,
    "spmd_train": phase_spmd_train,
    "replicas4": lambda: phase_serve(replicas=4),
}


# ------------------------------------------------------------------ parent
def _run_child(phase: str) -> dict:
    """Run one phase in its own process group, with a timeout; kill
    whatever it leaves behind. Returns its JSON record."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phase],
        stdout=subprocess.PIPE, text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=PHASE_TIMEOUT_S[phase])
    except subprocess.TimeoutExpired:
        out = None
    finally:
        # Kill what the phase left behind and wait until it is gone: a
        # killed process that held chips takes seconds to let go of them,
        # and the next phase opens the chip at once.
        deadline = time.monotonic() + 30
        try:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
            while time.monotonic() < deadline:
                os.killpg(proc.pid, 0)
                time.sleep(0.2)
        except ProcessLookupError:
            pass
        proc.wait()
    if out is None:
        raise SmokeFailure(
            f"phase {phase} ran past {PHASE_TIMEOUT_S[phase]}s; killed")
    lines = [ln for ln in out.splitlines() if ln.strip()]
    for ln in lines[:-1]:
        print(ln)
    if proc.returncode != 0 or not lines:
        if lines:
            print(lines[-1])
        raise SmokeFailure(
            f"phase {phase} exited with code {proc.returncode}")
    record = json.loads(lines[-1])
    check(record.get("phase") == phase and record.get("ok") is True,
          f"phase {phase} printed no passing record")
    print(lines[-1], flush=True)
    return record


def _child_main(phase: str) -> int:
    os.makedirs(OUT_DIR, exist_ok=True)
    t0 = time.perf_counter()
    try:
        record = PHASE_FNS[phase]()
    except SmokeFailure as exc:
        print(f"chip_smoke: phase {phase} FAILED: {exc}", file=sys.stderr)
        return 1
    record = {"phase": phase, "ok": True, **record,
              "wall_s": round(time.perf_counter() - t0, 2)}
    line = json.dumps(record)
    with open(os.path.join(OUT_DIR, f"{phase}.json"), "w") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--only", choices=sorted(PHASE_FNS),
                    help="run one phase (after `devices`)")
    ap.add_argument("--phase", choices=sorted(PHASE_FNS),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:
        return _child_main(args.phase)

    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        print("chip_smoke: no TPU — JAX_PLATFORMS=cpu pins JAX to the CPU; "
              "this check only passes on a chip", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(HERE, "ray_tpu")):
        print("chip_smoke: the ray_tpu package is not next to this script",
              file=sys.stderr)
        return 2
    phases = PHASES_4CHIP if args.chips == 4 else PHASES
    if args.only:
        phases = ("devices", args.only) if args.only != "devices" \
            else ("devices",)
    t0 = time.perf_counter()
    try:
        records = [_run_child(p) for p in phases]
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    if args.chips != 4 and not args.only:
        print(json.dumps({"phase": "four_chip", "skipped":
                          "run `python3 chip_smoke.py --chips 4` on a "
                          "four-chip host: " + ", ".join(PHASES_4CHIP[1:])}))
    dev = records[0]
    if dev["device_count"] != args.chips and not args.only:
        print(f"chip_smoke: FAILED: --chips {args.chips} but JAX sees "
              f"{dev['device_count']} device(s)", file=sys.stderr)
        return 1
    print(f"chip_smoke: {len(records)} phases passed in "
          f"{time.perf_counter() - t0:.0f}s", file=sys.stderr)
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
