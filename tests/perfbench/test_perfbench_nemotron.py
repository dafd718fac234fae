"""The Nemotron-H family's toy cell through the driver on the CPU: the
contract's last line, every one of the cell's per-layer readers, the new
scopes' segments, the balancing pass through the router's top-k, and what
``correct`` refuses.

The configuration is a toy of the tests' own (``data/configs/
tiny-nemotron.json`` under ``data/manifest-nemotron.json``, which names the
same per-layer metrics as the benchmark's cell), never a benchmark
configuration.
"""

import dataclasses
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, run, segments, trace_reduce
from perfbench import step as train_step
from perfbench.reference import train_check
from test_perfbench_line import RECORDED, _recorded_planes

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "data", "manifest-nemotron.json")
CELL = "tiny-nemotron.tiny-steps"
REAL = "nemotron3-super-train.seq4k"
SEED = 2 ** 31 + 97
NEW_SEGMENTS = ("seg.mamba_proj", "seg.mamba_core", "seg.moe_latent")


@pytest.fixture(scope="module")
def lines():
    """(the loaded cell, its untraced line, its traced line): the traced
    one lent the recorded chip trace and the v5e's peaks, as
    ``test_perfbench_line.py`` does."""
    loaded = harness.load_cell(CELL, MANIFEST)
    real, real_peak = trace_reduce.load, harness.peak
    real_reduce = trace_reduce.reduce
    trace_reduce.load = _recorded_planes
    trace_reduce.reduce = lambda planes, _window_s: real_reduce(
        planes, RECORDED["span_ns"] / 1e9)
    harness.peak = lambda kind: real_peak("TPU v5 lite")
    try:
        out = [run.run_cell(loaded, SEED, 1.0, trace, time.perf_counter(),
                            allow_cpu=True) for trace in (False, True)]
    finally:
        trace_reduce.load, harness.peak = real, real_peak
        trace_reduce.reduce = real_reduce
    return loaded, out[0], out[1]


def test_the_toy_names_the_cells_own_metrics():
    toy = harness.load_cell(CELL, MANIFEST)
    real = harness.load_cell(REAL)
    assert [m["name"] for m in toy["per_layer"]] \
        == [m["name"] for m in real["per_layer"]]
    assert len(toy["per_layer"]) == 24
    assert toy["config"]["family"] == real["config"]["family"] \
        == "nemotron_h"
    keys = set(harness.run_model(toy["config"]))
    assert keys <= set(harness.run_model(real["config"]))
    assert real["traffic"] == harness.load_cell("mistral7b-train.seq4k")[
        "traffic"]                       # the mix that was there


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_last_line_has_the_contracts_shape(lines, trace):
    loaded, plain, traced = lines
    line = json.loads(json.dumps(traced if trace else plain))
    assert harness.line_faults(line, loaded, trace) == []
    assert list(line)[:5] == list(harness.LINE_KEYS)
    assert list(line)[-1] == "checks" and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["checks"]) == {"grad_norm_gap", "change_norm_gap",
                                   "loss_not_finite"}
    if trace:
        # the recorded trace's instructions are another program's: every
        # segment reader reads 0 there and the unattributed share 100, and
        # a roofline over no time is left out, not infinite
        names = set(line["metrics"])
        assert {"train.step_mfu", "train.step_ms", "device.idle_share.train",
                "train.moe_load_max_over_mean.nemotron3",
                "train.moe_rows_worked_share.nemotron3",
                "kernel.moe_gmm_rows_multiplied_x.nemotron3",
                "train.seg.mamba_core_ms.nemotron3",
                "train.seg.unattributed_share.nemotron3"} <= names
        assert "kernel.ssd_roofline.nemotron3" not in names
        assert line["metrics"]["train.moe_load_max_over_mean.nemotron3"][
            "value"] >= 1.0
        assert line["metrics"]["train.seg.unattributed_share.nemotron3"][
            "value"] > 90.0
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_the_join_gives_the_new_scopes_their_segments():
    """``segments.compiled_text`` builds the step as the family does; of
    its text the join gives the Mamba layers' scan to ``seg.mamba_core``,
    their surroundings to ``seg.mamba_proj`` and the latent's two
    projections to ``seg.moe_latent``, forward and backward, beside the
    segments an attention-only and an expert-only layer keep."""
    loaded = harness.load_cell(CELL, MANIFEST)
    assert set(NEW_SEGMENTS) <= set(segments.vocabulary()[0])
    table = segments.attribute(segments.compiled_text(loaded),
                               *segments.vocabulary())
    ways = {}
    for row in table.values():
        ways.setdefault(row["segment"], set()).add(row["way"])
    for seg in NEW_SEGMENTS + ("seg.attn_proj", "seg.attn_core",
                               "seg.moe_route", "seg.moe_shared",
                               "seg.moe_experts", "seg.head_loss"):
        assert {"forward", "backward"} <= ways[seg] | {"both"} \
            or "both" in ways[seg], (seg, ways.get(seg))
    assert not {"seg.mlp", "seg.conv", "seg.kda_core"} & set(ways)
    assert segments.UPDATE in ways


def test_the_familys_join_is_the_ling_familys_rule():
    """One copy of the rule: this family's readers read through the Ling
    family's join, which only adds to the accepted one."""
    loaded = harness.load_cell(CELL, MANIFEST)
    family = harness.family(loaded["paths"], "nemotron_h")
    rule = family.join._rule({"cell": loaded})
    assert rule is harness.family(loaded["paths"], "ling3").join
    text, names = segments.compiled_text(loaded), segments.vocabulary()
    plain, mine = segments.attribute(text, *names), rule.attribute(text,
                                                                   *names)
    nobody = lambda table: sum(r["segment"] == segments.UNATTRIBUTED
                               for r in table.values())
    assert set(mine) == set(plain) and nobody(mine) < nobody(plain)


def test_the_ssd_roofline_reads_the_segment_under_the_scope():
    """The reader divides the family's least time by the device time of
    ``seg.mamba_core`` in the family's join; it returns nothing where that
    is no time or the family has no such cost. No share over 100 %: the
    least time is under a third of a millisecond, which the bytes bound."""
    from perfbench import flops

    loaded = harness.load_cell(REAL)
    read = harness.reader(loaded["paths"], "kernel.ssd_roofline.nemotron3")
    family = harness.family(loaded["paths"], "nemotron_h")
    key = family.join._rule({"cell": loaded}).KEY
    joined = {"segment": {"seg.mamba_core": 0.010}, "way": {}, "kernel": {},
              "busy_s": 0.1}
    ctx = {"family": family, "model": harness.run_model(loaded["config"]),
           "step_cfg": loaded["config"]["step"], "flops": flops,
           "peak": harness.peak("TPU v5 lite"), "cell": loaded, key: joined}
    cost = family.ssd_train_cost(ctx["model"], 1, 4096)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert least == pytest.approx(cost["bytes"] / 819e9)        # the bytes
    assert 0.25e-3 < cost["flops"] / 197e12 < least < 0.3e-3
    assert read(ctx) == pytest.approx(100 * least / 0.010)
    assert read({**ctx, key: dict(joined, segment={})}) is None
    assert read(dict(ctx, family=object())) is None
    assert read({**ctx, key: None}) is None


def test_the_bias_is_balanced_through_the_routers_top_k():
    loaded = harness.load_cell(CELL, MANIFEST)
    model = harness.run_model(loaded["config"])
    family = harness.family(loaded["paths"], "nemotron_h")
    weights, ref = family.weights, family.reference
    params = family.make_params(model, SEED)
    rule = model["expert_bias"]
    bias = np.asarray(params["layers"]["none_moe"]["expert_bias"])
    assert bias.shape == (5, 32) and np.abs(bias).max(axis=1).min() > 0
    # the first layer again by hand: the loads the balanced bias gives,
    # counted through the reference's own choice, meet the rule's stop
    tokens, _ = family.batch_of(harness.seed_key(SEED), 0, 1,
                                rule["seq_len"], model["vocab_size"])
    x = params["embed"][tokens[0]]
    lp = jax.tree.map(lambda a: a[0], params["layers"]["none_moe"])
    u = ref.rms_norm(x, lp["mlp_norm"], model["layer_norm_epsilon"])
    scores = ref.router_scores(lp, u, ref.mm_highest)
    e, k = model["router_experts"], model["num_experts_per_tok"]
    loads = lambda b: np.bincount(np.asarray(
        ref.choose(model, scores + b)).ravel(), minlength=e)
    mean = rule["seq_len"] * k / e
    assert loads(lp["expert_bias"]).max() <= rule["max_over_mean"] * mean
    assert loads(0.0).max() > rule["max_over_mean"] * mean
    # a second tree of the seed is the first, bias and all
    again = weights.make_params(model, SEED)
    np.testing.assert_array_equal(
        again["layers"]["none_moe"]["expert_bias"], bias)


# ---------------------------------------------------- what correct refuses

def _program_steps(family, model, hp, loss):
    step, init = train_step.adamw_step(
        loss,
        lambda key, index: family.batch_of(key, index, hp["batch"],
                                           hp["seq_len"],
                                           model["vocab_size"]), hp)
    key = harness.seed_key(SEED)
    params = family.make_params(model, SEED)
    opt_state = jax.jit(init)(params)
    got = {"loss": []}
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            params, opt_state, loss = step(params, opt_state, key, i)
            got["loss"].append(float(loss))
            if i == 0:
                got["grad"] = {
                    n: v / (1.0 - hp["b1"]) for n, v in family.leaf_norms(
                        family.first_moment(opt_state)).items()}
    got["change"] = family.change_norms(model, SEED, params)
    return got


@pytest.fixture(scope="module")
def control():
    """The toy at the real cell's limits, in float32 (at 128 tokens one
    top-k choice flipped by bfloat16 is a hundredth of the pairs):
    (family, model, hp, the program's configuration, the reference's three
    steps, the limits)."""
    loaded = harness.load_cell(CELL, MANIFEST)
    model = harness.run_model(loaded["config"])
    family = harness.family(loaded["paths"], "nemotron_h")
    hp = loaded["config"]["step"]
    cfg = dataclasses.replace(family.model_config(model), dtype=jnp.float32)
    want = train_check.reference_steps(family, model, hp, SEED, 3,
                                       log=lambda *_: None)
    limits = harness.load_cell(REAL)["config"]["correct"]
    return family, model, hp, cfg, want, limits


def _correct(got, want, limits):
    checks = train_check.compare(got, want, limits, log=lambda *_: None)
    assert set(checks) == set(limits)
    return {name: c["ok"] for name, c in checks.items()}


def _identity_up(loss_fn, cfg):
    """The loss with the latent's up projection replaced by the identity's
    slice: the experts' weighted sum laid into the first ``moe_latent``
    channels of the stream."""
    def loss(params, tokens, targets):
        up = params["layers"]["none_moe"]["latent_up"]
        eye = jnp.broadcast_to(jnp.eye(*up.shape[1:], dtype=up.dtype),
                               up.shape)
        layers = dict(params["layers"], none_moe=dict(
            params["layers"]["none_moe"], latent_up=eye))
        return loss_fn(cfg, dict(params, layers=layers), tokens, targets)
    return loss


@pytest.mark.parametrize("fault,expect", [
    ("none", True), ("no_shared_expert", False), ("no_decay", False),
    ("identity_up", False), ("half_batch", False)])
def test_what_the_cells_limits_pass_and_refuse(control, fault, expect):
    """A sound program reads true at the real cell's limits; one that
    leaves the shared expert out, one whose Mamba layers do not decay
    (``A`` = 0), one whose latent comes up through the identity's slice and
    one trained on the first half of each sequence read false. None is a
    patch of the program: two are configurations of it, one hands it other
    parameters, one other tokens. On the chip the same were read at the
    cell's size (PERF.md section 6, PR 41)."""
    from ray_tpu.models import loss_fn

    family, model, hp, cfg, want, limits = control
    loss = functools.partial(loss_fn, cfg)
    if fault == "no_shared_expert":
        loss = functools.partial(
            loss_fn, dataclasses.replace(cfg, shared_d_ff=0))
    elif fault == "no_decay":
        # A = -exp(A_log): a rate of exp(-80) is no decay in float32
        loss = lambda p, tokens, targets: loss_fn(
            cfg, dict(p, layers=dict(p["layers"], mamba_none=dict(
                p["layers"]["mamba_none"], mamba_a_log=jnp.full_like(
                    p["layers"]["mamba_none"]["mamba_a_log"], -80.0)))),
            tokens, targets)
    elif fault == "identity_up":
        loss = _identity_up(loss_fn, cfg)
    elif fault == "half_batch":
        half = hp["seq_len"] // 2
        loss = lambda p, tokens, targets: loss_fn(
            cfg, p, tokens[:, :half], targets[:, :half])
    ok = _correct(_program_steps(family, model, hp, loss), want, limits)
    assert all(ok.values()) is expect, ok


def test_the_int8_control_is_not_correct(control):
    family, model, hp, _cfg, want, limits = control
    got = train_check.reference_steps(family, model, hp, SEED, 3, mm="int8",
                                      log=lambda *_: None)
    assert not all(_correct(got, want, limits).values())
