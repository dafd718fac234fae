"""The Ling family's toy cell through the driver on the CPU: the contract's
last line, every one of the cell's per-layer readers, the balancing pass
through the group-limited choice, and what ``correct`` refuses.

The configuration is a toy of the tests' own (``data/configs/
tiny-ling3.json`` under ``data/manifest-ling3.json``, which names the same
per-layer metrics as the benchmark's cell), never a benchmark
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
MANIFEST = os.path.join(HERE, "data", "manifest-ling3.json")
CELL = "tiny-ling3.tiny-steps"
REAL = "ling3-flash-train.seq4k"
SEED = 2 ** 31 + 93


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
    assert toy["config"]["family"] == real["config"]["family"] == "ling3"
    keys = set(harness.run_model(toy["config"]))
    assert keys <= set(harness.run_model(real["config"]))


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
                "train.moe_load_max_over_mean.ling3",
                "train.seg.unattributed_share.ling3"} <= names
        assert "kernel.kda_roofline.ling3" not in names
        assert line["metrics"]["train.moe_load_max_over_mean.ling3"][
            "value"] >= 1.0
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_the_join_gives_the_new_scopes_their_segments():
    """``segments.compiled_text`` builds the step as the family does; of
    its text the join gives the KDA layers' scan to ``seg.kda_core``, their
    surroundings to ``seg.kda_proj`` and the shared expert to
    ``seg.moe_shared``, forward and backward."""
    loaded = harness.load_cell(CELL, MANIFEST)
    table = segments.attribute(segments.compiled_text(loaded),
                               *segments.vocabulary())
    ways = {}
    for row in table.values():
        ways.setdefault(row["segment"], set()).add(row["way"])
    for seg in ("seg.kda_proj", "seg.kda_core", "seg.moe_shared",
                "seg.attn_proj", "seg.attn_core", "seg.moe_route",
                "seg.moe_experts", "seg.mlp", "seg.head_loss"):
        assert {"forward", "backward"} <= ways[seg] | {"both"} \
            or "both" in ways[seg], (seg, ways.get(seg))
    assert "seg.conv" not in ways and segments.UPDATE in ways


def _ops(**counts):
    """(path, decides) operations: ``seg_mlp=3`` is three forward
    operations under seg.mlp, ``update=2`` two of the optimizer's,
    ``seg_mlp_dot=1`` one matmul under seg.mlp."""
    ops = []
    for name, n in counts.items():
        decides = name.endswith("_dot")
        scope = name[:-4] if decides else name
        scope = scope.replace("seg_", "seg.")
        path = "jit(step)/mul" if scope == "update" \
            else f"jit(step)/jvp({scope})/mul"
        ops += [(path, decides)] * n
    return ops


@pytest.mark.parametrize("ops,want", [
    # an AdamW pass fused with the end of its gradient and a stray scalar
    (_ops(update=25, seg_head_loss=1), "update"),
    # the embedding's scatter-add with the residual stream's last add
    (_ops(seg_embed=13, seg_kda_proj=2), "seg.embed"),
    (_ops(seg_kda_core=15, seg_kda_proj=10), "unattributed"),   # too even
    (_ops(seg_mlp=8, seg_embed=4), "seg.mlp"),                  # two thirds
    # a matmul decides as before, and two that disagree leave it open
    (_ops(update=25, seg_head_loss_dot=1), "seg.head_loss"),
    (_ops(seg_mlp_dot=1, seg_embed_dot=1, seg_mlp=9), "unattributed"),
    (_ops(seg_mlp=3), "seg.mlp"), (_ops(update=2), "update")],
    ids=["adamw", "scatter", "even", "two-thirds", "matmul", "matmuls",
         "one-class", "update"])
def test_the_familys_join_places_a_fusion_by_most_of_its_operations(ops, want):
    """``families/ling3/join.py``: where the accepted join finds several
    classes and nothing that decides, the class of two thirds of the
    operations; everything else as the accepted join has it."""
    family = harness.family(harness.load_cell(REAL)["paths"], "ling3")
    names = segments.vocabulary()[0]
    got, _way = family.join.classify(ops, names)
    assert got == want
    plain, _ = segments.classify(ops, names)
    assert plain == want or plain == segments.UNATTRIBUTED


def test_the_familys_join_only_adds_to_the_accepted_one():
    """Of the toy's compiled step: what the accepted join placed by an
    instruction's own names stays where it was, and fewer instructions
    are left with nobody."""
    loaded = harness.load_cell(CELL, MANIFEST)
    family = harness.family(loaded["paths"], "ling3")
    text, names = segments.compiled_text(loaded), segments.vocabulary()
    plain = segments.attribute(text, *names)
    mine = family.join.attribute(text, *names)
    assert set(mine) == set(plain)
    nobody = lambda table: sum(r["segment"] == segments.UNATTRIBUTED
                               for r in table.values())
    assert nobody(mine) < nobody(plain)
    for name, row in plain.items():
        if row["named"] and row["segment"] != segments.UNATTRIBUTED:
            assert mine[name]["segment"] == row["segment"], name


def test_the_kda_roofline_reads_the_segment_under_the_scope():
    """The reader divides the family's least time by the device time of
    ``seg.kda_core`` in the family's join; it returns nothing where that is
    no time or the family has no such cost."""
    from perfbench import flops

    loaded = harness.load_cell(REAL)
    read = harness.reader(loaded["paths"], "kernel.kda_roofline.ling3")
    family = harness.family(loaded["paths"], "ling3")
    joined = {"segment": {"seg.kda_core": 0.0105}, "way": {}, "kernel": {},
              "busy_s": 0.1}
    ctx = {"family": family, "model": harness.run_model(loaded["config"]),
           "step_cfg": loaded["config"]["step"], "flops": flops,
           "peak": harness.peak("TPU v5 lite"), family.join.KEY: joined}
    cost = family.kda_train_cost(ctx["model"], 1, 4096)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert least == pytest.approx(cost["bytes"] / 819e9)       # the bytes
    assert read(ctx) == pytest.approx(100 * least / 0.0105)
    assert 5 < read(ctx) < 15
    assert read({**ctx, family.join.KEY: dict(joined, segment={})}) is None
    assert read(dict(ctx, family=object())) is None
    assert read({**ctx, family.join.KEY: None}) is None


def test_the_bias_is_balanced_through_the_group_limited_choice():
    loaded = harness.load_cell(CELL, MANIFEST)
    model = harness.run_model(loaded["config"])
    family = harness.family(loaded["paths"], "ling3")
    weights, ref = family.weights, family.reference
    key = harness.seed_key(SEED)
    params = family.make_params(model, SEED)
    rule = model["expert_bias"]
    bias = {k: np.asarray(v["expert_bias"])
            for k, v in params["layers"].items() if "expert_bias" in v}
    assert set(bias) == {"kda_moe", "mla_moe"}
    assert all(np.abs(b).max() > 0 for b in bias.values())
    # one layer again by hand: the loads the balanced bias gives, counted
    # through the reference's own choice, meet the rule's stop
    tokens, _ = family.batch_of(key, 0, 1, rule["seq_len"],
                                model["vocab_size"])
    x = params["embed"][tokens[0]]
    first = jax.tree.map(lambda a: a[0], params["layers"]["kda_dense"])
    x = ref.layer(model, "kda_dense", first, x, ref.mm_highest)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["kda_moe"])
    x = ref.operator(model, "kda_moe", lp, x, ref.mm_highest)
    z = ref.rms_norm(x, lp["mlp_norm"], model["rms_norm_eps"])
    scores = ref.router_scores(lp, z, ref.mm_highest)
    e, k = model["router_experts"], model["num_experts_per_tok"]
    loads = lambda b: np.bincount(np.asarray(
        ref.choose(model, scores + b)).ravel(), minlength=e)
    mean = rule["seq_len"] * k / e
    assert loads(lp["expert_bias"]).max() <= rule["max_over_mean"] * mean
    assert loads(0.0).max() > rule["max_over_mean"] * mean
    # the choice the loads are counted by keeps to the kept groups
    chosen = np.asarray(ref.choose(model, scores + lp["expert_bias"]))
    per = e // model["n_group"]
    assert all(len({c // per for c in row}) <= model["topk_group"]
               for row in chosen)
    # a second tree of the seed is the first, bias and all
    again = weights.make_params(model, SEED)
    np.testing.assert_array_equal(
        again["layers"]["kda_moe"]["expert_bias"], bias["kda_moe"])


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
    family = harness.family(loaded["paths"], "ling3")
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


@pytest.mark.parametrize("fault,expect", [
    ("none", True), ("no_shared_expert", False), ("no_decay", False),
    ("half_batch", False)])
def test_what_the_cells_limits_pass_and_refuse(control, fault, expect):
    """A sound program reads true at the real cell's limits; one that
    leaves the shared expert out, one whose KDA layers do not decay
    (alpha = 1) and one trained on the first half of each sequence read
    false. The last keeps Adam's change (a direction) and is refused by the
    first gradient's norm alone. On the chip the same were read at the
    cell's size (PERF.md section 6, PR 39)."""
    from ray_tpu.models import loss_fn

    family, model, hp, cfg, want, limits = control
    cfg = {"no_shared_expert": dataclasses.replace(cfg, shared_d_ff=0),
           "no_decay": dataclasses.replace(cfg, kda_gate_floor=0.0)
           }.get(fault, cfg)
    loss = functools.partial(loss_fn, cfg)
    if fault == "half_batch":
        half = hp["seq_len"] // 2
        loss = lambda p, tokens, targets: loss_fn(
            cfg, p, tokens[:, :half], targets[:, :half])
    ok = _correct(_program_steps(family, model, hp, loss), want, limits)
    assert all(ok.values()) is expect, ok
    if fault == "half_batch":
        assert not ok["grad_norm_gap"]


def test_the_int8_control_is_not_correct(control):
    family, model, hp, _cfg, want, limits = control
    got = train_check.reference_steps(family, model, hp, SEED, 3, mm="int8",
                                      log=lambda *_: None)
    assert not all(_correct(got, want, limits).values())
