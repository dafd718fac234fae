"""The expert layer as published, on the CPU: the held experts' part of a
layer against the LFM2 family's plain reference (the shares add up, no
token is dropped under any imbalance, top-k selects by score plus bias and
weighs by score), the program's load counter, the selection bias drawn from
the seed and held, the two readers that divide the family's cost and the one
that reads the rows the layer's passes work on. The model and the helpers are
``test_pattern_model.py``'s."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness
from perfbench.reference.numerics import mm_highest
from ray_tpu.models import transformer
from ray_tpu.parallel import moe
from test_pattern_model import FAMILY, MODEL, ROOT, SEED, _batch, _cfg, _rel


def _expert_layer_part(model, params, x):
    """One expert layer's held part of its feed-forward for x [T, D]."""
    cfg = _cfg(model)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["conv_moe"])
    with jax.default_matmul_precision("highest"):
        out, sizes = jax.jit(functools.partial(
            transformer._moe_residual, cfg))(lp, x[None])
    return out[0] - x, sizes


def _reference_layer_part(model, params, x):
    """The same of the reference: its held experts on the normed x."""
    ref = FAMILY.reference
    lp = jax.tree.map(lambda a: a[0], params["layers"]["conv_moe"])
    return jax.jit(lambda lp, x: ref.held_experts(model, lp, ref.rms_norm(
        x, lp["mlp_norm"], model["norm_eps"]), mm_highest))(lp, x)


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts in 4 shares of 2: what the four chips compute of one
    expert layer sums to what the uncut reference gives for it."""
    whole = dict(MODEL, num_experts=8, experts_held=list(range(8)))
    params = FAMILY.make_params(whole, SEED)
    x = jax.random.normal(jax.random.PRNGKey(4), (40, 32), jnp.float32)
    want = _reference_layer_part(whole, params, x)
    total, pairs = jnp.zeros_like(x), 0
    for share in range(4):
        held = [2 * share, 2 * share + 1]
        part = dict(MODEL, num_experts=2, experts_held=held)
        cut = jax.tree.map(lambda a: a, params)
        for name in ("e_gate", "e_up", "e_down"):
            cut["layers"]["conv_moe"][name] = \
                params["layers"]["conv_moe"][name][:, held]
        out, sizes = _expert_layer_part(part, cut, x)
        assert float(jnp.max(jnp.abs(out))) > 0
        total, pairs = total + out, pairs + int(sizes.sum())
    assert pairs == 40 * MODEL["num_experts_per_tok"]
    assert _rel(total, want) < 2e-6


@pytest.mark.parametrize("tokens,held,forced", [
    (48, [1, 4, 6], True),       # three held, one of them takes every token
    (1024, [4], False),          # one held, at about its even share
    (1024, [4], True),           # one held, and every token goes to it
], ids=["small", "even-share", "one-expert-takes-all"])
def test_no_token_is_dropped_under_any_imbalance(tokens, held, forced):
    """A router that sends every token to one held expert first: that
    expert computes all T rows, none is lost to a capacity."""
    model = dict(MODEL, num_experts=len(held), experts_held=held)
    params = FAMILY.make_params(model, SEED)
    stack = params["layers"]["conv_moe"]
    if forced:
        bias = jnp.zeros_like(stack["expert_bias"]).at[:, 4].set(10.0)
        params["layers"]["conv_moe"] = {**stack, "expert_bias": bias}
    x = jax.random.normal(jax.random.PRNGKey(5), (tokens, 32), jnp.float32)
    out, sizes = _expert_layer_part(model, params, x)
    got = int(sizes[held.index(4)])
    assert got == tokens if forced else 0 < got <= 512
    want = _reference_layer_part(model, params, x)
    assert _rel(out, want) < 2e-6
    if forced:
        assert bool((jnp.abs(want).max(axis=-1) > 0).all())   # every token
    # and backward
    cot = jax.random.normal(jax.random.PRNGKey(6), x.shape, jnp.float32)
    got_dx = jax.grad(lambda x: jnp.sum(
        _expert_layer_part(model, params, x)[0] * cot))(x)
    want_dx = jax.grad(lambda x: jnp.sum(
        _reference_layer_part(model, params, x) * cot))(x)
    assert _rel(got_dx, want_dx) < 2e-5


def test_top_k_selects_by_score_plus_bias_and_weighs_by_score():
    h = jnp.eye(4, dtype=jnp.float32)
    logits = jnp.array([[2.0, 1.0, 0.0, -1.0], [0.0, 0.1, 0.2, 0.3],
                        [1.0, 1.0, -3.0, -3.0], [0.5, -0.5, 0.4, -0.4]])
    bias = jnp.array([0.0, 0.0, 0.0, 5.0])
    r = jax.jit(functools.partial(
        moe.route, experts_held=(0, 3), k=2, score="sigmoid",
        norm_topk=True, scale=1.0))(h, logits, bias)
    s = jax.nn.sigmoid(logits)
    # the bias brings expert 3 in everywhere; the other is the best score
    assert sorted(map(sorted, np.asarray(r.experts).tolist())) == sorted(
        [[0, 3], [2, 3], [0, 3], [0, 3]])
    for t, (experts, gates) in enumerate(zip(r.experts, r.gates)):
        chosen = s[t, experts]
        np.testing.assert_allclose(gates, chosen / (chosen.sum() + 1e-6),
                                   rtol=1e-6)       # the bias weighs nothing
    assert np.asarray(r.group_sizes).tolist() == [3, 4]
    assert int(r.held.sum()) == 7 and bool(r.held[:7].all())
    plain = jax.jit(functools.partial(
        moe.route, experts_held=(0, 3), k=2, score="softmax",
        norm_topk=False, scale=2.0))(h, logits, None)
    np.testing.assert_allclose(
        plain.gates[0], 2.0 * jax.nn.softmax(logits[0])[:2], rtol=1e-6)


def test_moe_load_counts_the_pairs_routed_here():
    cfg, params = _cfg(), FAMILY.make_params(MODEL, SEED)
    tokens, _ = _batch()
    load = jax.jit(functools.partial(transformer.moe_load, cfg))(
        params, tokens)
    assert {k: v.shape for k, v in load.items()} == {
        "attention_moe": (1, 3), "conv_moe": (3, 3)}
    whole = dict(MODEL, num_experts=8, experts_held=list(range(8)))
    every = jax.jit(functools.partial(transformer.moe_load, _cfg(whole)))(
        FAMILY.make_params(whole, SEED), tokens)
    for counts in every.values():       # all held: every pair is counted
        assert counts.sum(axis=1).tolist() == [tokens.size * 2] * len(counts)


def test_the_load_reader_reads_the_windows_seed_not_a_shape_pass():
    """The join builds the step from shapes (``eval_shape`` of
    ``make_params`` at seed 0) before the load's reader runs: the reader
    still counts at the seed whose tree was made on the device."""
    hp = {"batch": 2, "seq_len": 24}
    FAMILY.make_params(MODEL, SEED + 1)
    jax.eval_shape(lambda: FAMILY.make_params(MODEL, 0))
    got = FAMILY.moe_load(MODEL, hp)
    tokens, _ = FAMILY.batch_of(harness.seed_key(SEED + 1), 0, 2, 24, 64)
    want = jax.jit(functools.partial(transformer.moe_load, _cfg(
        dtype=jnp.bfloat16)))(FAMILY.make_params(MODEL, SEED + 1), tokens)
    assert {k: v.tolist() for k, v in got.items()} == {
        k: v.tolist() for k, v in want.items()}
    read = harness.reader(["perfbench"], "train.moe_load_max_over_mean.lfm2")
    worst = max(float(row.max() / row.mean())
                for rows in got.values() for row in rows)
    assert read({"family": FAMILY, "model": MODEL, "step_cfg": hp}) \
        == pytest.approx(worst)
    assert read({}) is None
    dense = harness.family(["perfbench"], "dense")
    assert read({"family": dense, "model": MODEL, "step_cfg": hp}) is None


def test_the_experts_roofline_readers_divide_the_familys_cost(monkeypatch):
    """The least time of the grouped products at the expected pairs, over
    the segment's time and over the two kernels' time; nothing where the
    family counts no experts or the step holds no such kernel."""
    from perfbench import flops, segments

    body = harness.load_json(os.path.join(
        ROOT, "perfbench", "configs", "lfm2-24b-a2b-train.json"))
    ctx = {"family": FAMILY, "model": harness.run_model(body),
           "step_cfg": body["step"], "peak": harness.peak("TPU v5 lite"),
           "flops": flops}
    spent = {"moe_gmm": 12.0, "moe_tgmm": 4.0}
    monkeypatch.setattr(segments, "segment_ms", lambda _ctx, seg: {
        "seg.moe_experts": 40.0}.get(seg, 0.0))
    monkeypatch.setattr(segments, "kernel_ms",
                        lambda _ctx, kernel: spent.get(kernel))
    cost = FAMILY.experts_train_cost(ctx["model"], 1, 8192)
    least, bound = flops.roofline_seconds(cost, ctx["peak"])
    assert bound == "compute" and least == pytest.approx(4.709e-3, rel=1e-3)
    whole = harness.reader(["perfbench"], "train.moe_experts_roofline.lfm2")
    kernels = harness.reader(["perfbench"], "kernel.moe_gmm_roofline.lfm2")
    assert whole(ctx) == pytest.approx(100 * least / 40e-3)
    assert kernels(ctx) == pytest.approx(100 * least / 16e-3)
    dense = dict(ctx, family=harness.family(["perfbench"], "dense"))
    assert whole(dense) is None and kernels(dense) is None
    del spent["moe_tgmm"]
    assert kernels(ctx) is None
    monkeypatch.setattr(segments, "segment_ms", lambda _ctx, _seg: None)
    assert whole(ctx) is None


def test_the_rows_worked_reader_reads_the_programs_counter(monkeypatch):
    """Whole tiles over the pairs routed to the held experts, over every
    sorted pair of a layer, mean over the expert layers; nothing where
    the family counts no load or the program has no ``rows_worked``."""
    hp = {"batch": 2, "seq_len": 24}
    FAMILY.make_params(MODEL, SEED + 1)
    read = harness.reader(["perfbench"], "train.moe_rows_worked_share.lfm2")
    ctx = {"family": FAMILY, "model": MODEL, "step_cfg": hp}
    load = FAMILY.moe_load(MODEL, hp)
    layers = [row for rows in load.values() for row in rows]
    assert len(layers) == 4
    tile, pairs = moe.ROW_TILE, 2 * 24 * MODEL["num_experts_per_tok"]
    want = [-(-int(row.sum()) // tile) * tile / pairs for row in layers]
    assert read(ctx) == pytest.approx(100 * sum(want) / 4)
    assert read({}) is None
    dense = harness.family(["perfbench"], "dense")
    assert read(dict(ctx, family=dense)) is None
    monkeypatch.delattr(moe, "rows_worked")     # the parent's program
    assert read(ctx) is None


def test_the_rows_multiplied_reader_reads_the_kernels_counter(monkeypatch):
    """The rows the grouped kernels' visits multiply over the pairs routed
    to the held experts, summed over the expert layers; nothing where the
    family counts no load or the program has no ``rows_multiplied``."""
    from ray_tpu.ops import grouped_matmul

    hp = {"batch": 2, "seq_len": 24}
    FAMILY.make_params(MODEL, SEED + 1)
    name = "kernel.moe_gmm_rows_multiplied_x.lfm2"
    read = harness.reader(["perfbench"], name)
    ctx = {"family": FAMILY, "model": MODEL, "step_cfg": hp}
    layers = [row for rows in FAMILY.moe_load(MODEL, hp).values()
              for row in rows]
    tile, visits = grouped_matmul.TM, 0
    for row in layers:
        ends = np.cumsum(row).astype(int)
        visits += sum(-(-end // tile) - (end - n) // tile
                      for end, n in zip(ends, row.astype(int)) if n)
    routed = sum(int(row.sum()) for row in layers)
    assert read(ctx) == pytest.approx(visits * tile / routed)
    assert read(ctx) >= 1.0
    assert read({}) is None
    dense = harness.family(["perfbench"], "dense")
    assert read(dict(ctx, family=dense)) is None
    cell = harness.load_cell("lfm2-24b-a2b-train.seq8k")
    assert name in [m["name"] for m in cell["per_layer"]]
    assert name not in [m["name"] for m in harness.load_cell(
        "mistral7b-train.seq4k")["per_layer"]]
    monkeypatch.delattr(grouped_matmul, "rows_multiplied")  # the parent's
    assert read(ctx) is None


@pytest.mark.parametrize("seed", [1, 2])
def test_the_bias_is_drawn_from_the_seed_and_no_gradient_reaches_it(seed):
    """``use_expert_bias`` as the cell runs it: normal * ``expert_bias_scale``
    from the seed, selecting and never weighing, so the loss's gradient at
    it is zero in program and reference alike. Nothing balances the loads:
    a seeded router's fullest expert draws well over its share."""
    whole = dict(MODEL, num_experts=8, experts_held=list(range(8)))
    params = FAMILY.make_params(whole, seed)
    bias = np.asarray(params["layers"]["conv_moe"]["expert_bias"])
    assert bias.shape == (3, 8) and 0.005 < bias.std() < 0.04
    other = FAMILY.make_params(whole, seed + 2)
    assert not np.allclose(
        bias, np.asarray(other["layers"]["conv_moe"]["expert_bias"]))
    tokens, targets = FAMILY.batch_of(harness.seed_key(seed), 0, 1, 512, 64)
    with jax.default_matmul_precision("highest"):
        grads = jax.jit(jax.grad(functools.partial(
            transformer.loss_fn, _cfg(whole))))(params, tokens, targets)
        load = jax.jit(functools.partial(
            transformer.moe_load, _cfg(whole)))(params, tokens)
    for kind in ("attention_moe", "conv_moe"):
        assert not np.any(np.asarray(grads["layers"][kind]["expert_bias"]))
        assert np.any(np.asarray(grads["layers"][kind]["router"]))
    worst = max(float(row.max() / row.mean())
                for rows in load.values() for row in rows)
    assert worst > 1.25, worst
