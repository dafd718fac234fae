"""What PR 38 re-issued ``lfm2-24b-a2b-train.seq8k`` with, at sizes a CPU
holds: the selection bias balanced at set-up by the auxiliary-loss-free
rule, and the linear warm-up of the rate, read alike by the program's step
and by the reference's. A configuration that names no warm-up builds the
step it built before, text for text; and with both in place the planted
faults and the int8 control still come out not correct.
"""

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, run
from perfbench import step as train_step
from perfbench.reference import numerics, train_check

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "data", "manifest.json")
CELL = "tiny-lfm2.tiny-steps"
REAL = harness.load_cell("lfm2-24b-a2b-train.seq8k")["config"]
SEED = 2 ** 31 + 38


def _cell(name=CELL):
    loaded = harness.load_cell(name, MANIFEST)
    return loaded, harness.family(loaded["paths"],
                                  loaded["config"]["family"])


def _weights(family):
    """The family's ``weights`` module, as the run's family imported it."""
    import sys
    return sys.modules[family.__name__ + ".weights"]


# --- the balancing pass ----------------------------------------------------

def _seeded_scores(seed, tokens=4096, width=64, d=32):
    """A seeded router of the published width over rows that share a
    component, as a decoder's hidden states do: its loads start uneven."""
    kz, kc, kw = jax.random.split(harness.seed_key(seed), 3)
    z = jax.random.normal(kz, (tokens, d)) + 0.7 * jax.random.normal(kc, (d,))
    w = jax.random.normal(kw, (d, width)) / np.sqrt(d)
    return jax.nn.sigmoid(z @ w)


def _fullest_over_mean(scores, bias, k):
    chosen = np.argsort(-(np.asarray(scores) + np.asarray(bias)), axis=-1)
    loads = np.bincount(chosen[:, :k].ravel(), minlength=scores.shape[1])
    return loads.max() / loads.mean()


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_the_balancing_pass_evens_a_seeded_router_of_64_top_4(seed):
    _, family = _cell()
    rule = REAL["assumed"]["expert_bias"]["run"]
    scores = _seeded_scores(seed)
    assert _fullest_over_mean(scores, np.zeros(64), 4) > 1.4
    bias, ran, fullest = jax.jit(
        lambda s: _weights(family).balance(s, 4, rule))(scores)
    assert 0 < int(ran) < rule["iterations"]
    # counted again by numpy, not by the pass's own count
    assert _fullest_over_mean(scores, bias, 4) <= rule["max_over_mean"]
    assert float(fullest) == pytest.approx(
        _fullest_over_mean(scores, bias, 4))
    # the rule moves every expert by u a time: the bias is a multiple of u
    steps = np.asarray(bias) / rule["u"]
    assert np.abs(steps - np.round(steps)).max() < 1e-2
    assert np.abs(steps).max() <= int(ran) + 1e-2


def test_the_rule_stops_at_its_iterations_where_no_bias_evens_the_loads():
    _, family = _cell()
    # every row the same: top-k gives the same k experts whatever u does
    # in 5 iterations
    scores = jnp.tile(jnp.linspace(0.1, 0.9, 8), (64, 1))
    rule = {"u": 0.001, "max_over_mean": 1.1, "iterations": 5}
    bias, ran, fullest = _weights(family).balance(scores, 2, rule)
    assert int(ran) == 5 and float(fullest) == pytest.approx(4.0)
    assert np.asarray(bias).tolist() == pytest.approx(
        [0.005] * 6 + [-0.005] * 2)


def test_the_balanced_bias_is_the_seeds_and_no_other_leaf_moves():
    loaded, family = _cell()
    weights = _weights(family)
    model = harness.run_model(loaded["config"])
    first = family.make_params(model, SEED)
    weights._BALANCED.clear()                 # made again, not remembered
    again = family.make_params(model, SEED)
    assert len(weights._BALANCED) == 1
    remembered = family.make_params(model, SEED)
    other = family.make_params(model, SEED + 1)
    # the tree a model without the rule gets: the bias drawn, as before
    drawn = family.make_params(
        {**{k: v for k, v in model.items() if k != "expert_bias"},
         "expert_bias_scale": 0.02}, SEED)
    flat = weights.flat
    for name, leaf in flat(first).items():
        assert np.array_equal(leaf, flat(again)[name]), name
        assert np.array_equal(leaf, flat(remembered)[name]), name
        if name.endswith(".expert_bias"):
            assert np.any(np.asarray(leaf))
            assert not np.array_equal(leaf, flat(other)[name])
            assert not np.array_equal(leaf, flat(drawn)[name])
            assert leaf.shape == flat(drawn)[name].shape
        else:
            assert np.array_equal(leaf, flat(drawn)[name]), name
    # the start that the change is measured from is the balanced bias
    zero = family.change_norms(model, SEED, first)
    assert all(abs(v) < 1e-5 for v in zero.values())
    # shapes alone (a compile for a described chip) remember nothing
    weights._BALANCED.clear()
    shapes = jax.eval_shape(lambda: family.make_params(model, SEED))
    assert not weights._BALANCED
    assert jax.tree.map(lambda a: (a.shape, a.dtype), shapes) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), first)


def test_the_balanced_bias_evens_the_loads_of_the_batch_it_was_made_on():
    """Through the family's plain reference, layer by layer as the pass
    walks them: every expert layer's fullest expert of all the router's
    is within the stop."""
    loaded, family = _cell()
    weights = _weights(family)
    model = harness.run_model(loaded["config"])
    rule = model["expert_bias"]
    params = family.make_params(model, SEED)
    _, ran, fullest = jax.jit(lambda p, k: weights.balanced_bias(
        model, p, k))(params, harness.seed_key(SEED))
    # on the balanced tree the pass starts from zero again and gets there
    # again: the same iterations, the same loads
    assert (np.asarray(fullest) <= rule["max_over_mean"] + 1e-6).all()
    assert (np.asarray(ran) < rule["iterations"]).all()
    assert len(ran) == sum(k.endswith("moe")
                           for k in weights.counts.kinds(model))


def test_the_cells_file_states_the_rule_and_the_schedule():
    rule = REAL["assumed"]["expert_bias"]
    assert rule["run"] == {"u": 0.001, "max_over_mean": 1.1,
                           "iterations": 1000, "seq_len": 8192}
    assert "2408.15664" in rule["source"] and "2412.19437" in rule["source"]
    assert "expert_bias_scale" not in REAL["assumed"]
    step = REAL["step"]
    # the rows the bias is balanced on are the step's batch of index 0
    assert step["batch"] == 1 and step["seq_len"] == rule["run"]["seq_len"]
    assert step["warmup_steps"] == 2000 and step["learning_rate"] == 3e-4
    assert "2412.19437" in REAL["assumed"]["optimizer"]
    assert harness.run_model(REAL)["expert_bias"] == rule["run"]
    # the toy of the tests runs under the same limits as the cell
    assert _cell()[0]["config"]["correct"] == REAL["correct"]


# --- the warm-up -------------------------------------------------------------

HP = {**REAL["step"]}


@pytest.mark.parametrize("index", [0, 1, 2, 87, 2000, 2001])
def test_program_and_reference_read_the_same_rate_at_a_step(index):
    assert "warmup_steps" in HP
    schedule = train_step.learning_rate(HP)
    program = float(jax.jit(schedule)(jnp.int32(index)))   # optax's count
    reference = float(jax.jit(lambda c: numerics.learning_rate(HP, c))(
        jnp.float32(index + 1)))                 # the number of the update
    assert program == reference
    want = HP["learning_rate"] * min(1.0, index / HP["warmup_steps"])
    assert program == pytest.approx(want, rel=1e-6)
    if index == 0:
        assert program == 0.0


def test_three_updates_of_the_programs_adamw_are_the_references():
    """optax under the schedule against AdamW written out, on a tree with
    a leaf of ones (a norm) and a leaf of small weights: the same
    parameters to float32's rounding after the warm-up's first steps."""
    import optax

    opt = optax.adamw(train_step.learning_rate(HP), b1=HP["b1"], b2=HP["b2"],
                      eps=HP["eps"], weight_decay=HP["weight_decay"])
    key = harness.seed_key(SEED)
    params = {"norm": jnp.ones((256,)),
              "w": jax.random.normal(key, (64, 64)) * 0.02}
    ours, state = params, opt.init(params)
    theirs = params
    mu = nu = jax.tree.map(jnp.zeros_like, params)
    for i in range(3):
        grads = jax.tree.map(lambda p: jax.random.normal(
            jax.random.fold_in(key, i), p.shape) * 1e-3, params)
        updates, state = opt.update(grads, state, ours)
        ours = optax.apply_updates(ours, updates)
        theirs, mu, nu = numerics.adamw_step(theirs, mu, nu, grads,
                                             jnp.float32(i + 1), HP)
    for name in params:
        moved = np.abs(np.asarray(theirs[name] - params[name])).max()
        assert 1e-7 < moved < 1e-6, (name, moved)      # 1.5e-7 + 3e-7
        assert np.abs(np.asarray(ours[name] - theirs[name])).max() \
            <= 1.2e-7, name                            # an ulp of 1.0


def _parents_adamw_step(loss, batch_of, hp):
    """``perfbench/step.py:adamw_step`` as it stood before PR 38 (commit
    dae6adb), kept here as what a step without a warm-up has to equal."""
    import optax

    from ray_tpu.ops import backend

    opt = optax.adamw(hp["learning_rate"], b1=hp["b1"], b2=hp["b2"],
                      eps=hp["eps"], weight_decay=hp["weight_decay"])

    def step(params, opt_state, key, index):
        tokens, targets = batch_of(key, index)
        value, grads = jax.value_and_grad(
            lambda p: loss(p, tokens, targets))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, value

    donate = () if backend.on_cpu() else (0, 1)
    return jax.jit(step, donate_argnums=donate), opt.init


def _lowered(family, config):
    step, init = family.build_step(config)
    params = jax.eval_shape(lambda: family.make_params(
        harness.run_model(config), 0))
    state = jax.eval_shape(init, params)
    key = jax.eval_shape(lambda: harness.seed_key(0))
    return step.lower(params, state, key, 0).as_text(), state


@pytest.mark.parametrize("cell", ["tiny-train.tiny-steps",
                                  "tiny-tied.tiny-steps"])
def test_a_step_without_a_warm_up_lowers_to_the_parents_text(
        monkeypatch, cell):
    loaded, family = _cell(cell)
    hp = loaded["config"]["step"]
    assert "warmup_steps" not in hp
    assert train_step.learning_rate(hp) is hp["learning_rate"]   # a float
    text, state = _lowered(family, loaded["config"])
    monkeypatch.setattr(train_step, "adamw_step", _parents_adamw_step)
    parents, parents_state = _lowered(family, loaded["config"])
    assert text == parents
    assert jax.tree.structure(state) == jax.tree.structure(parents_state)
    # and the Mistral cell's file names no warm-up either
    mistral = harness.load_cell("mistral7b-train.seq4k")["config"]["step"]
    assert train_step.learning_rate(mistral) is mistral["learning_rate"]


def test_a_step_with_a_warm_up_carries_its_count_and_no_branch():
    loaded, family = _cell()
    text, state = _lowered(family, loaded["config"])
    counts = [leaf for leaf in jax.tree.leaves(state)
              if leaf.dtype == jnp.int32 and leaf.shape == ()]
    assert len(counts) == 2          # Adam's and the schedule's
    assert "stablehlo.case" not in text and "stablehlo.if" not in text


# --- correct, with both in place --------------------------------------------

@pytest.fixture
def float32_family(monkeypatch):
    """The toy's family computing in float32: at 128 tokens and widths of
    32 one top-k choice flipped by bfloat16 moves a held expert's whole
    leaf (tests/test_pattern_model.py), which is the size's and not the
    cell's; the chip reads the bfloat16 program at the cell's size."""
    loaded, family = _cell()
    real = family.model_config
    monkeypatch.setattr(family, "model_config", lambda model:
                        dataclasses.replace(real(model), dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        yield loaded, family


def _run(loaded):
    return run.run_cell(loaded, SEED, 1.0, False, time.perf_counter(),
                        allow_cpu=True)


def _failed(line):
    return sorted(n for n, c in line["checks"].items() if not c["ok"])


def test_a_sound_run_under_balance_and_warm_up_is_correct(float32_family):
    loaded, _ = float32_family
    line = _run(loaded)
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == set(REAL["correct"]) | {"loss_not_finite"}


def test_one_held_expert_skipped_is_not_correct(float32_family, monkeypatch):
    from ray_tpu.parallel import moe

    loaded, _ = float32_family
    real = moe.held_experts

    def skipping(h, routing, e_gate, e_up, e_down):
        keep = jnp.ones((e_down.shape[0], 1, 1), e_down.dtype).at[1].set(0)
        return real(h, routing, e_gate, e_up, e_down * keep)

    monkeypatch.setattr(moe, "held_experts", skipping)
    line = _run(loaded)
    assert line["correct"] is False
    assert _failed(line) == ["change_norm_gap", "grad_norm_gap"]


def test_a_state_left_unchanged_reads_one(float32_family, monkeypatch):
    loaded, family = float32_family
    real = family.build_step

    def build(config):
        step, init = real(config)

        def lazy(params, opt_state, key, index):
            _, _, loss = step(params, opt_state, key, index)
            return params, opt_state, loss
        return lazy, init

    monkeypatch.setattr(family, "build_step", build)
    line = _run(loaded)
    assert line["correct"] is False
    # 1 but for the start made again in another program: to rounding the
    # same, and at the warm-up's rates the reference's change is small
    assert line["checks"]["change_norm_gap"]["value"] == pytest.approx(
        1.0, abs=1e-2)


@pytest.mark.parametrize("seed", [38, 39, 40])
def test_the_int8_control_is_not_correct_under_the_warm_up(seed):
    loaded, family = _cell()
    model = harness.run_model(loaded["config"])
    hp = loaded["config"]["step"]
    quiet = lambda *a: None  # noqa: E731
    want = train_check.reference_steps(family, model, hp, seed, 3, log=quiet)
    control = train_check.reference_steps(family, model, hp, seed, 3,
                                          mm="int8", log=quiet)
    checks = train_check.compare(control, want, REAL["correct"], log=quiet)
    assert not checks["grad_norm_gap"]["ok"]
    assert not checks["change_norm_gap"]["ok"]
