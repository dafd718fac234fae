"""What decides ``correct``, broken on purpose.

Each fault is planted underneath the harness, in the timed path, and a
run that skips only the look for a chip has to come out not correct. The
controls (the reference in int8, the precision below bf16) have to read
above the limits that sound runs keep under, at a size a test can hold.
"""

import os
import time

import jax
import pytest

from perfbench import harness, run, weights
from perfbench.drivers import train as train_driver
from perfbench.reference import train_check

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "data", "manifest.json")
SEED = 2 ** 31 + 50


def _run(cell):
    loaded = harness.load_cell(cell, MANIFEST)
    return run.run_cell(loaded, SEED, 1.0, False, time.perf_counter(),
                        allow_cpu=True)


def _failed(line):
    return sorted(n for n, c in line["checks"].items() if not c["ok"])


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    real = train_driver.build_step

    def build(config):
        step, opt = real(config)

        def lazy(params, opt_state, key, index):
            _, _, loss = step(params, opt_state, key, index)
            return params, opt_state, loss
        return lazy, opt

    monkeypatch.setattr(train_driver, "build_step", build)
    line = _run("tiny-train.tiny-steps")
    assert line["correct"] is False
    assert line["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    real = train_driver.batch_of

    def half(key, index, batch, seq_len, vocab):
        tokens, targets = real(key, index, batch, seq_len, vocab)
        return tokens[: batch // 2], targets[: batch // 2]

    # planted in the program's feed only: the reference is handed the
    # whole batch by the driver's own argument
    real_steps = train_check.reference_steps
    monkeypatch.setattr(train_driver, "batch_of", half)
    monkeypatch.setattr(
        train_check, "reference_steps",
        lambda model, hp, seed, n, _feed, **kw: real_steps(
            model, hp, seed, n, real, **kw))
    line = _run("tiny-train.tiny-steps")
    assert line["correct"] is False and "grad_norm_gap" in _failed(line)


def test_a_sound_run_is_correct():
    line = _run("tiny-train.tiny-steps")
    assert line["correct"] is True, _failed(line)


@pytest.mark.parametrize("seed", [50, 51, 52])
def test_the_int8_control_of_a_training_step_reads_above_the_limit(seed):
    cell = harness.load_cell("tiny-train.tiny-steps", MANIFEST)
    model, hp = train_driver.run_model(cell["config"]), cell["config"]["step"]
    quiet = lambda *a: None  # noqa: E731
    want = train_check.reference_steps(model, hp, seed, 3,
                                       train_driver.batch_of, log=quiet)
    control = train_check.reference_steps(model, hp, seed, 3,
                                          train_driver.batch_of, mm="int8",
                                          log=quiet)
    checks = train_check.compare(control, want, cell["config"]["correct"],
                                 log=quiet)
    assert not checks["grad_norm_gap"]["ok"]
    same = train_check.compare(want, want, cell["config"]["correct"],
                               log=quiet)
    assert all(c["ok"] and c["value"] == 0 for c in same.values())


def test_weights_are_the_programs_tree_and_come_from_the_seed():
    from ray_tpu.models import init_params

    cell = harness.load_cell("tiny-train.tiny-steps", MANIFEST)
    model = train_driver.run_model(cell["config"])
    ours = weights.make_params(model, 2 ** 31 + 9)
    theirs = jax.eval_shape(lambda: init_params(
        train_driver.model_config(model), jax.random.PRNGKey(0)))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape and a.dtype == b.dtype,
        ours, theirs)))
    again = weights.make_params(model, 2 ** 31 + 9)
    other = weights.make_params(model, 2 ** 31 + 10)
    assert bool((ours["lm_head"] == again["lm_head"]).all())
    assert not bool((ours["lm_head"] == other["lm_head"]).all())
    # 2**32 + 9 and 9 differ only above 32 bits: both halves count
    assert not bool((weights.make_params(model, 2 ** 32 + 9)["embed"]
                     == weights.make_params(model, 9)["embed"]).all())
    # made again leaf by leaf inside another program: the same to rounding
    zero = weights.change_norms(model, 2 ** 31 + 9, ours)
    assert all(float(abs(v).max()) < 1e-5 for v in zero.values())
