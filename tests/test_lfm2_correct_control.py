"""What ``correct`` refuses in the LFM2 cell, planted on the CPU at tiny
widths through ``train_check.compare`` with the limits of the cell's own
file: a sound program reads true; the reference computed in int8 (the
precision below the bfloat16 the configuration states) and a program that
skips one of the experts it holds read false. On the chip the same three
were read at the cell's size (PERF.md section 6); this file is where the
next PR reads them again. The model is ``test_pattern_model.py``'s."""

import functools

import jax
import jax.numpy as jnp
import pytest

from perfbench import harness
from perfbench import step as train_step
from perfbench.reference import train_check
from ray_tpu.models import loss_fn
from ray_tpu.parallel import moe
from test_pattern_model import FAMILY, MODEL, _cfg

SEED = 2 ** 31 + 77
LIMITS = harness.load_cell("lfm2-24b-a2b-train.seq8k")["config"]["correct"]
HP = dict(harness.load_cell("lfm2-24b-a2b-train.seq8k")["config"]["step"],
          batch=1, seq_len=64)


def _program_steps():
    """Three steps of the program's ``loss_fn`` under the harness's AdamW,
    read as the train driver reads them. Float32 here: at 64 tokens one
    top-k choice flipped by bfloat16 is a hundredth of the pairs."""
    step, init = train_step.adamw_step(
        functools.partial(loss_fn, _cfg()),
        lambda key, index: FAMILY.batch_of(key, index, HP["batch"],
                                           HP["seq_len"], MODEL["vocab_size"]),
        HP)
    key = harness.seed_key(SEED)
    params = FAMILY.make_params(MODEL, SEED)
    opt_state = jax.jit(init)(params)
    got = {"loss": []}
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            params, opt_state, loss = step(params, opt_state, key, i)
            got["loss"].append(float(loss))
            if i == 0:
                got["grad"] = {
                    n: v / (1.0 - HP["b1"]) for n, v in FAMILY.leaf_norms(
                        FAMILY.first_moment(opt_state)).items()}
    got["change"] = FAMILY.change_norms(MODEL, SEED, params)
    return got


@pytest.fixture(scope="module")
def want():
    return train_check.reference_steps(FAMILY, MODEL, HP, SEED, 3,
                                       log=lambda *_: None)


def _correct(got, want):
    checks = train_check.compare(got, want, LIMITS, log=lambda *_: None)
    assert set(checks) == set(LIMITS)
    return {name: c["ok"] for name, c in checks.items()}


def test_a_sound_program_is_correct(want):
    assert all(_correct(_program_steps(), want).values())


def test_one_held_expert_skipped_is_not_correct(want, monkeypatch):
    real = moe.held_experts

    def skipping(h, routing, e_gate, e_up, e_down):
        keep = jnp.ones((e_down.shape[0], 1, 1), e_down.dtype).at[1].set(0)
        return real(h, routing, e_gate, e_up, e_down * keep)

    monkeypatch.setattr(moe, "held_experts", skipping)
    assert not all(_correct(_program_steps(), want).values())


def test_the_int8_control_is_not_correct(want):
    control = train_check.reference_steps(FAMILY, MODEL, HP, SEED, 3,
                                          mm="int8", log=lambda *_: None)
    assert not all(_correct(control, want).values())
