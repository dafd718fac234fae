"""One description of a layer kind (``models/transformer.py:_kind_leaves``)
gives the parameters, their specs and the manual step's specs, and one
layer runs every stack: a uniform stack is a pattern of one kind.

The literal tables below are the ones ``param_specs`` and
``_stage_params_spec`` held before they became projections of the leaves'
roles, and the sums the flat layout's draws at the commit before that: a
seed has to mean what it meant to serving's weights and a trainer's.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.models import TransformerConfig, init_params, loss_fn, param_specs
from ray_tpu.models import transformer
from ray_tpu.parallel.sharding import ShardingRules

PLAIN = TransformerConfig(vocab_size=64, d_model=32, n_layers=3, n_heads=4,
                          n_kv_heads=2, d_ff=48)


@pytest.mark.parametrize("remat", [False, True], ids=["remat0", "remat1"])
def test_a_uniform_stack_is_a_pattern_of_one_kind(remat):
    flat_cfg = dataclasses.replace(PLAIN, dtype=jnp.float32, remat=remat)
    nested_cfg = dataclasses.replace(
        flat_cfg, layer_types=("attention",) * flat_cfg.n_layers)
    flat = init_params(flat_cfg, jax.random.PRNGKey(0))
    nested = dict(flat, layers={"attention_dense": flat["layers"]})
    assert jax.tree.structure(nested) == jax.tree.structure(
        jax.eval_shape(lambda: init_params(nested_cfg,
                                           jax.random.PRNGKey(0))))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    targets = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 64)
    grad = lambda cfg, p: jax.jit(jax.value_and_grad(
        lambda p: loss_fn(cfg, p, tokens, targets)))(p)
    loss, got = grad(flat_cfg, flat)
    want_loss, want = grad(nested_cfg, nested)
    assert float(loss) == float(want_loss)
    want = dict(want, layers=want["layers"]["attention_dense"])
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      jax.tree_util.keystr(path))


def _gspmd_table(st, tp, fs, vocab):
    return {
        "embed": P(vocab, None),
        "layers": {
            "attn_norm": P(st, None),
            "wq": P(st, fs, tp), "wk": P(st, fs, tp), "wv": P(st, fs, tp),
            "wo": P(st, tp, fs),
            "mlp_norm": P(st, None),
            "w_gate": P(st, fs, tp), "w_up": P(st, fs, tp),
            "w_down": P(st, tp, fs),
        },
        "final_norm": P(None),
        "lm_head": P(fs, vocab),
    }


TP_RULES = ShardingRules(batch=None, sequence=None, mlp="model",
                         heads="model", kv_heads="model", vocab="model",
                         stage=None, fsdp_shard=None)
STAGE_TABLE = {
    "attn_norm": P("pp", None),
    "wq": P("pp", None, "tp"), "wk": P("pp", None, "tp"),
    "wv": P("pp", None, "tp"), "wo": P("pp", "tp", None),
    "mlp_norm": P("pp", None),
    "w_gate": P("pp", None, "tp"), "w_up": P("pp", None, "tp"),
    "w_down": P("pp", "tp", None),
}


@pytest.mark.parametrize("got,want", [
    (lambda: param_specs(PLAIN), _gspmd_table("pp", "tp", "fsdp", "tp")),
    (lambda: param_specs(PLAIN, TP_RULES),
     _gspmd_table(None, "model", None, "model")),
    (lambda: transformer._stage_params_spec(PLAIN), STAGE_TABLE),
], ids=["default-rules", "tensor-parallel-rules", "manual-step"])
def test_specs_come_from_the_leaves(got, want):
    assert got() == want


def test_a_kinds_stack_of_several_runs_takes_no_stage_axis():
    cfg = dataclasses.replace(
        PLAIN, layer_types=("conv", "attention", "conv"), router_experts=4,
        num_dense_layers=1)
    specs = param_specs(cfg)["layers"]
    assert sorted(specs) == ["attention_moe", "conv_dense", "conv_moe"]
    assert specs["conv_moe"]["conv_in"] == P(None, "fsdp", "tp")
    assert specs["attention_moe"]["e_down"] == P(None, "ep", "tp", None)
    assert specs["attention_moe"]["router"] == P(None, None, None)


def test_flat_init_draws_are_the_parents():
    p = init_params(PLAIN, jax.random.PRNGKey(0))
    total = lambda a: float(jnp.sum(a))
    assert sorted(p) == ["embed", "final_norm", "layers", "lm_head"]
    assert list(p["layers"]) == ["attn_norm", "wq", "wk", "wv", "wo",
                                 "mlp_norm", "w_gate", "w_up", "w_down"]
    assert total(p["embed"]) == pytest.approx(-1.229291319847107, rel=1e-6)
    assert total(p["lm_head"]) == pytest.approx(0.32113003730773926, rel=1e-6)
    assert total(p["layers"]["wk"]) == pytest.approx(-2.399568796157837,
                                                     rel=1e-6)
    assert total(p["layers"]["w_down"]) == pytest.approx(
        3.7678093910217285, rel=1e-6)
    assert float(p["layers"]["wo"][1].ravel()[5]) == 0.21421997249126434
    assert float(p["layers"]["w_up"][1].ravel()[5]) == -0.14202022552490234


def test_kinds_of_one_seed_do_not_share_draws():
    cfg = dataclasses.replace(
        PLAIN, layer_types=("attention", "conv", "attention"),
        router_experts=4, num_dense_layers=1)
    layers = init_params(cfg, jax.random.PRNGKey(0))["layers"]
    assert sorted(layers) == ["attention_dense", "attention_moe", "conv_moe"]
    wq, wq_moe = layers["attention_dense"]["wq"], layers["attention_moe"]["wq"]
    assert wq.shape == wq_moe.shape == (1, 32, 32)
    assert not np.allclose(np.asarray(wq), np.asarray(wq_moe))
    e = layers["conv_moe"]
    assert not np.allclose(np.asarray(e["e_gate"]), np.asarray(e["e_up"]))


@pytest.mark.parametrize("options,error", [
    (dict(num_experts=4), TypeError),
    (dict(moe_every=2), TypeError),
    (dict(capacity_factor=1.25), TypeError),
    (dict(layer_types=("attention", "conv")), ValueError),
    (dict(layer_types=("attention", "conv", "retention")), ValueError),
    (dict(router_experts=4, experts_held=(1, 1)), ValueError),
    (dict(router_experts=4, experts_per_token=5), ValueError),
    (dict(router_experts=4, router_score="tanh"), ValueError),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else v.__name__)
def test_the_configuration_refuses(options, error):
    with pytest.raises(error):
        dataclasses.replace(PLAIN, **options)
