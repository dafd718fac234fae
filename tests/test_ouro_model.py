"""A looped stack with sandwich norms, an exit gate after every pass and the
exit objective in the training body, against the Ouro family's plain
reference (``perfbench/families/ouro/reference.py``, which imports nothing of
the program and writes the loop out pass by pass).

Seeded random weights at tiny widths on the CPU. The program in float32
(its matmuls at ``highest``) has to agree with the reference tightly: the
loss and every leaf's gradient, the stacks' holding their four uses. The
loop is tied to its unrolled form (four copies of the layers, their
gradients summed), one pass to the plain next-token loss, and the
configurations that have no loop to the trees they had.
"""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness
from perfbench.reference.numerics import mm_highest
from ray_tpu.models import (
    TransformerConfig,
    decode_step,
    exit_weights,
    forward,
    init_kv_cache,
    init_params,
    loss_fn,
    make_spmd_train_step,
    prefill_chunk,
)
from ray_tpu.models import transformer

FAMILY = harness.family(["perfbench"], "ouro")
REF = FAMILY.reference
# Hidden 64, 4 heads of 16, 2 layers run 4 times.
MODEL = {
    "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "max_position_embeddings": 4096, "rope_theta": 1000000,
    "rms_norm_eps": 1e-6, "vocab_size": 128, "tie_word_embeddings": False,
    "layer_types": ["full_attention"] * 2, "use_sliding_window": False,
    "sliding_window": None, "total_ut_steps": 4, "sandwich_norm": True,
    "exit_entropy_beta": 0.1}
SEED = 2 ** 31 + 48


def _cfg(model=MODEL, dtype=jnp.float32):
    return dataclasses.replace(FAMILY.model_config(model), dtype=dtype)


def _batch(model=MODEL, batch=2, seq_len=24):
    return FAMILY.batch_of(harness.seed_key(SEED), 0, batch, seq_len,
                           model["vocab_size"])


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def _program_grads(cfg, params, tokens, targets):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, targets)))(params)


@pytest.fixture(scope="module")
def reference_grads():
    params = FAMILY.make_params(MODEL, SEED)
    tokens, targets = _batch()
    loss, grads = jax.jit(jax.value_and_grad(lambda p: REF.loss(
        MODEL, p, tokens, targets, mm_highest)))(params)
    return params, tokens, targets, loss, grads


def test_the_family_tree_is_the_programs():
    """The family's seeded tree has the program's structure and shapes: the
    four norms a layer, the gate's weight and bias beside the table, the
    final norm and the head."""
    cfg = _cfg()
    tree = jax.eval_shape(lambda: FAMILY.make_params(MODEL, 0))
    program = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.structure(program) == jax.tree.structure(tree)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape and a.dtype == b.dtype,
        program, tree)))
    assert {"attn_post_norm", "mlp_post_norm"} <= set(tree["layers"])
    assert tree["exit_gate"]["w"].shape == (64, 1)
    assert tree["exit_gate"]["b"].shape == (1,)


def test_loss_and_every_gradient_match_the_reference(reference_grads):
    """Float32 against float32 at ``highest``: the loss to 1e-5 and each
    leaf's gradient to 1e-4 of its largest entry (the two sum the same
    products in other orders, and the table's gradient adds the rows'
    cotangents of 48 positions, where float32's rounding of a sum of many
    terms is some 1e-6 of it)."""
    params, tokens, targets, want_loss, want = reference_grads
    loss, got = _program_grads(_cfg(), params, tokens, targets)
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * abs(float(want_loss))
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert len(flat_got) == len(flat_want) == 16
    for path, g in flat_got:
        assert _rel(g, flat_want[path]) < 1e-4, jax.tree_util.keystr(path)
    # every leaf learns: the gate through the distribution, the stack
    # through every pass's loss and the gates, the last gate's logit
    # weighs nothing but the gate's weights are read by three passes
    assert all(float(jnp.max(jnp.abs(g))) > 0 for _p, g in flat_got)


def test_the_loop_is_four_unrolled_copies_summed(reference_grads):
    """Weight sharing: the looped stack's gradient of a layer's leaf is the
    sum of the gradients of four unrolled copies of the layers handed the
    same weights, pass by pass through the program's own layers, final
    norm, gate, head and objective."""
    params, tokens, targets, _loss, _want = reference_grads
    cfg = _cfg()
    T = cfg.total_ut_steps

    def unrolled(copies, rest):
        x, positions, constrain = transformer._stream(cfg, rest, tokens,
                                                      None, None)
        gates, nlls = [], []
        for layers in copies:
            x = transformer._layers(cfg, layers, x, positions, constrain)
            x = transformer.rms_norm(x, rest["final_norm"], cfg.norm_eps)
            gates.append(transformer._exit_logits(rest, x))
            nlls.append(transformer._token_nll(
                transformer._lm_head(cfg, rest, x), targets))
        return transformer._exit_objective(cfg, jnp.stack(gates),
                                           jnp.stack(nlls))

    with jax.default_matmul_precision("highest"):
        loss_u, (grads_u, _rest) = jax.jit(jax.value_and_grad(
            unrolled, argnums=(0, 1)))((params["layers"],) * T, params)
    loss, grads = _program_grads(cfg, params, tokens, targets)
    assert abs(float(loss) - float(loss_u)) <= 1e-6 * abs(float(loss))
    summed = jax.tree.map(lambda *g: sum(g), *grads_u)
    for name, g in grads["layers"].items():
        assert _rel(g, summed[name]) < 1e-5, name
        # and no one copy alone is the gradient
        assert _rel(g, grads_u[0][name]) > 1e-2, name


def test_one_pass_is_the_plain_next_token_loss_of_a_sandwich_decoder():
    """T = 1: no gate in the tree, the loss the mean next-token loss of the
    last logits, and the reference's objective of one pass (p_1 = 1, no
    entropy) is the same number."""
    model = dict(MODEL, total_ut_steps=1)
    cfg = _cfg(model)
    params = init_params(cfg, jax.random.PRNGKey(3))
    assert "exit_gate" not in params
    assert {"attn_post_norm", "mlp_post_norm"} <= set(params["layers"])
    tokens, targets = _batch(model)
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(functools.partial(loss_fn, cfg))(params, tokens,
                                                        targets)
        logits = forward(cfg, params, tokens)
        ref = REF.loss(model, params, tokens, targets, mm_highest)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits), targets[..., None],
                               axis=-1)
    np.testing.assert_allclose(loss, jnp.mean(nll), rtol=1e-6)
    np.testing.assert_allclose(loss, ref, rtol=1e-5)
    # and the sandwich's norms are read: weights of 2 move the loss
    doubled = jax.tree.map(lambda a: a, params)
    doubled["layers"]["mlp_post_norm"] = 2 * params["layers"]["mlp_post_norm"]
    assert abs(float(loss_fn(cfg, doubled, tokens, targets) - loss)) > 1e-3


def test_the_exit_distribution_sums_to_one_and_the_entropy_lowers_the_loss():
    """p over the four passes sums to 1 at every position (and so its mean,
    ``exit_weights``); the entropy term is the report's, ``-beta H(p)``:
    the objective with beta is the objective without it minus beta times
    the exit distribution's entropy."""
    cfg = _cfg()
    params = FAMILY.make_params(MODEL, SEED)
    tokens, targets = _batch()
    shares = exit_weights(cfg, params, tokens)
    assert shares.shape == (4,)
    np.testing.assert_allclose(float(jnp.sum(shares)), 1.0, rtol=1e-6)
    assert float(jnp.min(shares)) > 0
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 2, 24)) * 3
    p = jnp.exp(transformer._exit_log_probs(logits))
    np.testing.assert_allclose(jnp.sum(p, 0), 1.0, rtol=1e-6)
    lam = jax.nn.sigmoid(logits)
    # (through log-sigmoids, so to float32's rounding of a logarithm)
    np.testing.assert_allclose(p[1], lam[1] * (1 - lam[0]), rtol=1e-5,
                               atol=1e-7)
    np.testing.assert_allclose(p[3], jnp.prod(1 - lam[:3], 0), rtol=1e-5,
                               atol=1e-7)
    with jax.default_matmul_precision("highest"):
        plain = loss_fn(dataclasses.replace(cfg, exit_entropy_beta=0.0),
                        params, tokens, targets)
        regular = loss_fn(cfg, params, tokens, targets)
        probs, _nll = jax.vmap(lambda t, y: REF.parts(
            MODEL, params, t, y, mm_highest))(tokens, targets)
    entropy = float(jnp.mean(-jnp.sum(probs * jnp.log(probs), axis=1)))
    assert entropy > 0
    np.testing.assert_allclose(float(regular),
                               float(plain) - 0.1 * entropy, rtol=1e-5)
    np.testing.assert_allclose(shares, jnp.mean(probs, axis=(0, 2)),
                               rtol=1e-4)


# The seeded trees of the test suite's train configurations (those of
# tests/test_segment_names.py, key 7) as the parent of the looped stack drew
# them: path and bytes of every leaf, hashed.
DRAWS = {"dense": "60c4302e3fbf9509", "pattern": "2c6dfffee743f8d4",
         "hybrid": "79de31eca61c1734", "alone": "411e0cb1750f5d5f",
         "module": "2fcdb091936135f6"}


def _draw_hash(cfg):
    h = hashlib.sha256()
    for path, a in jax.tree_util.tree_leaves_with_path(
            init_params(cfg, jax.random.PRNGKey(7))):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("program", sorted(DRAWS))
def test_the_configurations_without_a_loop_keep_their_trees(program):
    import test_segment_names

    assert _draw_hash(test_segment_names.TRAIN[program]) == DRAWS[program]


def test_sandwich_norms_add_ones_and_move_no_draw():
    plain = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                              n_heads=4, n_kv_heads=4, d_ff=48)
    sandwich = dataclasses.replace(plain, sandwich_norm=True)
    a = init_params(plain, jax.random.PRNGKey(1))
    b = init_params(sandwich, jax.random.PRNGKey(1))
    for name, leaf in a["layers"].items():
        np.testing.assert_array_equal(b["layers"][name], leaf)
    assert set(b["layers"]) - set(a["layers"]) == {"attn_post_norm",
                                                  "mlp_post_norm"}
    np.testing.assert_array_equal(b["layers"]["attn_post_norm"], 1.0)


@pytest.mark.parametrize("change", [{"total_ut_steps": 4},
                                    {"sandwich_norm": True}],
                         ids=["looped", "sandwich"])
def test_the_cached_bodies_refuse_a_loop_and_sandwich_norms(change):
    """A looped stack needs a cache a layer a pass, which the paged cache
    lacks, and the cached bodies have met sandwich norms in no test."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=4, n_kv_heads=4, d_ff=48, **change)
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = init_kv_cache(cfg, 4, 4)
    tables = jnp.zeros((1, 2), jnp.int32)
    ones = jnp.ones((1,), jnp.int32)
    with pytest.raises(NotImplementedError, match="runs each layer once"):
        decode_step(cfg, params, cache, ones, ones, tables)
    with pytest.raises(NotImplementedError, match="runs each layer once"):
        prefill_chunk(cfg, params, cache, jnp.ones((1, 4), jnp.int32),
                      ones, ones, tables)


def test_the_manual_step_refuses_a_looped_stack():
    from ray_tpu.parallel.mesh import make_mesh

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=4, n_kv_heads=4, d_ff=48,
                            total_ut_steps=4)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    with pytest.raises(NotImplementedError, match="4 passes of its stack"):
        make_spmd_train_step(cfg, make_mesh(devices=jax.devices()[:1]), params)


def test_the_manual_step_refuses_sandwich_norms():
    from ray_tpu.parallel.mesh import make_mesh

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=4, n_kv_heads=4, d_ff=48,
                            sandwich_norm=True)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    with pytest.raises(NotImplementedError, match="sandwich_norm True"):
        make_spmd_train_step(cfg, make_mesh(devices=jax.devices()[:1]), params)


def test_a_loop_of_another_layer_kind_is_refused():
    with pytest.raises(ValueError, match="looped stack"):
        TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                          n_kv_heads=4, d_ff=48, layer_types=("conv",) * 2,
                          total_ut_steps=4)
    with pytest.raises(ValueError, match="looped stack"):
        TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                          n_kv_heads=4, d_ff=48, total_ut_steps=0)
