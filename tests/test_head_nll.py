"""``models/transformer.py:_head_nll``: the head and its next-token loss in
one ``jax.custom_vjp``.

Forward the logits in the configuration's type, their log-sum-exp and the
target's logit picked by a comparison with the columns' index; backward the
logits' cotangent ``(softmax - onehot) dnll`` made in one elementwise pass,
then the two products. Held here to autodiff of the formulation it replaces
in the training loss, ``_token_nll(_lm_head(...))``: float32 logits, a
log-softmax and ``take_along_axis``, whose transpose scatters into float32
zeros over the vocabulary.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import TransformerConfig, init_params, loss_fn
from ray_tpu.models import transformer

V, D = 200, 48      # a vocabulary that is no multiple of 128
B, S = 2, 16


def _cfg(tied=False, dtype=jnp.float32, **kw):
    return TransformerConfig(vocab_size=V, d_model=D, n_layers=2, n_heads=4,
                             n_kv_heads=2, d_ff=64, tie_embeddings=tied,
                             dtype=dtype, **kw)


def _unconstrained(y, *_logical):
    return y


def _parent_nll(cfg, params, x, targets, constrain=_unconstrained):
    """The next-token loss as the training loss took it before
    ``_head_nll``: float32 logits, log-softmax, ``take_along_axis``."""
    logits = constrain(transformer._lm_head(cfg, params, x),
                       "batch", "sequence", "vocab")
    return transformer._token_nll(logits, targets)


def _targets(case):
    key = jax.random.PRNGKey(3)
    if case == "drawn":
        return jax.random.randint(key, (B, S), 0, V)
    if case == "ends":           # the first and the last column only
        return jnp.where(jax.random.bernoulli(key, 0.5, (B, S)), 0, V - 1)
    raise ValueError(case)


def _value_and_grads(nll, cfg, params, x, targets):
    """The loss weighed by a drawn cotangent a position, and its gradients
    for the parameters and for ``x``."""
    w = jax.random.uniform(jax.random.PRNGKey(4), (B, S), jnp.float32)
    return jax.value_and_grad(
        lambda p, x: jnp.sum(nll(cfg, p, x, targets, _unconstrained) * w),
        argnums=(0, 1))(params, x)


def _leaves(tree):
    return [np.asarray(a, np.float32) for a in jax.tree.leaves(tree)]


@pytest.mark.parametrize("case", ["drawn", "ends"])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_float32_value_and_gradients_are_the_parents(tied, case):
    cfg = _cfg(tied)
    params = init_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32)
    targets = _targets(case)
    nll = transformer._head_nll(cfg, params, x, targets, _unconstrained)
    assert nll.dtype == jnp.float32 and nll.shape == (B, S)
    np.testing.assert_allclose(
        np.asarray(nll), np.asarray(_parent_nll(cfg, params, x, targets)),
        rtol=1e-6, atol=1e-6)
    got = _value_and_grads(transformer._head_nll, cfg, params, x, targets)
    want = _value_and_grads(_parent_nll, cfg, params, x, targets)
    assert ("lm_head" in params) != tied
    for a, b in zip(_leaves(got), _leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("case", ["drawn", "ends"])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_bfloat16_is_the_parent_to_its_own_rounding(tied, case):
    """In bfloat16 the logits, ``x``'s gradient and the weight's gradient
    before its cast are bfloat16 in both formulations; the change lies no
    farther from the parent than the parent lies from its own float32
    self, and the types are the parent's."""
    cfg = _cfg(tied, jnp.bfloat16)
    params = init_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.bfloat16)
    targets = _targets(case)
    got = _value_and_grads(transformer._head_nll, cfg, params, x, targets)
    want = _value_and_grads(_parent_nll, cfg, params, x, targets)
    exact = _value_and_grads(_parent_nll, dataclasses.replace(
        cfg, dtype=jnp.float32), params, x.astype(jnp.float32), targets)
    assert [a.dtype for a in jax.tree.leaves(got)] == [
        a.dtype for a in jax.tree.leaves(want)]
    for a, b, c in zip(_leaves(got), _leaves(want), _leaves(exact)):
        assert np.abs(a - b).max() <= np.abs(b - c).max(), (
            np.abs(a - b).max(), np.abs(b - c).max())


def test_nothing_is_fetched_or_put_by_index():
    """The target's logit is picked by a comparison with the columns' index,
    and its cotangent is a select: no gather forward, no scatter back."""
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32)
    jaxpr = str(jax.make_jaxpr(lambda p, x: _value_and_grads(
        transformer._head_nll, cfg, p, x, _targets("drawn")))(params, x))
    assert "gather" not in jaxpr and "scatter" not in jaxpr
    parent = str(jax.make_jaxpr(lambda p, x: _value_and_grads(
        _parent_nll, cfg, p, x, _targets("drawn")))(params, x))
    assert "gather" in parent and "scatter-add" in parent


def _model_grads(cfg, monkeypatch, parent):
    """``loss_fn`` and its gradients over a drawn batch, with the training
    loss's head as it is or, where ``parent``, as it was."""
    if parent:
        monkeypatch.setattr(transformer, "_head_nll", _parent_nll)
    jax.clear_caches()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, V)
    targets = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, V)
    out = jax.value_and_grad(
        lambda p: loss_fn(cfg, p, tokens, targets))(params)
    monkeypatch.undo()
    jax.clear_caches()
    return out


@pytest.mark.parametrize("program", ["plain", "tied", "module", "looped"])
def test_the_models_loss_and_gradients_are_the_parents(program, monkeypatch):
    """Each of the three places the training loss reads the head: the plain
    stack's (a tied table's too), a multi-token-prediction module's, whose
    last position is left out of the mean outside the function, and a
    looped stack's, where each pass's head is checkpointed inside the
    passes' ``lax.scan`` and its gradient for the head's weight summed over
    the passes."""
    cfg = {"plain": _cfg(), "tied": _cfg(tied=True),
           "module": _cfg(mtp_depth=1),
           "looped": _cfg(total_ut_steps=3, sandwich_norm=True)}[program]
    got = _model_grads(cfg, monkeypatch, parent=False)
    want = _model_grads(cfg, monkeypatch, parent=True)
    for a, b in zip(_leaves(got), _leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_the_modules_last_position_moves_nothing():
    """The mask stays outside ``_head_nll``: the module's loss is the mean
    over the first S - 1 positions. The last, whose token after next does
    not exist (its rolled-in target is the sequence's first), reaches the
    loss by no path, its layer being causal: the stack's state there gets
    no gradient from the module, and every other position gets one."""
    cfg = _cfg(mtp_depth=1)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, V)
    targets = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, V)
    h, positions, constrain = transformer._hidden(cfg, params, tokens, None,
                                                  None)
    grad = np.asarray(jax.grad(lambda h: transformer._mtp_loss(
        cfg, params, h, targets, positions, constrain))(h))
    assert not grad[:, -1].any()
    assert grad[:, :-1].any(axis=-1).all()
