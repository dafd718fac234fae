"""Group-limited routing (``parallel/moe.py:route`` with ``n_group`` above
1): DeepSeek-V3's choice among the best groups of experts, on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.parallel import moe

T, D, E, K = 96, 16, 32, 4
HELD = (1, 6, 9, 17)


def _inputs(seed=0, bias_scale=0.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, E)) / np.sqrt(D)
    bias = bias_scale * jax.random.normal(ks[2], (E,))
    return h, router, bias


def _parents_route(h, router, bias, *, experts_held, k, score, norm_topk,
                   scale):
    """``route`` as it stood before it knew of groups, field for field."""
    T, E = h.shape[0], router.shape[1]
    logits = jnp.matmul(h.astype(jnp.float32), router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if score == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    select = scores + lax.stop_gradient(bias.astype(jnp.float32))
    _, experts = lax.top_k(select, k)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    gates = gates * scale
    n_held = len(experts_held)
    place = np.full((E,), n_held, np.int32)
    place[list(experts_held)] = np.arange(n_held, dtype=np.int32)
    group = jnp.asarray(place)[experts].reshape(T * k)
    group, pair = lax.sort_key_val(group, jnp.arange(T * k, dtype=jnp.int32))
    sizes = jnp.sum(group[:, None] == jnp.arange(n_held)[None, :], axis=0,
                    dtype=jnp.int32)
    return moe.Routing(token=pair // k, gate=gates.reshape(T * k)[pair],
                       held=group < n_held, group_sizes=sizes,
                       experts=experts, gates=gates)


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_one_group_is_the_parents_routing_field_for_field(score):
    h, router, bias = _inputs(bias_scale=0.05)
    kw = dict(experts_held=HELD, k=K, score=score, norm_topk=True, scale=2.5)
    got = moe.route(h, router, bias, n_group=1, topk_group=1, **kw)
    default = moe.route(h, router, bias, **kw)
    want = _parents_route(h, router, bias, **kw)
    for field in moe.Routing._fields:
        np.testing.assert_array_equal(getattr(got, field),
                                      getattr(want, field), field)
        np.testing.assert_array_equal(getattr(default, field),
                                      getattr(want, field), field)
    # and the program is the parent's, text for text
    text = lambda f: jax.jit(f).lower(h, router, bias).as_text()
    assert text(lambda *a: moe.route(*a, **kw)) \
        == text(lambda *a: _parents_route(*a, **kw))


@pytest.mark.parametrize("n_group,topk_group", [(4, 2), (8, 4), (2, 1)])
def test_every_chosen_expert_lies_in_a_kept_group(n_group, topk_group):
    h, router, bias = _inputs(seed=1, bias_scale=0.05)
    r = moe.route(h, router, bias, experts_held=HELD, k=K, score="sigmoid",
                  n_group=n_group, topk_group=topk_group)
    experts = np.asarray(r.experts)
    scores = np.asarray(jax.nn.sigmoid(jnp.matmul(
        h, router, precision=lax.Precision.HIGHEST))) + np.asarray(bias)
    per = E // n_group
    for t in range(T):
        grouped = scores[t].reshape(n_group, per)
        group_score = np.sort(grouped, axis=-1)[:, -2:].sum(-1)
        kept = set(np.argsort(-group_score, kind="stable")[:topk_group])
        assert {e // per for e in experts[t]} <= kept
        # and they are the best k of the kept groups' experts
        allowed = [e for e in range(E) if e // per in kept]
        best = sorted(allowed, key=lambda e: -scores[t, e])[:K]
        assert set(experts[t]) == set(best)
        assert len(set(experts[t])) == K
    # every pair has its row, held or not
    assert int(r.held.sum()) == int(r.group_sizes.sum())
    assert r.token.shape == (T * K,)


def test_the_limit_changes_the_choice_and_all_groups_kept_does_not():
    h, router, bias = _inputs(seed=2)
    kw = dict(experts_held=HELD, k=K, score="sigmoid")
    plain = moe.route(h, router, bias, **kw)
    every = moe.route(h, router, bias, n_group=4, topk_group=4, **kw)
    limited = moe.route(h, router, bias, n_group=4, topk_group=1, **kw)
    np.testing.assert_array_equal(plain.experts, every.experts)
    assert not np.array_equal(plain.experts, limited.experts)
    assert all(len({e // 8 for e in row}) == 1
               for row in np.asarray(limited.experts))


def test_the_bias_selects_and_does_not_weigh():
    h, router, _ = _inputs(seed=3)
    bias = jnp.zeros((E,)).at[5].set(10.0)      # expert 5 wins its group
    kw = dict(experts_held=HELD, k=K, score="sigmoid", norm_topk=True,
              scale=2.5, n_group=4, topk_group=2)
    r = moe.route(h, router, bias, **kw)
    assert bool((r.experts == 5).any(axis=-1).all())
    # its gate is its score's share, not its biased score's
    scores = jax.nn.sigmoid(jnp.matmul(h, router,
                                       precision=lax.Precision.HIGHEST))
    picked = jnp.take_along_axis(scores, r.experts, axis=-1)
    want = 2.5 * picked / picked.sum(-1, keepdims=True)
    np.testing.assert_allclose(r.gates, want, rtol=2e-6)
    # the scale: the k gates of a token sum to it
    np.testing.assert_allclose(r.gates.sum(-1), 2.5, rtol=2e-6)
    # no gradient reaches the bias, through the choice or the groups
    g = jax.grad(lambda b: moe.route(h, router, b, **kw).gates.sum())(bias)
    assert not np.any(np.asarray(g))
    g = jax.grad(lambda w: (moe.route(h, w, bias, **kw).gates ** 2).sum())(
        router)
    assert np.any(np.asarray(g))
