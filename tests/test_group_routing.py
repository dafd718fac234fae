"""Group-limited routing (``parallel/moe.py:route`` with ``n_group`` above
1): DeepSeek-V3's choice among the best groups of experts, on the CPU; and
``route``, which fetches and puts no value by index, against the
formulation that does (``_fetching_route``, kept here and nowhere else):
the three cells' routers at tiny widths, bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax._src.ad_checkpoint import saved_residuals

from ray_tpu.parallel import moe

T, D, E, K = 96, 16, 32, 4
HELD = (1, 6, 9, 17)


def _inputs(seed=0, bias_scale=0.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, E)) / np.sqrt(D)
    bias = bias_scale * jax.random.normal(ks[2], (E,))
    return h, router, bias


def _fetching_route(h, router, bias, *, experts_held, k, score="softmax",
                    norm_topk=False, scale=1.0, n_group=1, topk_group=1,
                    keep_input=True):
    """``route`` as it stood until PR 42, field for field: the gates fetched
    with ``take_along_axis``, an expert's place looked up in a table, the
    sorted pairs' gates fetched by the places an ``argsort`` gives."""
    T, E = h.shape[0], router.shape[1]

    def product(h, router):
        return jnp.matmul(h.astype(jnp.float32), router.astype(jnp.float32),
                          precision=lax.Precision.HIGHEST)

    logits = (product if keep_input else
              jax.checkpoint(product, prevent_cse=False))(h, router)
    scores = (jax.nn.sigmoid(logits) if score == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    select = scores if bias is None else \
        scores + lax.stop_gradient(bias.astype(jnp.float32))
    if n_group > 1:
        select = moe._kept_groups(select, n_group, topk_group)
    _, experts = lax.top_k(select, k)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    gates = gates * scale
    n_held = len(experts_held)
    place = np.full((E,), n_held, np.int32)
    place[list(experts_held)] = np.arange(n_held, dtype=np.int32)
    group = jnp.asarray(place)[experts].reshape(T * k)
    pair = jnp.argsort(group, stable=True).astype(jnp.int32)
    pair = pair[:T * min(k, n_held)]
    group = group[pair]
    sizes = jnp.sum(group[:, None] == jnp.arange(n_held)[None, :], axis=0,
                    dtype=jnp.int32)
    return moe.Routing(token=pair // k, gate=gates.reshape(T * k)[pair],
                       held=group < n_held, group_sizes=sizes,
                       experts=experts, gates=gates)


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_one_group_is_the_fetching_routing_field_for_field(score):
    h, router, bias = _inputs(bias_scale=0.05)
    kw = dict(experts_held=HELD, k=K, score=score, norm_topk=True, scale=2.5)
    got = moe.route(h, router, bias, n_group=1, topk_group=1, **kw)
    default = moe.route(h, router, bias, **kw)
    want = _fetching_route(h, router, bias, **kw)
    for field in moe.Routing._fields:
        np.testing.assert_array_equal(getattr(got, field),
                                      getattr(want, field), field)
        np.testing.assert_array_equal(getattr(default, field),
                                      getattr(want, field), field)
    # and the limit is not in the program where there is one group
    text = lambda **lim: jax.jit(lambda *a: moe.route(*a, **kw, **lim)).lower(
        h, router, bias).as_text()
    assert text(n_group=1, topk_group=1) == text()


# The three cells' routers at tiny widths; ``twist`` makes the batch the
# case is there for. LFM2's: sigmoid, a bias, top-4, every expert of a
# token's row of ``select`` distinct but two, which are equal (the router's
# columns 2 and 3 are one column, the bias zero: top-k's order between them
# decides the pair's place). Ling's: 8 groups of which 4 are kept, a scale,
# and a bias that sends a third of the tokens to experts none of which is
# held. Nemotron's: top-22 with 8 held, fewer than a token takes, so the
# sorted rows end at T x 8, and the float32 copy made again.
ROUTERS = {
    "lfm2-two-equal": dict(
        E=16, held=(0, 2, 3, 5, 7, 9, 11, 12), twist="equal", kw=dict(
            k=4, score="sigmoid", norm_topk=True, scale=1.0)),
    "ling3-tokens-with-no-held-expert": dict(
        E=64, held=(0, 1, 2, 3, 4, 5, 6, 7), twist="elsewhere", kw=dict(
            k=8, score="sigmoid", norm_topk=True, scale=2.5, n_group=8,
            topk_group=4)),
    "nemotron3-fewer-held-than-taken": dict(
        E=64, held=(0, 1, 2, 3, 4, 5, 6, 7), twist=None, kw=dict(
            k=22, score="sigmoid", norm_topk=True, scale=5.0,
            keep_input=False)),
    "softmax-no-bias": dict(
        E=32, held=HELD, twist="no-bias", kw=dict(
            k=K, score="softmax", norm_topk=False, scale=2.0)),
}


def _router_case(name):
    case = ROUTERS[name]
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 3)
    h = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, case["E"])) / np.sqrt(D)
    bias = 0.05 * jax.random.normal(ks[2], (case["E"],))
    if case["twist"] == "equal":
        router, bias = router.at[:, 3].set(router[:, 2]), jnp.zeros_like(bias)
    if case["twist"] == "elsewhere":
        # a token of the first third scores every held expert's group last
        h = h.at[:T // 3].set(jnp.abs(h[:T // 3]))
        router = router.at[:, :8].set(-jnp.abs(router[:, :8]) - 1.0)
    if case["twist"] == "no-bias":
        bias = None
    return h, router, bias, dict(case["kw"], experts_held=case["held"])


@pytest.mark.parametrize("name", list(ROUTERS))
def test_nothing_fetched_by_index_and_the_fetching_routing_bit_for_bit(name):
    """The six fields on every row, and the gradient of a scalar of ``gate``
    and ``gates`` with respect to ``h``, ``router`` and the bias (zero: it
    selects and does not weigh), equal to the last bit, eagerly and under
    ``jit``, where the compiler is free to merge the sums it finds."""
    h, router, bias, kw = _router_case(name)
    rows = T * min(kw["k"], len(kw["experts_held"]))
    by_row = jnp.cos(jnp.arange(rows, dtype=jnp.float32))
    by_pair = jnp.sin(jnp.arange(T * kw["k"], dtype=jnp.float32))

    def fields(route):
        return lambda *a: route(*a, **kw)

    # Linear in ``gates``: a curve there would be fused into the
    # renormalisation's own backward pass, arithmetic that ``route`` did
    # not change, and the CPU's compiler then associates that by what
    # stands around it (a last bit at k = 22 under ``jit``, none eagerly).
    def scalar(route):
        def of(h, router, bias):
            r = route(h, router, bias, **kw)
            return jnp.sum(jnp.sin(jnp.where(r.held, r.gate, 0)) * by_row) \
                + jnp.sum(r.gates.reshape(-1) * by_pair)
        return jax.grad(of, argnums=(0, 1) if bias is None else (0, 1, 2))

    for wrap in (lambda f: f, jax.jit):
        got = wrap(fields(moe.route))(h, router, bias)
        want = wrap(fields(_fetching_route))(h, router, bias)
        assert got.token.shape == (rows,)
        for field in moe.Routing._fields:
            np.testing.assert_array_equal(getattr(got, field),
                                          getattr(want, field), field)
        got_d = wrap(scalar(moe.route))(h, router, bias)
        want_d = wrap(scalar(_fetching_route))(h, router, bias)
        for ours, theirs in zip(got_d, want_d):
            np.testing.assert_array_equal(ours, theirs)
        assert np.any(np.asarray(got_d[0])) and np.any(np.asarray(got_d[1]))
        assert bias is None or not np.any(np.asarray(got_d[2]))
    # nothing of [T, k, E] or [T, k, held] waits for the backward pass: the
    # masks are made again there from the [T, k] experts
    kept = saved_residuals(lambda h, router: moe.route(h, router, bias, **kw),
                           h, router)
    assert max(int(np.prod(aval.shape)) for aval, _ in kept) \
        <= T * max(kw["k"], router.shape[1], D)
    # the batch is the one the case is there for
    held = np.isin(np.asarray(got.experts), kw["experts_held"])
    if ROUTERS[name]["twist"] == "equal":
        both = np.isin(np.asarray(got.experts), (2, 3)).sum(axis=-1) == 2
        assert both.sum() > T // 8
    if ROUTERS[name]["twist"] == "elsewhere":
        assert (held.sum(axis=-1) == 0).sum() >= T // 3
        assert 0 < int(got.group_sizes.sum()) == held.sum()
    if kw["k"] > len(kw["experts_held"]):
        assert rows == T * 8 < T * kw["k"] and int(got.held.sum()) == held.sum()


def test_an_infinite_score_of_another_expert_stays_where_it_is():
    """The one-hot selection is a ``where`` and no product: a token whose
    row holds an infinite or undefined score beside its chosen ones takes
    its own experts' gates as they are."""
    scores = jnp.array([[0.5, jnp.inf, 0.25, jnp.nan], [0.1, 0.2, 0.3, 0.4]])
    experts = jnp.array([[0, 2], [3, 1]], jnp.int32)
    np.testing.assert_array_equal(
        moe._chosen(scores, experts),
        np.array([[0.5, 0.25], [0.4, 0.2]], np.float32))
    got = jax.grad(lambda s: jnp.sum(moe._chosen(s, experts) * jnp.array(
        [[1.0, 2.0], [3.0, 4.0]])))(scores)
    np.testing.assert_array_equal(got, [[1, 0, 2, 0], [0, 4, 0, 3]])


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
