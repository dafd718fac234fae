"""The expert layer's row passes follow the rows routed here, on the CPU.

``ops/moe_rows.py`` at tiny widths and a tile of 8 rows: the gather and the
scatter-add against plain ``src[token]`` and ``.at[token].add`` on the first
``n`` rows, for every ``n`` around a tile's edge, tokens repeated inside a
tile; each as the other's ``jax.vjp``; the row-wise pass and its derivative
against ``fn`` itself. Then ``held_experts`` against the plain formulation
over every sorted row (the one it replaced), value and every gradient, from
no pair routed here to all T x k of them, and the layer's program holds no
branch. What a pass leaves behind ``n`` is undefined (the interpreter
leaves NaN there): every comparison here reads a whole result, so a row
that leaks fails it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import loss_fn
from ray_tpu.ops import moe_rows
from ray_tpu.ops.grouped_matmul import grouped_matmul
from ray_tpu.ops.moe_rows import ROW_TILE
from ray_tpu.parallel import moe
from test_pattern_model import FAMILY, MODEL, _batch, _cfg

TILE, T, K, D, F = 8, 16, 2, 8, 16
M = T * K
EDGES = [0, 1, TILE - 1, TILE, TILE + 1, M]


def _eqns(jaxpr):
    """Every equation under ``jaxpr``, loop and kernel bodies included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


def _normal(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def _token():
    """The tokens of M sorted pairs: every token K times, and twice inside
    the first tile."""
    token = np.repeat(np.arange(T, dtype=np.int32), K)
    rest = np.random.default_rng(0).permutation(token[2:])
    return jnp.asarray(np.concatenate([token[:2], rest]))


def _live(n):
    return (jnp.arange(M) < n)[:, None]


def _worked(n):
    return -(-n // TILE) * TILE


@pytest.mark.parametrize("n", EDGES)
def test_gather_rows_is_the_plain_gather_on_the_first_rows(n):
    src, token = _normal(1, (T, D)), _token()
    got = jax.jit(functools.partial(moe_rows.gather_rows, tile=TILE))(
        src, token, n)
    worked = _worked(n)
    np.testing.assert_array_equal(got[:worked], src[token[:worked]])
    assert got.shape == (M, D)


@pytest.mark.parametrize("n", EDGES)
def test_scatter_add_rows_is_the_plain_scatter_add_of_the_first_rows(n):
    token = _token()
    # behind n: what a pass may have left there
    rows = jnp.where(_live(n), _normal(2, (M, D)), jnp.nan)
    got = jax.jit(functools.partial(
        moe_rows.scatter_add_rows, num_tokens=T, tile=TILE))(rows, token, n)
    want = jnp.zeros((T, D)).at[token[:n]].add(rows[:n])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n", EDGES)
def test_each_row_operation_is_the_others_transpose(n):
    src, rows, token = _normal(3, (T, D)), _normal(4, (M, D)), _token()
    gather = functools.partial(moe_rows.gather_rows, token=token, n_rows=n,
                               tile=TILE)
    scatter = functools.partial(moe_rows.scatter_add_rows, token=token,
                                n_rows=n, num_tokens=T, tile=TILE)
    (back,) = jax.vjp(gather, src)[1](jnp.where(_live(n), rows, jnp.nan))
    np.testing.assert_allclose(back, scatter(rows), rtol=1e-6, atol=1e-6)
    (back,) = jax.vjp(scatter, rows)[1](src)
    worked = _worked(n)
    np.testing.assert_array_equal(back[:worked], gather(src)[:worked])


def _swiglu(g, u):
    return (jax.nn.silu(g.astype(jnp.float32))
            * u.astype(jnp.float32)).astype(g.dtype)


def _scaled(y, w):
    return y.astype(jnp.float32) * w


@pytest.mark.parametrize("n", EDGES)
@pytest.mark.parametrize("fn,widths,dtypes", [
    (_swiglu, (F, F), (jnp.bfloat16, jnp.bfloat16)),
    (_scaled, (D, 1), (jnp.bfloat16, jnp.float32)),
    (lambda x: (x * 2, x + 1), (D,), (jnp.float32,)),
], ids=["swiglu", "a-number-a-row", "two-results"])
def test_map_rows_is_fn_on_the_worked_rows_and_so_is_its_derivative(
        fn, widths, dtypes, n):
    operands = [_normal(5 + i, (M, w), dt)
                for i, (w, dt) in enumerate(zip(widths, dtypes))]
    mapped = functools.partial(moe_rows.map_rows, fn, n, tile=TILE)
    worked = _worked(n)
    got, back = jax.vjp(mapped, *operands)
    want, plain_back = jax.vjp(fn, *operands)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a[:worked], b[:worked])
    cot = jax.tree.map(lambda a: jnp.ones_like(a), want)
    for a, b in zip(back(cot), plain_back(cot)):
        np.testing.assert_allclose(a[:worked].astype(jnp.float32),
                                   b[:worked].astype(jnp.float32), rtol=1e-6)


@pytest.mark.parametrize("n", EDGES)
def test_twice_adds_its_readers_cotangents_over_the_worked_rows(n):
    x, a, b = (_normal(i, (M, D)) for i in (8, 9, 10))
    (back,) = jax.vjp(functools.partial(
        moe_rows.twice, n_rows=n, tile=TILE), x)[1]((a, b))
    worked = _worked(n)
    np.testing.assert_array_equal(back[:worked], (a + b)[:worked])


def test_shapes_that_are_not_whole_tiles_take_the_plain_form():
    src, token = _normal(1, (T, D)), _token()[:M - 3]
    rows = _normal(2, (M - 3, D))
    np.testing.assert_array_equal(
        moe_rows.gather_rows(src, token, 5, tile=TILE)[:5], src[token[:5]])
    np.testing.assert_allclose(
        moe_rows.scatter_add_rows(rows, token, 5, T, tile=TILE),
        jnp.zeros((T, D)).at[token[:5]].add(rows[:5]), rtol=1e-6)
    np.testing.assert_array_equal(
        moe_rows.map_rows(jnp.negative, 5, rows, tile=TILE), -rows)


# ------------------------------------------------------------ held_experts
def _plain_held_experts(h, routing, e_gate, e_up, e_down):
    """Every sorted pair a row of every pass: the formulation before the
    passes followed the routing."""
    held = routing.held[:, None]
    xs = jnp.where(held, h[routing.token], 0)
    g = grouped_matmul(xs, e_gate, routing.group_sizes)
    u = grouped_matmul(xs, e_up, routing.group_sizes)
    ys = grouped_matmul(jax.nn.silu(g) * u, e_down, routing.group_sizes)
    ys = jnp.where(held, ys, 0).astype(jnp.float32) * routing.gate[:, None]
    return jnp.zeros(h.shape, jnp.float32).at[routing.token].add(ys)


def _routing(sizes, gate):
    """A layer's routing with ``sizes`` pairs on each of 2 held experts:
    the first pairs in (token, choice) order go to them."""
    group = np.full((M,), 2, np.int32)
    group[:sum(sizes)] = np.repeat(np.arange(2, dtype=np.int32), sizes)
    group = np.random.default_rng(1).permutation(group)
    group, pair = jax.lax.sort_key_val(jnp.asarray(group),
                                       jnp.arange(M, dtype=jnp.int32))
    return moe.Routing(
        token=pair // K, gate=gate[pair], held=group < 2,
        group_sizes=jnp.asarray(sizes, jnp.int32),
        experts=jnp.zeros((T, K), jnp.int32), gates=gate.reshape(T, K))


@pytest.mark.parametrize("sizes", [
    (0, 0), (3, 5), (4, 5), (M - 5, 5), (M, 0), (0, M)],
    ids=["none-routed-here", "one-tile", "one-row-past-a-tile",
         "all-pairs-on-two", "all-pairs-on-the-first", "all-on-the-last"])
def test_held_experts_is_the_plain_formulation_under_any_routing(sizes):
    h, gate = _normal(11, (T, D)), jax.nn.sigmoid(_normal(12, (M,)))
    weights = (_normal(13, (2, D, F)), _normal(14, (2, D, F)),
               _normal(15, (2, F, D)))
    cot = _normal(16, (T, D))

    def run(layer, h, gate, *weights):
        return jnp.sum(layer(h, _routing(sizes, gate), *weights) * cot)

    followed = functools.partial(moe.held_experts, tile=TILE)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(functools.partial(
            run, followed), argnums=(0, 1, 2, 3, 4)))(h, gate, *weights)
        want = jax.jit(jax.value_and_grad(functools.partial(
            run, _plain_held_experts), argnums=(0, 1, 2, 3, 4)))(
                h, gate, *weights)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    if sum(sizes) == 0:
        assert not np.any(np.asarray(got[1][0]))
    else:
        assert all(np.any(np.asarray(g)) for g in got[1][:2])


@pytest.mark.parametrize("n", EDGES + [2 * TILE + 3])
def test_rows_worked_is_whole_tiles_over_the_pairs_routed_here(n):
    sizes = jnp.asarray([n // 3, n - n // 3], jnp.int32)
    assert int(moe.rows_worked(sizes, TILE)) == _worked(n)
    assert int(moe.rows_worked(sizes)) == -(-n // ROW_TILE) * ROW_TILE
    assert int(moe_rows.worked_tiles(n, TILE)) * TILE == _worked(n)


def test_the_expert_layer_is_one_program_with_no_branch():
    """Whatever the routing, the same instructions: no ``cond`` anywhere
    under ``seg.moe_experts`` of the patterned model's loss, forward or
    backward, and its row passes are there under their names."""
    tokens, targets = _batch(batch=2, seq_len=ROW_TILE // 2)
    cfg = _cfg()
    params = jax.eval_shape(lambda: FAMILY.make_params(MODEL, 0))
    jaxpr = jax.make_jaxpr(jax.grad(functools.partial(loss_fn, cfg)))(
        params, tokens, targets)
    under = [eqn for eqn in _eqns(jaxpr.jaxpr)
             if "seg.moe_experts" in str(eqn.source_info.name_stack)]
    names = {eqn.primitive.name for eqn in under}
    assert "cond" not in names and "while" in names and \
        "pallas_call" in names, sorted(names)
    stacks = " ".join(str(eqn.source_info.name_stack) for eqn in under)
    for name in ("moe_gather_rows", "moe_scatter_rows", "moe_map_rows"):
        assert name in stacks, name
