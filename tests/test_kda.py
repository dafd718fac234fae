"""The chunked KDA operator (``ops/kda.py``) against the recurrence it
computes, position by position in float32, forward and through
``jax.grad``, on the CPU at small widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax._src.ad_checkpoint import saved_residuals

from ray_tpu.ops.kda import CHUNK, SUB, _solve_transposed, kda_chunk

B, H, K, V = 2, 3, 16, 8


def kda_recurrence(q, k, v, g, beta):
    """The same operator position by position, in float32: what
    ``kda_chunk`` is held to. Shapes as ``kda_chunk``'s."""
    f32 = jnp.float32
    q, k, v, g, beta = (jnp.moveaxis(a.astype(f32), 1, 0)
                        for a in (q, k, v, g, beta))

    def step(S, x):
        q, k, v, g, b = x                                  # [B,H,K] ...
        S = jnp.exp(g)[..., None] * S
        u = b[..., None] * (v - jnp.sum(S * k[..., None], axis=-2))
        S = S + k[..., None] * u[..., None, :]
        return S, jnp.sum(S * q[..., None], axis=-2)

    S0 = jnp.zeros(q.shape[1:] + (v.shape[-1],), f32)
    _, o = lax.scan(step, S0, (q, k, v, g, beta))
    return jnp.moveaxis(o, 0, 1)


def _inputs(T, seed=0, dtype=jnp.float32, floor=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, T, H, K))
    k = jax.random.normal(ks[1], (B, T, H, K))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * K ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, V))
    g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (B, T, H, K)))
    if floor:
        g = jnp.full_like(g, -5.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _gap(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


# a whole chunk, several, and lengths the operator pads behind
@pytest.mark.parametrize("T", [CHUNK, 3 * CHUNK, 100, 7])
def test_forward_is_the_recurrence(T):
    args = _inputs(T)
    got, want = jax.jit(kda_chunk)(*args), kda_recurrence(*args)
    assert got.shape == want.shape == (B, T, H, V)
    assert _gap(got, want) < 5e-6


@pytest.mark.parametrize("T", [2 * CHUNK, 100])
def test_every_gradient_is_the_recurrences(T):
    args = _inputs(T, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, V))
    grads = lambda f: jax.jit(jax.grad(
        lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3, 4)))(*args)
    for name, got, want in zip("q k v g beta".split(), grads(kda_chunk),
                               grads(kda_recurrence)):
        assert _gap(got, want) < 5e-5, name


def test_the_steepest_decay_over_whole_chunks_does_not_overflow():
    """Every log-decay at its floor of -5 for two whole chunks: a score
    factored about a chunk's first position would need exp(63 * 5); about a
    sub-chunk's it needs exp(75) at most, which float32 and bfloat16 hold."""
    assert (SUB - 1) * 5.0 < np.log(np.finfo(np.float32).max)
    args = _inputs(2 * CHUNK, seed=2, floor=True)
    got, want = jax.jit(kda_chunk)(*args), kda_recurrence(*args)
    assert bool(jnp.isfinite(got).all()) and _gap(got, want) < 5e-6
    w = jax.random.normal(jax.random.PRNGKey(9), got.shape)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * w),
                               argnums=(0, 1, 2, 3, 4))(*args)
    for name, dgot, dwant in zip("q k v g beta".split(), grads(kda_chunk),
                                 grads(kda_recurrence)):
        assert bool(jnp.isfinite(dgot).all()), name
        # at the floor the decay's own gradient is exp(-5) small: held to
        # an absolute gap, not to itself
        assert float(jnp.max(jnp.abs(dgot - dwant))) < 2e-5, name
    # and in the type the model computes in, nothing overflows either
    low = jax.jit(kda_chunk)(*_inputs(2 * CHUNK, seed=2, dtype=jnp.bfloat16,
                                      floor=True))
    assert low.dtype == jnp.bfloat16 and bool(jnp.isfinite(low).all())
    assert _gap(low.astype(jnp.float32), want) < 0.05


def test_no_decay_and_no_write_are_the_limits():
    """g = 0 and beta = 0 leave the state as it is: from an empty state
    nothing is read. beta = 0 alone after a first chunk: the state only
    decays, so later outputs shrink by the decay."""
    q, k, v, g, beta = _inputs(2 * CHUNK, seed=3)
    none = kda_chunk(q, k, v, jnp.zeros_like(g), jnp.zeros_like(beta))
    assert not bool(jnp.any(none))
    beta = beta.at[:, CHUNK:].set(0.0)
    got, want = kda_chunk(q, k, v, g, beta), kda_recurrence(q, k, v, g, beta)
    assert _gap(got, want) < 5e-6


def test_bfloat16_products_stay_near_float32():
    args32 = _inputs(3 * CHUNK, seed=4)
    args16 = _inputs(3 * CHUNK, seed=4, dtype=jnp.bfloat16)
    got = jax.jit(kda_chunk)(*args16).astype(jnp.float32)
    assert _gap(got, kda_recurrence(*args32)) < 0.03


def _loops_and_inversions(fn, args):
    """The ``while`` loops and triangular solves of ``fn``'s program as it
    is lowered for the TPU (no compiler has run: nothing is folded away)."""
    text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)) \
        .as_text()
    return (text.count("stablehlo.while"),
            text.count("stablehlo.triangular_solve"))


@pytest.mark.parametrize("T", [2 * CHUNK, 100])
def test_the_backward_pass_makes_no_forward_of_its_own(T):
    """The forward is one scan over the chunks and one inversion of their
    triangles; its gradient adds the scan's transpose and nothing else. (A
    loss that reads the output, so the first forward is live; with
    ``jax.checkpoint`` round the whole operator the gradient held three
    loops and four solves: forward, forward again, two in the transpose.)"""
    args = _inputs(T)
    loss = lambda *a: jnp.sum(jnp.square(kda_chunk(*a)))
    assert _loops_and_inversions(kda_chunk, args) == (1, 1)
    assert _loops_and_inversions(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)), args) == (2, 1)


@pytest.mark.parametrize("at", range(5), ids="q k v g beta".split())
def test_bfloat16_gradients_stay_near_float32(at):
    """As ``test_bfloat16_products_stay_near_float32``, a gradient an
    input: the kept values are the forward's own, in its own types, so the
    backward pass adds the rounding of its own bfloat16 products and no
    more: the forward's 0.03 of the largest value, here of the gradient
    (0.005 to 0.011 on three seeds, as with the forward made twice)."""
    T = 3 * CHUNK
    w = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, V))
    grad = lambda f, args: jax.jit(jax.grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w), argnums=at))(*args)
    got = grad(kda_chunk, _inputs(T, seed=4, dtype=jnp.bfloat16))
    want = grad(kda_recurrence, _inputs(T, seed=4))
    assert got.dtype == (jnp.float32 if at > 2 else jnp.bfloat16)
    assert _gap(got.astype(jnp.float32), want) < 0.03


@pytest.mark.parametrize("n", [CHUNK, 24])
def test_the_solves_backward_is_two_products_with_the_inverse(n):
    """``_solve_transposed`` (the backward pass about the kept inverse and
    the solution) against JAX's own through ``triangular_solve``."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    A = jnp.tril(jax.random.normal(ks[0], (3, 2, n, n)), -1) * 0.5
    rhs = jax.random.normal(ks[1], (3, 2, n, 2 * n))
    eye = jnp.broadcast_to(jnp.eye(n), A.shape)
    solve = lambda A, rhs: lax.linalg.triangular_solve(
        A + eye, rhs, left_side=True, lower=True, unit_diagonal=True)
    solved, solve_vjp = jax.vjp(solve, A, rhs)
    dsolved = jax.random.normal(ks[2], solved.shape)
    dA, drhs = _solve_transposed(solve(A, eye), solved, dsolved)
    want_dA, want_drhs = solve_vjp(dsolved)
    assert not bool(jnp.any(jnp.triu(dA)))
    assert _gap(dA, jnp.tril(want_dA, -1)) < 1e-5
    assert _gap(drhs, want_drhs) < 1e-5


def test_a_layer_keeps_what_it_chose_to():
    """At the Ling cell's shapes (one sequence of 4096, 8 heads of 128,
    bfloat16 q, k, v) the backward pass is left, beside the five inputs
    the layer holds anyway, the chunks' inverses and the state each chunk
    starts from, both float32: 8.4 and 33.6 MB (250 MB a layer, 1.5 GB over
    the cell's six, is the most the step has room for)."""
    S, heads, width = 4096, 8, 128
    low = jax.ShapeDtypeStruct((1, S, heads, width), jnp.bfloat16)
    kept = saved_residuals(
        kda_chunk, low, low, low,
        jax.ShapeDtypeStruct((1, S, heads, width), jnp.float32),
        jax.ShapeDtypeStruct((1, S, heads), jnp.float32))
    own = [a for a, why in kept if "from the argument" not in why]
    assert len(kept) - len(own) == 5
    chunks = S // CHUNK
    assert sorted((a.shape, a.dtype) for a in own) == [
        ((1, heads, chunks, CHUNK, CHUNK), jnp.float32),
        ((chunks, 1, heads, width, width), jnp.float32)]
    assert sum(a.size * a.dtype.itemsize for a in own) == 41_943_040
