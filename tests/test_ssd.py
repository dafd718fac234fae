"""The chunked Mamba-2 scan (``ops/ssd.py``) against the recurrence it
computes, position by position in float32, forward and through
``jax.grad``, on the CPU at small widths: several chunks, groups of
several heads, lengths that are no whole number of chunks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops.ssd import ssd_chunk

B, H, P, G, N = 2, 6, 8, 3, 16
NAMES = "x dt a b c d".split()


def ssd_recurrence(x, dt, a, b, c, d):
    """The same operator position by position, in float32: what
    ``ssd_chunk`` is held to. Shapes as ``ssd_chunk``'s."""
    f32 = jnp.float32
    per_group = x.shape[2] // b.shape[2]
    b, c = (jnp.repeat(v.astype(f32), per_group, axis=2) for v in (b, c))
    x, dt = x.astype(f32), dt.astype(f32)

    def step(S, at):
        x, dt, b, c = at                          # [B,H,P] [B,H] [B,H,N] x2
        S = jnp.exp(dt * a)[..., None, None] * S \
            + (dt[..., None] * x)[..., None] * b[..., None, :]
        return S, jnp.sum(S * c[..., None, :], axis=-1) + d[:, None] * x

    S0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], f32)
    _, y = lax.scan(step, S0, tuple(jnp.moveaxis(v, 1, 0)
                                    for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def _inputs(T, seed=0, dtype=jnp.float32, rate=16.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 2.0)
    a = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=rate)
    b = jax.random.normal(ks[3], (B, T, G, N))
    c = jax.random.normal(ks[4], (B, T, G, N))
    d = jax.random.normal(ks[5], (H,))
    return x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype), d


def _gap(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


# a whole chunk, several, and lengths the operator pads behind
@pytest.mark.parametrize("T,chunk", [(16, 16), (80, 16), (100, 16), (7, 16),
                                     (300, 128), (128, 128)])
def test_forward_is_the_recurrence(T, chunk):
    args = _inputs(T)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: ssd_chunk(*a, chunk))(*args)
    want = ssd_recurrence(*args)
    assert got.shape == want.shape == (B, T, H, P)
    assert _gap(got, want) < 5e-6


@pytest.mark.parametrize("T,chunk", [(64, 16), (100, 16), (200, 128)])
def test_every_gradient_is_the_recurrences(T, chunk):
    args = _inputs(T, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, P))
    grads = lambda f: jax.jit(jax.grad(
        lambda *a: jnp.sum(f(*a) * w), argnums=tuple(range(6))))(*args)
    with jax.default_matmul_precision("highest"):
        got = grads(lambda *a: ssd_chunk(*a, chunk))
    # float32 sums in another order; ``a``'s is one number a head summed
    # over every position, with cancellation
    for name, g, want in zip(NAMES, got, grads(ssd_recurrence)):
        assert _gap(g, want) < 2e-4, name


def test_a_steep_decay_over_whole_chunks_neither_overflows_nor_leaks():
    """``dt A`` of -40 a position for two whole chunks of 128: every
    exponent is a difference taken before its exponential, so nothing
    overflows and the state is gone within a position, in float32 and in
    the type the model computes in."""
    x, dt, a, b, c, d = _inputs(256, seed=2)
    dt, a = jnp.full_like(dt, 2.0), jnp.full_like(a, -20.0)
    got = jax.jit(ssd_chunk)(x, dt, a, b, c, d)
    want = ssd_recurrence(x, dt, a, b, c, d)
    assert bool(jnp.isfinite(got).all()) and _gap(got, want) < 5e-6
    grads = jax.grad(lambda *v: jnp.sum(ssd_chunk(*v)),
                     argnums=tuple(range(6)))(x, dt, a, b, c, d)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    low = jax.jit(ssd_chunk)(x.astype(jnp.bfloat16), dt, a,
                             b.astype(jnp.bfloat16), c.astype(jnp.bfloat16),
                             d)
    assert low.dtype == jnp.bfloat16 and bool(jnp.isfinite(low).all())


def test_no_step_leaves_the_state_and_the_skip_alone_reads_the_input():
    """dt = 0 writes nothing and decays nothing: from an empty state only
    the skip ``D x`` comes out. dt = 0 after a first chunk: the state
    stays, and later positions read it undecayed."""
    x, dt, a, b, c, d = _inputs(48, seed=3)
    none = ssd_chunk(x, jnp.zeros_like(dt), a, b, c, d, 16)
    np.testing.assert_allclose(none, d[:, None] * x, rtol=1e-6, atol=1e-6)
    dt = dt.at[:, 16:].set(0.0)
    with jax.default_matmul_precision("highest"):
        got = ssd_chunk(x, dt, a, b, c, d, 16)
    assert _gap(got, ssd_recurrence(x, dt, a, b, c, d)) < 5e-6


def test_the_chunk_changes_no_value_and_groups_share_b_and_c():
    args = _inputs(96, seed=4)
    with jax.default_matmul_precision("highest"):
        small, large = ssd_chunk(*args, 16), ssd_chunk(*args, 32)
        assert _gap(small, large) < 5e-6
        # a head reads its group's B and C: with every group's alike, the
        # grouping is no matter
        x, dt, a, b, c, d = args
        b, c = (jnp.broadcast_to(v[:, :, :1], v.shape) for v in (b, c))
        one = ssd_chunk(x, dt, a, b[:, :, :1], c[:, :, :1], d, 16)
        assert _gap(ssd_chunk(x, dt, a, b, c, d, 16), one) < 5e-6


def test_bfloat16_products_stay_near_float32():
    args32 = _inputs(300, seed=5)
    args16 = _inputs(300, seed=5, dtype=jnp.bfloat16)
    got = jax.jit(ssd_chunk)(*args16).astype(jnp.float32)
    assert _gap(got, ssd_recurrence(*args32)) < 0.03


def test_the_backward_pass_keeps_no_chunks_scores():
    """Under ``jax.checkpoint`` the residuals of the scan are its six
    inputs: no ``[chunk, chunk]`` array crosses from the forward to the
    backward pass."""
    args = _inputs(64, seed=6)
    _, vjp = jax.vjp(lambda *a: ssd_chunk(*a, 16), *args)
    kept = [v.shape for v in jax.tree.leaves(vjp)]
    assert all(s[-2:] != (16, 16) for s in kept if len(s) >= 2), kept
    assert sum(int(np.prod(s)) for s in kept) \
        <= 2 * sum(int(v.size) for v in args)
