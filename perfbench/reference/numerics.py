"""What every family's plain reference computes with: the matmul each
product goes through, and AdamW written out.

``mm_highest`` is the reference's: float32 at ``highest`` precision (on a
TPU a float32 matmul is otherwise several bf16 passes short of float32).
``mm_int8`` is the control of "How correct is decided": the same
mathematics with both operands of every matmul rounded to int8 (absmax,
per row of the left and per column of the right), forward and backward:
the precision below bf16 that a later PR would be tempted by. Nothing
here imports the program or any family.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def mm_highest(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _q8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


@jax.custom_vjp
def mm_int8(a, b):
    return jnp.matmul(_q8(a, -1), _q8(b, -2), precision=HIGHEST)


def _mm_int8_fwd(a, b):
    return mm_int8(a, b), (a, b)


def _mm_int8_bwd(res, g):
    a, b = res
    bt, at = jnp.swapaxes(b, -1, -2), jnp.swapaxes(a, -1, -2)
    da = jnp.matmul(_q8(g, -1), _q8(bt, -2), precision=HIGHEST)
    db = jnp.matmul(_q8(at, -1), _q8(g, -2), precision=HIGHEST)
    # b may be a plain [K, N] weight under a batched a: sum the batch out.
    while db.ndim > b.ndim:
        db = db.sum(0)
    return da, db


mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)

MATMULS = {"highest": mm_highest, "int8": mm_int8}


def learning_rate(hp, count):
    """The rate of update number ``count`` (from 1): the configuration's,
    or under ``warmup_steps`` its linear warm-up from 0, which update
    ``warmup_steps + 1`` is the first to take whole."""
    if "warmup_steps" not in hp:
        return hp["learning_rate"]
    return hp["learning_rate"] * jnp.minimum(
        1.0, (count - 1) / hp["warmup_steps"])


def adamw_step(params, mu, nu, grads, count, hp):
    """One AdamW update as published (decoupled weight decay, bias
    correction), count = the number of this update, from 1."""
    rate = learning_rate(hp, count)
    b1, b2 = hp["b1"], hp["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count

    def upd(p, m, n):
        step = (m / c1) / (jnp.sqrt(n / c2) + hp["eps"])
        return p - rate * (step + hp["weight_decay"] * p)

    return jax.tree.map(upd, params, mu, nu), mu, nu
