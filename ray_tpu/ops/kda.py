"""Kimi Delta Attention's core as a chunked scan (Kimi Linear,
arXiv:2510.26692, section 3): the repo's first recurrent operator.

Per head a state ``S [K, V]`` in float32, from zero, a decay for every
channel of the keys and the delta rule:

    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

``g = log alpha <= 0`` per channel, ``b`` in (0, 1). ``kda_chunk`` computes
it in chunks of ``CHUNK`` positions. With ``G`` the running sum of ``g``
inside a chunk and ``S`` the state the chunk starts from, every position's
rank-one update is ``k_i u_i^T`` decayed onwards, where

    (I + A) U = b * (V - (K * exp(G)) S),
    A_ij = b_i sum_d k_id k_jd exp(G_id - G_jd)  for j < i, else 0

(the WY form: one unit-lower-triangular ``[CHUNK, CHUNK]`` solve a head and
chunk, in float32), and

    O  = (Q * exp(G)) S + tril(Aqk) U,   Aqk_ij = sum_d q_id k_jd exp(G_id - G_jd)
    S' = exp(G_last) * S + (K * exp(G_last - G))^T U.

Everything that does not read ``S`` (both score matrices, the solve) is
computed for all chunks at once; a ``lax.scan`` over the chunks carries
``S`` through three small products a chunk.

**The overflow this is built round.** The decay is per channel, so a score
is no product of two scaled rows unless ``exp(G_i - G_j)`` is split as
``exp(G_i - G_r) * exp(G_r - G_j)`` about a position ``r``, and the second
factor grows with ``j - r``: over a whole chunk of 64 at ``g = -5`` it is
``exp(315)``, no float32. A score row block of ``SUB`` = 16 positions is
therefore referred to its own first position ``r``: the rows' factor is at
most 1, the columns' is at most 1 before ``r`` (the off-diagonal blocks)
and at most ``exp(15 * 5) = exp(75)`` inside the block (the diagonal
block), which float32 and bfloat16 hold; columns after the block are zeroed
before the product, their exponent cut at ``_EXP_CAP``. So the safe range
is ``g >= -5``, the lower bound the model's gate has
(``TransformerConfig.kda_gate_floor``); a steeper decay needs a smaller
``SUB``.

Products take their operands in the type of ``q`` (bfloat16 in the model,
float32 in the tests) and accumulate in float32; ``g``, ``b``, the running
sums, the solve and the state are float32.

**The backward pass makes no forward of its own.** The operator is one
``jax.custom_vjp``. Beside its five inputs a layer keeps two things the
forward made, each in the forward's own type (float32): the chunks'
inverses ``(I + A)^-1`` and the state each chunk starts from. At 4096
positions of 8 heads of 128 that is 8.4 + 33.6 MB a layer. The backward
pass inverts no triangle and carries no state forward again. What is
cheap it makes again from the inputs, exactly as the forward made it: the
running sums, the scalings, both score products (0.5 GFLOP of bfloat16
beside the passes that make their operands, which the transposes read
anyway), the right-hand sides, their product with the kept inverse, and
inside the loop a chunk's ``U`` from the kept state. JAX transposes those
pieces (``jax.vjp`` of ``_operands`` and of ``_chunk``); the solve's own
transpose is two products with the inverse (``_solve_transposed``). The
choice of what to keep is by measurement on the chip (``PERF.md``, PR 44):
keeping the scan's operands, the scores and the right-hand sides as well
(97 MB a layer more) read the same rate to 0.07 % and compiled to 0.73 GiB
more; keeping the running sums as well read 2.7 % slower end to end.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64
SUB = 16
_EXP_CAP = 80.0     # exp(80) < float32's largest; only masked columns meet it


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _dot_highest(a, b, contract):
    """A float32 product over the leading axes both share, at the precision
    the triangular solve's own product has."""
    batch = tuple(range(a.ndim - 2))
    return lax.dot_general(a, b, (contract, (batch, batch)),
                           precision=lax.Precision.HIGHEST)


def _scores(rows, cols):
    """[..., n, SUB, K] x [..., n, CHUNK, K] -> [..., CHUNK, CHUNK]."""
    nb = rows.ndim - 2
    batch = tuple(range(nb))
    s = _dot(rows, cols, (((nb + 1,), (nb + 1,)), (batch, batch)))
    return s.reshape(s.shape[:-3] + (CHUNK, CHUNK))


def kda_chunk(q, k, v, g, beta):
    """q, k ``[B, T, H, K]``, v ``[B, T, H, V]``, g ``[B, T, H, K]``
    float32 (the log-decay, in ``[-5, 0]``), beta ``[B, T, H]`` float32 ->
    o ``[B, T, H, V]`` in the type of ``v``. A ``T`` that is no multiple
    of ``CHUNK`` is padded behind with positions that leave the state as it
    is (``b = 0``, ``g = 0``), and their outputs are dropped."""
    T = q.shape[1]
    pad = -T % CHUNK
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                    (a.ndim - 2)) for a in (q, k, v, g, beta))
    o = _whole_chunks(q, k, v, g, beta)
    return o[:, :T] if pad else o


@jax.custom_vjp
def _whole_chunks(q, k, v, g, beta):
    """``kda_chunk`` on whole chunks; its backward pass is ``_backward``."""
    return _forward(q, k, v, g, beta)[0]


def _forward(q, k, v, g, beta):
    """-> o, and what the backward pass is left."""
    A, *operands = _operands(q, k, v, g, beta)
    eye = jnp.broadcast_to(jnp.eye(CHUNK, dtype=A.dtype), A.shape)
    # one diagonal block a triangle: the solve of the identity is the inverse
    inverse = lax.linalg.triangular_solve(
        A, eye, left_side=True, lower=True, unit_diagonal=True)
    xs = _scanned(inverse, *operands)[1]

    def chunk(S, xs):
        after, o = _chunk(S, xs)
        return after, (o, S)

    B, _, H, V = v.shape
    start = jnp.zeros((B, H, q.shape[-1], V), jnp.float32)
    _, (o, states) = lax.scan(chunk, start, xs)
    # [N, B, H, C, V] -> [B, T, H, V]
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(v.shape).astype(v.dtype)
    return o, (q, k, v, g, beta, inverse, states)


def _backward(kept, do):
    """The transpose of ``_forward`` about what it kept."""
    *inputs, inverse, states = kept
    (_, *operands), operands_vjp = jax.vjp(_operands, *inputs)
    solved, xs = _scanned(inverse, *operands)
    B, _, H, V = do.shape
    do = jnp.moveaxis(do.astype(jnp.float32).reshape(B, -1, CHUNK, H, V),
                      (1, 3), (0, 2))

    def chunk(dS, at):
        S, xs, do = at
        _, chunk_vjp = jax.vjp(_chunk, S, xs)
        return chunk_vjp((dS, do))

    _, dxs = lax.scan(chunk, jnp.zeros_like(states[0]), (states, xs, do),
                      reverse=True)
    dW, dU0, *dxs = (jnp.moveaxis(a, 0, 2) for a in dxs)
    dsolved = jnp.concatenate([dW.astype(jnp.float32), dU0], axis=-1)
    dA, drhs = _solve_transposed(inverse, solved, dsolved)
    return operands_vjp((dA, drhs, *dxs))


_whole_chunks.defvjp(_forward, _backward)


def _scanned(inverse, rhs, *others):
    """``[W | U0] = (I + A)^-1 rhs`` in float32, and the scan's operands
    chunk by chunk ``[N, B, H, ...]``: ``W`` in the type of ``q``, ``U0``,
    and the others."""
    solved = _dot_highest(inverse, rhs, ((4,), (3,)))
    K = others[0].shape[-1]
    xs = (solved[..., :K].astype(others[0].dtype), solved[..., K:], *others)
    return solved, tuple(jnp.moveaxis(a, 2, 0) for a in xs)


def _solve_transposed(inverse, solved, dsolved):
    """The backward pass of ``solved = (I + A)^-1 rhs`` about the inverse
    and the solution: ``drhs = (I + A)^-T dsolved`` and ``dA = -drhs
    solved^T`` below the diagonal. Two products; nothing is solved."""
    nb = inverse.ndim - 2
    drhs = _dot_highest(inverse, dsolved, ((nb,), (nb,)))
    dA = _dot_highest(drhs, solved, ((nb + 1,), (nb + 1,)))
    at = jnp.arange(inverse.shape[-1])
    return jnp.where(at[:, None] > at[None, :], -dA, 0.0), drhs


def _chunk(S, xs):
    """One chunk of every head from the state it starts at."""
    W, U0, q_in, Aqk, k_out, keep = xs                     # [B,H,C,...]
    dt = W.dtype
    Sd = S.astype(dt)
    bh = ((0, 1), (0, 1))
    U = U0 - _dot(W, Sd, (((3,), (2,)), bh))               # [B,H,C,V]
    Ud = U.astype(dt)
    o = _dot(q_in, Sd, (((3,), (2,)), bh)) \
        + _dot(Aqk, Ud, (((3,), (2,)), bh))
    return keep[..., None] * S + _dot(k_out, Ud, (((2,), (2,)), bh)), o


def _operands(q, k, v, g, beta):
    """Everything of every chunk that does not read the state, ``[B, H, N,
    CHUNK, ...]``: ``A``, the right-hand sides ``b * [K exp(G) | V]``
    (float32), and what the scan reads beside the solution: ``Q exp(G)``,
    ``tril(Aqk)``, ``K exp(G_last - G)`` (in the type of ``q``) and
    ``exp(G_last)``."""
    B, T, H, K = q.shape
    dt, f32 = q.dtype, jnp.float32
    N, n = T // CHUNK, CHUNK // SUB

    def chunks(a):          # [B, T, H, ...] -> [B, H, N, CHUNK, ...]
        a = a.reshape((B, N, CHUNK, H) + a.shape[3:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    G = jnp.cumsum(g.astype(f32), axis=3)                  # [B,H,N,C,K]
    Gs = G.reshape(B, H, N, n, SUB, K)
    first = Gs[..., :1, :]                                 # [B,H,N,n,1,K]
    # rows of block a about its first position; columns up to block a's end
    row_scale = jnp.exp(Gs - first)                        # <= 1
    ahead = first - G[:, :, :, None]                       # [B,H,N,n,C,K]
    inside = (jnp.arange(CHUNK)[None, :]
              < (jnp.arange(n)[:, None] + 1) * SUB)[..., None]
    col_scale = jnp.where(inside, jnp.exp(jnp.minimum(ahead, _EXP_CAP)), 0.0)
    k32, q32 = k.astype(f32), q.astype(f32)
    cols = (k32[:, :, :, None] * col_scale).astype(dt)
    k_rows = (k32.reshape(Gs.shape) * row_scale).astype(dt)
    q_rows = (q32.reshape(Gs.shape) * row_scale).astype(dt)
    at = jnp.arange(CHUNK)
    below = at[:, None] > at[None, :]
    b = beta.astype(f32)[..., None]                        # [B,H,N,C,1]
    A = jnp.where(below, b * _scores(k_rows, cols), 0.0)
    Aqk = jnp.where(below | (at[:, None] == at[None, :]),
                    _scores(q_rows, cols), 0.0)
    decay = jnp.exp(G)
    # (I + A) [W | U0] = b * [K exp(G) | V]
    rhs = jnp.concatenate([b * k32 * decay, b * v.astype(f32)], axis=-1)
    last = G[:, :, :, -1:, :]                              # [B,H,N,1,K]
    return (A, rhs, (q32 * decay).astype(dt), Aqk.astype(dt),
            (k32 * jnp.exp(last - G)).astype(dt), jnp.exp(last[..., 0, :]))
