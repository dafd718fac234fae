"""Grouped matrix products for an expert layer: rows sorted by group, one
weight matrix a group.

``grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G]) -> [M, N]``
multiplies the first ``group_sizes[0]`` rows of ``lhs`` with ``rhs[0]``, the
next ``group_sizes[1]`` with ``rhs[1]``, and so on. The groups may cover
fewer than M rows (an expert layer that holds a share of the experts sorts
the other experts' pairs behind its own): rows behind the last group are
UNDEFINED in the result, and in the gradient with respect to ``lhs``, and
what ``lhs`` and the result's cotangent hold there is never read into a row
of a group (the kernels select a tile's rows by its group, they do not
weigh them). Nobody masks them: the expert layer's other passes
(``moe_rows.py``) stop at the last row tile the groups touch, as these
kernels do, and ``parallel/moe.py`` selects by the held pairs where a
whole array is read.

On an accelerator the products are the grouped-matmul Pallas kernels that
ship with JAX (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` for
the product and for the gradient of ``lhs``, ``tgmm`` for the gradient of
``rhs``). Their grid's row dimension is the number of row tiles the groups
touch, read from ``group_sizes`` when the kernel starts, so the time follows
the rows routed here and not M. Each call is named (``KERNELS`` of
``util/profiling.py``: ``moe_gmm``, ``moe_tgmm``), which ``lax.ragged_dot``
cannot be: XLA's own expansion of it drops the operation's name, and a
profile then cannot say whose time it is. On the CPU backend, where the
tests run, the product is ``lax.ragged_dot``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import backend

# Rows, contraction, columns of one tile. 512 rows: a group of an expert
# layer at even routing is about one tile, and a tile's weights (512 x 512)
# are read once for 512 rows, which balances the v5e's MXU against its HBM.
TILING = (512, 512, 512)


def kernel_accepts(m: int, k: int, n: int) -> bool:
    """Whole tiles only: the kernels mask a ragged k, not a ragged m."""
    return m % TILING[0] == 0 and k % 128 == 0 and n % 128 == 0


def grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    m, k = lhs.shape
    if backend.on_cpu() or not kernel_accepts(m, k, rhs.shape[2]):
        return lax.ragged_dot(lhs, rhs, group_sizes)
    return _grouped(lhs, rhs, group_sizes.astype(jnp.int32))


def _gmm(lhs, rhs, group_sizes, transpose_rhs=False):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    with jax.named_scope("moe_gmm"):
        return gmm(lhs, rhs, group_sizes, lhs.dtype, TILING,
                   transpose_rhs=transpose_rhs)


def _tgmm(lhs, grad, group_sizes, dtype):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    with jax.named_scope("moe_tgmm"):
        return tgmm(lhs.swapaxes(0, 1), grad, group_sizes, dtype, TILING)


@jax.custom_vjp
def _grouped(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes)


def _grouped_fwd(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_bwd(res, grad):
    lhs, rhs, group_sizes = res
    return (_gmm(grad, rhs, group_sizes, transpose_rhs=True),
            _tgmm(lhs, grad, group_sizes, rhs.dtype), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)
