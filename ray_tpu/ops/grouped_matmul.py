"""Grouped matrix products for an expert layer: rows sorted by group, one
weight matrix a group.

``grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G]) -> [M, N]``
multiplies the first ``group_sizes[0]`` rows of ``lhs`` with ``rhs[0]``, the
next ``group_sizes[1]`` with ``rhs[1]``, and so on. The groups may cover
fewer than M rows (an expert layer that holds a share of the experts sorts
the other experts' pairs behind its own): rows behind the last group are
UNDEFINED in the result, and in the gradient with respect to ``lhs``, and
what ``lhs`` and the result's cotangent hold there, finite or not, is never
read into a row of a group (the kernels select a tile's rows by its group,
they do not weigh them). Nobody masks them: the expert layer's other passes
(``moe_rows.py``) stop at the last row tile the groups touch, as these
kernels do, and ``parallel/moe.py`` selects by the held pairs where a
whole array is read.

On an accelerator the products are two Pallas kernels, each call named
(``KERNELS`` of ``util/profiling.py``), which ``lax.ragged_dot`` cannot be:
XLA's own expansion of it drops the operation's name, and a profile then
cannot say whose time it is. Both walk the *visits* of the groups, the
(group, row tile) pairs in which a row tile holds a row of the group, in
the groups' order; their number is read from ``group_sizes`` when the
kernel starts, so the time follows the rows routed here and not M.

``moe_gmm`` (the product, and the gradient of ``lhs`` with ``rhs``
transposed in the kernel): a grid of (column tiles, visits). A step holds
the whole contraction, so a group's ``[K, tn]`` weights keep one block index
over the group's consecutive visits and are copied into VMEM once a column
tile, whatever the row tile; one dot, stored whole where the tile lies
inside the group and selected into the tile's other rows where a group
begins or ends in it.

``moe_tgmm`` (the gradient of ``rhs``): a grid of (column tiles,
contraction tiles, visits), a float32 ``[tk, tn]`` accumulator over a
group's visits, the rows contracted as they lie (dimension 0 of both
tiles, nothing transposed by hand). Only a visit whose tile a group does
not fill masks its rows, in both operands: behind the last group either
may hold anything.

The tiles come from the shapes (``TM``, ``_gmm_columns``, ``_tgmm_tile``).
On the CPU backend, where the tests run, the product is ``lax.ragged_dot``;
the tests of the kernels themselves interpret them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import backend
from ray_tpu.ops.moe_rows import ROW_TILE

# Rows of one visit. A group begins and ends anywhere, so a tile in which
# one group ends and the next begins is visited by both, and a visit
# multiplies all its rows: G groups over R routed rows multiply the tiles
# R touches and G - 1 more, about R + G * TM rows. 128 is the MXU's own
# height and divides ``ROW_TILE``, so no visit reaches behind the last
# tile the row passes worked. The other tile sizes are the widest that fit: on the
# v5e, at the LFM2 cell's widths, every tiling from 128 to 512 rows and
# from 512 columns to all of them ran, and at 128 rows each wider tile was
# faster (``PERF.md``, PR 36).
TM = 128

# What a kernel's pipelined blocks (two copies each) and its float32 tile
# of results may take of a v5e's 128 MiB of VMEM, and what the compiler is
# told to grant the kernel (16 MiB unless told).
_VMEM_BLOCKS = 40 * 2**20
_VMEM_LIMIT = 64 * 2**20


def kernel_accepts(m: int, k: int, n: int) -> bool:
    """Whole row tiles of the row passes, whole lanes: nothing ragged."""
    return m % ROW_TILE == 0 and k % 128 == 0 and n % 128 == 0


def _widest(n: int, fits) -> int:
    """The widest multiple of 128 that divides ``n`` and ``fits``."""
    return max((t for t in range(128, n + 1, 128)
                if n % t == 0 and fits(t)), default=128)


def _gmm_columns(k: int, n: int) -> int:
    """Columns of a ``moe_gmm`` step that contracts ``k`` into ``n``: a
    group's ``[k, tn]`` weights are copied once whatever ``tn``, and the
    rows once a column tile, so the widest tile that fits is the least
    traffic and the fewest steps."""
    return _widest(n, lambda tn: 4 * (TM * k + k * tn + TM * tn)
                   + 4 * TM * tn <= _VMEM_BLOCKS)


def _tgmm_tile(k: int, n: int) -> tuple[int, int]:
    """(tk, tn), the output tile of ``moe_tgmm`` for ``[., k]`` rows
    against ``[., n]`` cotangents: a step reads ``TM * (tk + tn)`` for
    ``2 * TM * tk * tn`` operations, so the largest that fits, columns
    first."""
    def need(tk, tn):
        return 4 * (TM * tk + TM * tn + tk * tn) + 4 * tk * tn
    tn = _widest(n, lambda tn: need(128, tn) <= _VMEM_BLOCKS)
    return _widest(k, lambda tk: need(tk, tn) <= _VMEM_BLOCKS), tn


def grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    m, k = lhs.shape
    if backend.on_cpu() or not kernel_accepts(m, k, rhs.shape[2]):
        return lax.ragged_dot(lhs, rhs, group_sizes)
    return _grouped(lhs, rhs, group_sizes.astype(jnp.int32))


# ------------------------------------------------------------------ visits
def _visits(group_sizes, m: int, empty: bool):
    """The kernels' walk over ``group_sizes``: ``(offsets [G + 1], group
    [V], tile [V]), count``. Visit v < count works on row tile ``tile[v]``
    for group ``group[v]``, whose rows are ``offsets[g]:offsets[g + 1]``;
    V = m // TM + G - 1 is the most there can be. A group's visits are
    consecutive and so are a tile's. ``empty``: a group without rows gets
    one visit all the same (``moe_tgmm`` writes its zeros there)."""
    groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // TM
    tiles = jnp.where(group_sizes > 0, (ends + TM - 1) // TM - first,
                      1 if empty else 0)
    most = m // TM + groups - 1
    group = jnp.repeat(jnp.arange(groups, dtype=jnp.int32), tiles,
                       total_repeat_length=most)
    before = jnp.cumsum(tiles) - tiles       # visits of the groups before
    nth = jnp.arange(most, dtype=jnp.int32) - before[group]
    tile = jnp.minimum(first[group] + nth, m // TM - 1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets.astype(jnp.int32), group, tile.astype(jnp.int32)), \
        jnp.sum(tiles)


def rows_multiplied(group_sizes, m: int) -> jax.Array:
    """The rows the kernels multiply for these groups among ``m`` sorted
    rows: their visits times the rows of one."""
    sizes = jnp.asarray(group_sizes, jnp.int32)
    return _visits(sizes, m, empty=False)[1] * TM


def _rows_of_group(offsets, group, tile, v):
    """For visit ``v``: does the group fill its tile, and which of the
    tile's rows ``[TM, 1]`` are the group's."""
    g = group[v]
    start, end, row0 = offsets[g], offsets[g + 1], tile[v] * TM
    rows = row0 + lax.broadcasted_iota(jnp.int32, (TM, 1), 0)
    return (jnp.logical_and(start <= row0, row0 + TM <= end),
            jnp.logical_and(rows >= start, rows < end))


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


# ----------------------------------------------------------------- kernels
def _gmm(lhs, rhs, group_sizes, transpose_rhs=False):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _gmm_columns(k, n)
    metadata, count = _visits(group_sizes, m, empty=False)
    contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def kernel(offsets, group, tile, lhs_ref, rhs_ref, out_ref):
        whole, mine = _rows_of_group(offsets, group, tile, pl.program_id(1))
        product = lax.dot_general(lhs_ref[...], rhs_ref[...], contract,
                                  preferred_element_type=jnp.float32)

        @pl.when(whole)
        def _():
            out_ref[...] = product.astype(out_ref.dtype)

        @pl.when(jnp.logical_not(whole))
        def _():
            # the tile's other rows are another visit's, before or after
            out_ref[...] = jnp.where(
                mine, product, out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (None, tn, k), lambda j, v, offsets, group, tile: (group[v], j, 0))
    else:
        rhs_spec = pl.BlockSpec(
            (None, k, tn), lambda j, v, offsets, group, tile: (group[v], 0, j))
    call = pl.pallas_call(
        kernel,
        name="moe_gmm",
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, count),
            in_specs=[
                pl.BlockSpec((TM, k),
                             lambda j, v, offsets, group, tile: (tile[v], 0)),
                rhs_spec],
            out_specs=pl.BlockSpec(
                (TM, tn), lambda j, v, offsets, group, tile: (tile[v], j))),
        compiler_params=_params("parallel", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=2 * (m * k * (n // tn) + rhs.size + m * n)),
        interpret=backend.on_cpu(),
    )
    with jax.named_scope("moe_gmm"):
        return call(*metadata, lhs, rhs)


def _tgmm(lhs, grad, group_sizes, dtype):
    m, k = lhs.shape
    n = grad.shape[1]
    groups = group_sizes.shape[0]
    tk, tn = _tgmm_tile(k, n)
    metadata, count = _visits(group_sizes, m, empty=True)

    def kernel(offsets, group, tile, lhs_ref, grad_ref, out_ref, acc_ref):
        v = pl.program_id(2)
        g = group[v]
        whole, mine = _rows_of_group(offsets, group, tile, v)

        @pl.when(jnp.logical_or(v == 0, group[jnp.maximum(v - 1, 0)] != g))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def add(rows, cotangents):
            acc_ref[...] += lax.dot_general(
                rows, cotangents, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(whole)
        def _():
            add(lhs_ref[...], grad_ref[...])

        @pl.when(jnp.logical_not(whole))
        def _():     # an empty group's one visit adds zeros
            add(jnp.where(mine, lhs_ref[...], 0),
                jnp.where(mine, grad_ref[...], 0))

        last = pl.num_programs(2) - 1

        @pl.when(jnp.logical_or(v == last,
                                group[jnp.minimum(v + 1, last)] != g))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    call = pl.pallas_call(
        kernel,
        name="moe_tgmm",
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, k // tk, count),
            in_specs=[
                pl.BlockSpec((TM, tk), lambda j, i, v, offsets, group, tile:
                             (tile[v], i)),
                pl.BlockSpec((TM, tn), lambda j, i, v, offsets, group, tile:
                             (tile[v], j))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda j, i, v, offsets, group, tile:
                (group[v], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=2 * (m * k * (n // tn) + m * n * (k // tk)
                                + groups * k * n)),
        interpret=backend.on_cpu(),
    )
    with jax.named_scope("moe_tgmm"):
        return call(*metadata, lhs, grad)


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
