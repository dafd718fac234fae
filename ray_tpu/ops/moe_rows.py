"""Row passes of an expert layer that cost the rows routed here.

An expert layer that holds a share of the experts sorts its (token, expert)
pairs with its own experts' first (``parallel/moe.py``): of the M sorted
rows only the first ``n_rows`` are this chip's work, and M is the worst
case. Every pass here takes its trip count from ``n_rows`` on the device,
``worked_tiles(n_rows, tile)`` row tiles, as the grouped products beside it
(``grouped_matmul.py``) take theirs from ``group_sizes``. Arrays keep their
static M rows; a tile behind the last worked one is never read and never
written, so its rows are UNDEFINED in every ``[M, width]`` result here, as
they are in ``grouped_matmul``'s, and so are the rows behind ``n_rows`` in
the last worked tile. One program serves any ``n_rows`` from 0 to M.

``gather_rows(src [T, D], token [M], n_rows) -> [M, D]``: row i is
``src[token[i]]``. ``scatter_add_rows(rows [M, D], token, n_rows, T) ->
[T, D]``: the sum of ``rows[i]`` at ``token[i]`` over i < n_rows. Each is
the other's transpose, which is the only derivative written here. Both are
a ``lax.fori_loop`` over the worked tiles around XLA's own gather and
scatter-add of one tile: a traced trip count cannot be differentiated in
reverse, which is why the halves are explicit. (Measured on the v5e beside
Pallas kernels that copy row by row: the loop's gather as fast, its
scatter-add three times faster at an even share than a token-major kernel
that has to visit every pair to find the held ones; ``PERF.md``.)

``map_rows(fn, n_rows, *operands)`` applies a row-wise ``fn`` to the worked
tiles of ``[M, width]`` operands, a block of rows at a time, as one Pallas
call whose grid is the worked tiles; its derivative is ``jax.vjp`` of
``fn`` on the same blocks. ``twice`` hands a value to two readers so that
their cotangents are added over the worked tiles too.

Each pass is named (``KERNELS`` of ``util/profiling.py``). Whole tiles
only: other shapes take the plain form over every row, masked behind
``n_rows``. On the CPU backend, where the tests run, the loops are the
same program and the Pallas call is interpreted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import backend

# Rows of one trip of the gather and scatter-add loops and of one worked
# tile of the row-wise call: a trip costs 48-67 us on the v5e whatever it
# holds, so the tile is as tall as a group at even routing. The grouped
# products' own row tile (``grouped_matmul.TM``) divides it.
ROW_TILE = 512

# Bytes of the widest block ``map_rows`` hands ``fn``, counted at 32 bits
# an element: a handful of blocks, twice over for the pipeline, share the
# kernel's 16 MB of VMEM with ``fn``'s float32 values.
_BLOCK_BYTES = 512 * 1024


def worked_tiles(n_rows: jax.Array, tile: int = ROW_TILE) -> jax.Array:
    """Row tiles the first ``n_rows`` rows touch: every pass's trip count."""
    return (n_rows + (tile - 1)) // tile


def rows_accept(m: int, tile: int, *widths: int) -> bool:
    """Whole row tiles, and whole lanes where a compiler lays them out."""
    return m % tile == 0 and (
        backend.on_cpu() or all(w % 128 == 0 for w in widths))


def _live(first: jax.Array, count: int, n_rows: jax.Array) -> jax.Array:
    return (first + jnp.arange(count, dtype=jnp.int32) < n_rows)[:, None]


def gather_rows(src: jax.Array, token: jax.Array, n_rows: jax.Array, *,
                tile: int = ROW_TILE) -> jax.Array:
    m = token.shape[0]
    if not rows_accept(m, tile):
        return jnp.where(_live(0, m, n_rows), src[token], 0)
    return _gather(src, token, jnp.asarray(n_rows, jnp.int32), tile)


def scatter_add_rows(rows: jax.Array, token: jax.Array, n_rows: jax.Array,
                     num_tokens: int, *, tile: int = ROW_TILE) -> jax.Array:
    m = token.shape[0]
    if not rows_accept(m, tile):
        return jnp.zeros((num_tokens, rows.shape[1]), rows.dtype).at[
            token].add(jnp.where(_live(0, m, n_rows), rows, 0))
    return _scatter(rows, token, jnp.asarray(n_rows, jnp.int32), num_tokens,
                    tile)


def map_rows(fn, n_rows: jax.Array, *operands: jax.Array,
             tile: int = ROW_TILE):
    """``fn`` maps blocks ``(rows, width_i)`` of the operands to one block
    or a tuple of blocks ``(rows, width_o)``, each row from its own row
    (an operand of width 1 holds a number a row)."""
    if not rows_accept(operands[0].shape[0], tile,
                       *(a.shape[1] for a in operands if a.shape[1] > 1)):
        return fn(*operands)
    return _mapped(fn, tile, jnp.asarray(n_rows, jnp.int32), *operands)


def twice(x: jax.Array, n_rows: jax.Array, *, tile: int = ROW_TILE):
    """``(x, x)`` for two readers of ``x [M, width]``. JAX would add their
    cotangents over all M rows; this adds them over the worked tiles."""
    if not rows_accept(x.shape[0], tile, x.shape[1]):
        return x, x
    return _twice(x, jnp.asarray(n_rows, jnp.int32), tile)


# ------------------------------------------------- gather and scatter-add
def _unwritten(shape, dtype, n_rows):
    """An array nobody has written, made where ``n_rows`` is known.
    ``lax.empty`` is the same thing without the operand, and for want of
    one XLA moves it out of the layer loop around the caller and then
    copies all of it in every layer, to keep what the loop below writes
    into from being the same buffer twice."""
    return pl.pallas_call(
        lambda _n_rows, _out: None,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        interpret=backend.on_cpu(),
    )(n_rows.reshape(1))


def _gather_loop(src, token, n_rows, tile):
    def one_tile(i, out):
        at = lax.dynamic_slice(token, (i * tile,), (tile,))
        return lax.dynamic_update_slice(out, src[at], (i * tile, 0))

    with jax.named_scope("moe_gather_rows"):
        return lax.fori_loop(
            0, worked_tiles(n_rows, tile), one_tile,
            _unwritten((token.shape[0], src.shape[1]), src.dtype, n_rows))


def _scatter_loop(rows, token, n_rows, num_tokens, tile):
    def one_tile(i, out):
        at = lax.dynamic_slice(token, (i * tile,), (tile,))
        part = lax.dynamic_slice(rows, (i * tile, 0), (tile, rows.shape[1]))
        # selected, not weighed: a row behind n_rows may hold anything
        return out.at[at].add(
            jnp.where(_live(i * tile, tile, n_rows), part, 0))

    with jax.named_scope("moe_scatter_rows"):
        return lax.fori_loop(
            0, worked_tiles(n_rows, tile), one_tile,
            jnp.zeros((num_tokens, rows.shape[1]), rows.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather(src, token, n_rows, tile):
    return _gather_loop(src, token, n_rows, tile)


def _gather_fwd(src, token, n_rows, tile):
    return _gather_loop(src, token, n_rows, tile), (
        token, n_rows, src.shape[0])


def _gather_bwd(tile, res, grad):
    token, n_rows, num_tokens = res
    return _scatter_loop(grad, token, n_rows, num_tokens, tile), None, None


_gather.defvjp(_gather_fwd, _gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _scatter(rows, token, n_rows, num_tokens, tile):
    return _scatter_loop(rows, token, n_rows, num_tokens, tile)


def _scatter_fwd(rows, token, n_rows, num_tokens, tile):
    return _scatter_loop(rows, token, n_rows, num_tokens, tile), (
        token, n_rows)


def _scatter_bwd(num_tokens, tile, res, grad):
    token, n_rows = res
    return _gather_loop(grad, token, n_rows, tile), None, None


_scatter.defvjp(_scatter_fwd, _scatter_bwd)


# ---------------------------------------------------------------- row-wise
def _map_call(fn, tile, n_rows, operands):
    m = operands[0].shape[0]
    widest = max(4 * a.shape[1] for a in operands)
    rows = tile
    while rows * widest > _BLOCK_BYTES and rows % 32 == 0:
        rows //= 2
    outs, tree = jax.tree.flatten(jax.eval_shape(fn, *(
        jax.ShapeDtypeStruct((rows, a.shape[1]), a.dtype)
        for a in operands)))

    def kernel(*refs):
        values = fn(*(ref[...] for ref in refs[:len(operands)]))
        for ref, value in zip(refs[len(operands):], jax.tree.leaves(values)):
            ref[...] = value

    def block(a):
        return pl.BlockSpec((rows, a.shape[1]), lambda i: (i, 0))

    call = pl.pallas_call(
        kernel,
        name="moe_map_rows",
        grid=(worked_tiles(n_rows, tile) * (tile // rows),),
        in_specs=[block(a) for a in operands],
        out_specs=[block(o) for o in outs],
        out_shape=[jax.ShapeDtypeStruct((m, o.shape[1]), o.dtype)
                   for o in outs],
        interpret=backend.on_cpu(),
    )
    with jax.named_scope("moe_map_rows"):
        return jax.tree.unflatten(tree, call(*operands))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _mapped(fn, tile, n_rows, *operands):
    return _map_call(fn, tile, n_rows, operands)


def _mapped_fwd(fn, tile, n_rows, *operands):
    return _map_call(fn, tile, n_rows, operands), (n_rows, operands)


def _mapped_bwd(fn, tile, res, grads):
    n_rows, operands = res
    cotangents, tree = jax.tree.flatten(grads)

    def transposed(*blocks):
        return jax.vjp(fn, *blocks[:len(operands)])[1](
            jax.tree.unflatten(tree, blocks[len(operands):]))

    return (None,) + _map_call(transposed, tile, n_rows,
                               operands + tuple(cotangents))


_mapped.defvjp(_mapped_fwd, _mapped_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _twice(x, n_rows, tile):
    return x, x


def _twice_fwd(x, n_rows, tile):
    return (x, x), n_rows


def _twice_bwd(tile, n_rows, grads):
    return _map_call(jnp.add, tile, n_rows, grads), None


_twice.defvjp(_twice_fwd, _twice_bwd)
