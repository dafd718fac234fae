"""The expert layer as published (``route``, ``held_experts``; the training
body of ``models/transformer.py``). Scores are a sigmoid or a softmax of a
float32 router product; the k experts of a token are the top k of scores
plus a selection bias (among the groups of experts kept for the token,
where the router limits its choice to some), and their gates the scores
themselves, renormalised over the k where the model says so. No token is
dropped, under any imbalance, at static shapes: the T x k (token, expert)
pairs are sorted by expert, the held experts' in the order the layer is told
it holds them, an expert's by token, and the pairs of experts this chip does
not hold after them, by token and then by the place the top k gave each.
``route`` fetches and puts no value by index, forward or backward: the
compiler moves one scalar at a time through a gather or a scatter (8-12 ns
each on a v5e, more than everything else the router does), so a pair's gate
is selected out of its token's row of scores by a dense comparison, an
expert's place among the held ones by comparisons with their ids, and the
gates ride the pairs' sort as an operand, as their cotangents ride a sort
back. Every pass over the sorted rows stops at the last row tile the held
groups touch: the products of an expert (three of a SwiGLU, two where it
is two matrices with a squared ReLU between) run grouped over the held
groups (``ops/grouped_matmul.py``), and the gather before them, the
row-wise stages between them and the weighted scatter-add after them take
the same trip count from the same group sizes (``ops/moe_rows.py``,
``rows_worked``). So the layer's cost follows the pairs routed here, from
none to all T x k of them, as one program. The layer is told which experts
it holds, routes over all of them and returns its own experts' part of the
result. On one chip nothing is exchanged, and nothing stands in for the
absent chips.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import moe_rows
from ray_tpu.ops.grouped_matmul import grouped_matmul
from ray_tpu.ops.moe_rows import ROW_TILE


class Routing(NamedTuple):
    """The (token, expert) pairs of one expert layer, sorted by expert with
    the held experts' pairs first, in the order ``experts_held`` gives: the
    first M = T * min(k, experts held) of them, which hold every held pair."""
    token: jax.Array        # [M] int32: the token of each sorted pair
    gate: jax.Array         # [M] float32: its gate
    held: jax.Array         # [M] bool: the pair's expert lives here
    group_sizes: jax.Array  # [experts held] int32: pairs of each
    experts: jax.Array      # [T, k] int32: the experts each token chose
    gates: jax.Array        # [T, k] float32: and their gates, unsorted


def route(h: jax.Array, router: jax.Array, bias: Optional[jax.Array], *,
          experts_held: Tuple[int, ...], k: int, score: str = "softmax",
          norm_topk: bool = False, scale: float = 1.0, n_group: int = 1,
          topk_group: int = 1, keep_input: bool = True) -> Routing:
    """h [T, D], router [D, E] (E the router's published width), bias [E]
    or None -> the layer's routing. The router product is float32 at
    ``highest``: a bf16 product flips near-ties of the top k. The bias
    selects and does not weigh, and takes no gradient. With ``n_group``
    above 1 the choice is group-limited (``_kept_groups``). The backward
    pass is left the float32 copy of ``h`` that the product reads, or with
    ``keep_input`` false makes it again from ``h``.

    The sorted pairs come by group (a held expert's place in
    ``experts_held``; one group more, behind them, for every other expert)
    and inside a group in the order token by token, a token's by the place
    the top k gave them: a held expert's rows are its tokens in rising
    order. No value is fetched or put by index on the way, in either pass
    (``_chosen``, ``_sorted_pairs``), and nothing of [T, k, E] waits for the
    backward pass."""
    T = h.shape[0]

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
        select = _kept_groups(select, n_group, topk_group)
    _, experts = lax.top_k(select, k)                           # [T, k]
    gates = _chosen(scores, experts)
    if norm_topk:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    gates = gates * scale
    # An expert's place among the held ones; len(held) for one not held.
    n_held = len(experts_held)
    here = experts[:, :, None] == jnp.asarray(experts_held, jnp.int32)
    group = jnp.min(jnp.where(here, jnp.arange(n_held, dtype=jnp.int32),
                              n_held), axis=-1).reshape(T * k)
    # A token chooses an expert once, so at most ``n_held`` of its k pairs
    # are held here: behind row T * n_held no pair is, whatever the
    # routing, and the sorted rows end there (nothing is dropped).
    rows = T * min(k, n_held)
    group, pair, gate = (a[:rows] for a in _sorted_pairs(
        group, gates.reshape(T * k)))
    group_sizes = jnp.sum(
        group[:, None] == jnp.arange(n_held, dtype=jnp.int32)[None, :],
        axis=0, dtype=jnp.int32)
    return Routing(token=pair // k, gate=gate, held=group < n_held,
                   group_sizes=group_sizes, experts=experts, gates=gates)


@jax.custom_vjp
def _chosen(scores: jax.Array, experts: jax.Array) -> jax.Array:
    """``scores[t, experts[t, j]]`` for scores [T, E] and experts [T, k],
    with nothing fetched by index: of each row's E scores the one whose
    place equals the expert's is selected and the others read as zero, so
    the sum over them is that score to the bit (``where`` and no product:
    an infinite score of another expert stays where it is). The [T, k, E]
    mask is the compiler's to fuse into the sum, and the backward pass
    makes it again from ``experts``. The result stands in memory as a
    fetched one would: left to merge this sum with a sum over k behind it,
    the compiler adds a token's gates in another order."""
    return lax.optimization_barrier(jnp.sum(jnp.where(
        _chose(experts, scores.shape[1]), scores[:, None, :], 0), axis=-1))


def _chose(experts: jax.Array, n_experts: int) -> jax.Array:
    """[T, k, E] bool: pair (t, j) is token t's choice of expert e."""
    return experts[:, :, None] == jnp.arange(n_experts, dtype=experts.dtype)


def _chosen_fwd(scores, experts):
    return _chosen(scores, experts), (experts, scores.shape[1])


def _chosen_bwd(res, grad):
    """A token takes an expert once, so at most one of the k terms of an
    expert's sum is not zero: what a scatter-add would have put there."""
    experts, n_experts = res
    return jnp.sum(jnp.where(_chose(experts, n_experts),
                             grad[:, :, None], 0), axis=1), None


_chosen.defvjp(_chosen_fwd, _chosen_bwd)


@jax.custom_vjp
def _sorted_pairs(group: jax.Array, gate: jax.Array):
    """The N pairs in the order of ``group`` [N], a group's pairs in the
    order they came in (the sort is stable): their groups, their places
    before the sort, and ``gate`` [N] in that order. The gates ride the sort
    as one more operand, and their cotangents ride a sort back whose keys
    are the places (a permutation's transpose is its inverse): no value is
    fetched or put by index in either pass."""
    place = jnp.arange(group.shape[0], dtype=jnp.int32)
    return lax.sort((group, place, gate), num_keys=1)


def _sorted_pairs_fwd(group, gate):
    out = _sorted_pairs(group, gate)
    return out, out[1]


def _sorted_pairs_bwd(place, grads):
    # no two places are equal, so stability has nothing to keep apart
    return None, lax.sort((place, grads[2]), num_keys=1, is_stable=False)[1]


_sorted_pairs.defvjp(_sorted_pairs_fwd, _sorted_pairs_bwd)


def _kept_groups(select: jax.Array, n_group: int, topk_group: int
                 ) -> jax.Array:
    """The group-limited choice of DeepSeek-V3's router (arXiv:2412.19437,
    section 2.1.2; ``noaux_tc``): the E experts are ``n_group`` groups of
    consecutive ones, a group's score is the sum of its two largest of
    ``select`` [T, E] (scores plus bias), the ``topk_group`` best groups
    are kept, and an expert of another group cannot be chosen: its entry
    comes back as -inf."""
    T, E = select.shape
    grouped = select.reshape(T, n_group, E // n_group)
    group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)    # [T, groups]
    _, kept = lax.top_k(group_score, topk_group)
    keep = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
    return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(T, E)


def rows_worked(group_sizes: jax.Array, tile: int = ROW_TILE) -> jax.Array:
    """The sorted rows ``held_experts``' passes touch for these groups:
    whole tiles over the pairs routed here, the trip count of every pass
    times the tile."""
    return moe_rows.worked_tiles(jnp.sum(group_sizes), tile) * tile


def held_experts(h: jax.Array, routing: Routing,
                 e_gate: Optional[jax.Array], e_up: jax.Array,
                 e_down: jax.Array, tile: int = ROW_TILE) -> jax.Array:
    """This chip's experts' part of the layer: h [T, D] at the width the
    experts work at (the model's, or a latent's), the held experts' SwiGLU
    weights [held, D, F], [held, D, F], [held, F, D] in the activations'
    type, or with ``e_gate`` None the two matrices of an expert that is
    ``relu(h W1) ** 2 W2`` -> [T, D], ``sum over a token's held experts of
    gate * expert(h)``.

    Every sorted pair that can be a held expert's has a row here (all T x k,
    or T x held where fewer experts are held than a token takes), so no
    pair is dropped whatever the routing, and the rows of pairs whose expert
    lives elsewhere lie behind the groups, where no pass goes: each one works
    on ``rows_worked`` rows, in tiles of ``tile`` (a multiple of the grouped
    products' row tile). What a pass leaves behind the routed pairs is
    undefined, so whatever reads a whole array selects by ``held`` first.
    The rows are made again in the backward pass and not kept."""
    return jax.checkpoint(functools.partial(_held_experts, tile))(
        h, routing, e_gate, e_up, e_down)


def _held_experts(tile, h, routing, e_gate, e_up, e_down):
    n = jnp.sum(routing.group_sizes)
    rows = functools.partial(moe_rows.map_rows, tile=tile)
    xs = moe_rows.gather_rows(h, routing.token, n, tile=tile)
    if e_gate is None:
        mid = rows(_relu2_rows, n,
                   grouped_matmul(xs, e_up, routing.group_sizes))
    else:
        xs_gate, xs_up = moe_rows.twice(xs, n, tile=tile)
        g = grouped_matmul(xs_gate, e_gate, routing.group_sizes)
        u = grouped_matmul(xs_up, e_up, routing.group_sizes)
        mid = rows(_swiglu_rows, n, g, u)
    ys = grouped_matmul(mid, e_down, routing.group_sizes)
    # Masked before the gate meets ys: the gate's gradient reads ys' rows.
    gate = jnp.where(routing.held, routing.gate, 0)[:, None]
    ys = rows(lambda y, w: y.astype(jnp.float32) * w, n, ys, gate)
    out = moe_rows.scatter_add_rows(ys, routing.token, n, h.shape[0],
                                    tile=tile)
    return out.astype(h.dtype)


def _swiglu_rows(g: jax.Array, u: jax.Array) -> jax.Array:
    return (jax.nn.silu(g.astype(jnp.float32))
            * u.astype(jnp.float32)).astype(g.dtype)


def _relu2_rows(u: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(u.astype(jnp.float32))).astype(u.dtype)
