"""Seeded weights in the program's parameter tree, made on the device:
``{"embed", "final_norm", "lm_head", "layers": {kind: {leaf: [layers of
that kind, ...]}}, "mtp": {"h_norm", "e_norm", "proj", "out_norm", "block":
{leaf: [1, ...]}}}`` (``ray_tpu/models/transformer.py``: parameters stacked
per kind of layer, the multi-token-prediction module's stacked by module).

One jitted call from ``--seed`` builds the whole tree (f32 master weights),
at the program's own scales: normal / sqrt(fan_in), embedding 0.02, norms
1. The plain reference is given the same arrays; it never sees anything the
program made. The rotary columns of ``W_qb`` and ``W_kva`` stand in the
program's half-rotation order (``reference.py`` puts them back).

The expert bias (``topk_method`` ``noaux_tc``: added to the scores to
select, never to weigh) of all five routers, the module's among them, is
balanced once, at set-up (``balanced_bias``), by the rule the router's form
comes from: DeepSeek-V3's auxiliary-loss-free balancing (arXiv:2408.15664;
arXiv:2412.19437, section 2.1.2), ``b_i += u * sign(mean load - load_i)``
over all the router's experts, iterated with the weights frozen. A load
is counted over ``sequences`` of the seed's batches (index 0, 1, ...), as
many as the chips that share a layer bring to one step, which is what the
deployment's rule counts over. At seeded weights a sequence moves every
expert's load its own way by most of the mean (the attention's output is
near the running mean of the values, one vector a sequence, as long as a
token's own embedding), so a bias balanced on one sequence is balanced on
no other: the chip's share of the pairs was then the seed's, 0.55 to 1.95
times the even one, and so was the timed step's work (PERF.md, PR 45).
What to iterate with is the configuration's (``assumed.expert_bias.run``),
the LFM2 and Ling cells' rule and constants.
No gradient reaches the bias, and the timed step holds it. A model without
that entry (the tests of the program's layer kinds) draws it from the seed,
normal * ``expert_bias_scale``.
"""

from __future__ import annotations

import functools
import json
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench.harness import seed_key
from perfbench.reference.numerics import mm_highest
from perfbench.reference.train_check import layerwise

from . import counts, reference

# (the model as it is run, the seed) -> {group: [layers, router]}: the
# balanced bias on the host, made once a process. A run asks for the seed's
# tree three times (the program, the load's reader, the reference).
_BALANCED = {}
MODULE = ("h_norm", "e_norm", "proj", "out_norm")


def _remembered_as(model: dict, seed: int) -> tuple:
    return json.dumps(model, sort_keys=True), int(seed)


def _balances(model: dict) -> bool:
    """Whether ``model`` carries the rule its bias is balanced by."""
    return "expert_bias" in model


def kind_counts(model: dict) -> dict:
    """kind -> how many layers of it, in the tree's (sorted) order."""
    every = counts.kinds(model)
    return {k: every.count(k) for k in sorted(set(every))}


def kind_leaves(model: dict, kind: str) -> dict:
    """name -> (shape of one layer's slice, fan_in; None: a norm's ones)."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    rank_q, rank, rot = (model["q_lora_rank"], model["kv_lora_rank"],
                         model["qk_rope_head_dim"])
    nope, dv = model["qk_nope_head_dim"], model["v_head_dim"]
    leaves = {"mla_norm": ((d,), None),
              "mla_q_a": ((d, rank_q), d), "mla_q_norm": ((rank_q,), None),
              "mla_q_b": ((rank_q, h * (nope + rot)), rank_q),
              "mla_kv_a": ((d, rank + rot), d),
              "mla_kv_norm": ((rank,), None),
              "mla_kv_b": ((rank, h * (nope + dv)), rank),
              "mla_out": ((h * dv, d), h * dv),
              "mlp_norm": ((d,), None)}
    if kind.endswith("dense"):
        f = model["intermediate_size"]
        leaves.update(w_gate=((d, f), d), w_up=((d, f), d),
                      w_down=((f, d), f))
    else:
        f, held = model["moe_intermediate_size"], model["n_routed_experts"]
        fs, router = (model["n_shared_experts"] * f,
                      model["router_experts"])
        leaves.update(router=((d, router), d), e_gate=((held, d, f), d),
                      e_up=((held, d, f), d), e_down=((held, f, d), f),
                      s_gate=((d, fs), d), s_up=((d, fs), d),
                      s_down=((fs, d), fs), expert_bias=((router,), None))
    return leaves


def _stacked(model: dict) -> dict:
    """group -> (how many are stacked, its leaves), a group a kind of
    layer, ``mtp`` (a module's own leaves) or ``mtp_block`` (its layer)."""
    d = model["hidden_size"]
    groups = {kind: (n, kind_leaves(model, kind))
              for kind, n in kind_counts(model).items()}
    modules = model["num_nextn_predict_layers"]
    if modules:
        norm = ((d,), None)
        groups["mtp"] = (modules, {"h_norm": norm, "e_norm": norm,
                                   "proj": ((2 * d, d), 2 * d),
                                   "out_norm": norm})
        groups["mtp_block"] = (modules,
                               kind_leaves(model, counts.MODULE_KIND))
    return groups


def leaf_names(model: dict) -> list:
    """Every leaf as ``embed``, ``final_norm``, ``lm_head`` or
    ``<group>.<leaf>``."""
    top = ["embed", "final_norm"] + (
        [] if model["tie_word_embeddings"] else ["lm_head"])
    return top + [f"{group}.{name}"
                  for group, (_n, leaves) in _stacked(model).items()
                  for name in leaves]


def _recipe(model: dict, name: str) -> tuple:
    """-> (the leaf's index in ``leaf_names``, which its key is folded
    with; its shape; the scale of its normal draw, None for a norm's
    ones). A bias that ``balanced_bias`` fills in is drawn at scale 0."""
    d, v = model["hidden_size"], model["vocab_size"]
    index = leaf_names(model).index(name)
    if name == "embed":
        return index, (v, d), 0.02
    if name == "final_norm":
        return index, (d,), None
    if name == "lm_head":
        return index, (d, v), 1 / math.sqrt(d)
    group, leaf = name.split(".")
    n, leaves = _stacked(model)[group]
    shape, fan_in = leaves[leaf]
    if leaf == "expert_bias":
        return index, (n,) + shape, \
            0.0 if _balances(model) else model["expert_bias_scale"]
    return index, (n,) + shape, fan_in and 1 / math.sqrt(fan_in)


def _draw(key: jax.Array, index, shape: tuple, scale) -> jax.Array:
    """A leaf of ``_recipe``. Index and scale may be arguments of the
    program that draws: a draw is then one program a shape, and makes the
    numbers the whole tree's program made (``make_params`` hands its
    scales in as arguments too, so that no compiler folds one of them
    into the normal's own constants)."""
    if scale is None:
        return jnp.ones(shape, jnp.float32)
    return jax.random.normal(jax.random.fold_in(key, index), shape,
                             jnp.float32) * scale


def _tree(leaves: dict) -> dict:
    tree = {"layers": {}}
    for name, a in leaves.items():
        group, _, leaf = name.partition(".")
        if not leaf:
            tree[name] = a
        elif group == "mtp":
            tree.setdefault("mtp", {})[leaf] = a
        elif group == "mtp_block":
            tree.setdefault("mtp", {}).setdefault("block", {})[leaf] = a
        else:
            tree["layers"].setdefault(group, {})[leaf] = a
    return tree


def flat(params: dict) -> dict:
    """``leaf_names``' name -> array."""
    out = {n: a for n, a in params.items() if n not in ("layers", "mtp")}
    for kind, leaves in params["layers"].items():
        out.update({f"{kind}.{n}": a for n, a in leaves.items()})
    module = params.get("mtp", {})
    out.update({f"mtp.{n}": module[n] for n in MODULE if n in module})
    out.update({f"mtp_block.{n}": a
                for n, a in module.get("block", {}).items()})
    return out


def batch_of(key, index, batch: int, seq_len: int, vocab: int):
    """Step ``index``'s rows: tokens and their next tokens, ids uniform
    over the vocabulary's slice. The reference draws its batches with this
    same function."""
    rows = jax.random.randint(jax.random.fold_in(key, index),
                              (batch, seq_len + 1), 0, vocab)
    return rows[:, :-1], rows[:, 1:]


def balance(model: dict, scores, rule: dict):
    """One router's bias by the auxiliary-loss-free rule: from zero,
    ``b += u * sign(mean load - load)`` over every expert of ``scores``
    [T, E] (the tokens of all the rule's sequences), a load being how many
    of the T * k pairs the router's choice (``reference.choose``: top-k of
    ``scores + b``) gives an expert, until
    the fullest expert is at most ``max_over_mean`` times the mean or
    ``iterations`` have run.
    -> (bias [E], iterations run, fullest over mean)."""
    t, e = scores.shape
    mean = t * model["num_experts_per_tok"] / e

    def load(b):
        experts = reference.choose(model, scores + b)
        return jnp.sum(experts[..., None] == jnp.arange(e), axis=(0, 1),
                       dtype=jnp.float32)

    def full(state):
        _, loads, i = state
        return (jnp.max(loads) > rule["max_over_mean"] * mean) \
            & (i < rule["iterations"])

    def move(state):
        b, loads, i = state
        b = b + rule["u"] * jnp.sign(mean - loads)
        return b, load(b), i + 1

    zero = jnp.zeros((e,), jnp.float32)
    b, loads, i = lax.while_loop(full, move, (zero, load(zero), 0))
    return b, i, jnp.max(loads) / mean


def balanced_bias(model: dict, params: dict, key, mm=mm_highest):
    """Every router's bias, layer by layer in depth order, the module's
    last: a layer's scores come of the routing before it and of what the
    experts held here gave (the plain reference's layers, float32). The
    rows are the rule's ``sequences`` of its ``seq_len``, drawn as
    ``batch_of`` draws the batches of index 0, 1, ...; each runs through
    the layers alone and a router's loads are counted over all of them;
    the module is fed the stack's last hidden state and each position's
    next token at every position, as the program runs it.
    -> ({group: [stacked, router]}, then a row a router: iterations,
    fullest over mean, the pairs a sequence gives the experts held)."""
    rule = model["expert_bias"]
    rows = jax.vmap(lambda i: batch_of(key, i, 1, rule["seq_len"],
                                       model["vocab_size"]))(
        jnp.arange(rule["sequences"]))
    tokens, targets = (r[:, 0] for r in rows)
    eps, held = model["rms_norm_eps"], jnp.asarray(model["experts_held"])

    def each(f, *xs):
        """``f`` on one sequence at a time."""
        return lax.map(lambda a: f(*a), xs)

    def balanced(lp, xs):
        """One expert layer: its bias balanced on xs, and xs after it."""
        xs = each(lambda x: reference.operator(model, lp, x, mm), xs)
        zs = reference.rms_norm(xs, lp["mlp_norm"], eps)
        scores = each(lambda z: reference.router_scores(lp, z, mm), zs)
        scores = scores.reshape(-1, scores.shape[-1])
        b, i, worst = balance(model, scores, rule)
        here = jnp.sum(reference.choose(model, scores + b)[..., None] == held)
        lp = {**lp, "expert_bias": b}
        xs = xs + each(lambda z: reference.experts(model, lp, z, mm), zs)
        return xs, b, (i, worst, here / rule["sequences"])

    xs = params["embed"][tokens]
    bias, ran = {}, []
    for kind, lp in reference.layers_of(model, params):
        if kind.endswith("dense"):
            xs = each(lambda x: reference.layer(model, kind, lp, x, mm), xs)
            continue
        xs, b, how = balanced(lp, xs)
        bias.setdefault(kind, []).append(b)
        ran.append(how)
    if model["num_nextn_predict_layers"]:
        xs = each(lambda x, nxt: reference.mtp_input(model, params, x, nxt,
                                                     mm), xs, targets)
        _xs, b, how = balanced(reference.module_layer(model, params), xs)
        bias["mtp_block"] = [b]
        ran.append(how)
    return ({group: jnp.stack(rows) for group, rows in bias.items()},
            *(jnp.stack(column) for column in zip(*ran)))


def make_params(model: dict, seed: int) -> dict:
    """The whole tree in one jitted program, then the balanced bias in a
    second, once a process for a seed."""
    key = seed_key(seed)
    recipes = {n: _recipe(model, n) for n in leaf_names(model)}
    scales = {n: np.float32(scale) for n, (_i, _shape, scale)
              in recipes.items() if scale is not None}
    params = jax.jit(lambda key, scales: _tree(
        {n: _draw(key, index, shape, scales.get(n))
         for n, (index, shape, _scale) in recipes.items()}))(key, scales)
    if not _balances(model):
        return params
    memo = _remembered_as(model, seed)
    if memo not in _BALANCED:
        bias, ran, fullest, here = jax.jit(
            lambda p, k: balanced_bias(model, p, k))(params, key)
        if isinstance(ran, jax.core.Tracer):        # shapes only
            return _with_bias(params, bias)
        print(f"perfbench joyai: bias balanced in {ran.tolist()} iterations "
              f"a router, fullest over mean "
              f"{[round(float(w), 4) for w in fullest]}, the held experts' "
              f"pairs a sequence {[round(float(n), 1) for n in here]}",
              file=sys.stderr, flush=True)
        _BALANCED[memo] = jax.device_get(bias)
    return _with_bias(params, _BALANCED[memo])


def _with_bias(params: dict, bias: dict) -> dict:
    """A fresh device array each time: the step donates its parameters."""
    tree = flat(params)
    tree.update({f"{group}.expert_bias": jnp.asarray(b)
                 for group, b in bias.items()})
    return _tree(tree)


def _layer_axes(name: str, a) -> tuple:
    """The axes one layer's slice of a stacked leaf is reduced over; None
    for a top-level leaf, reduced whole."""
    return tuple(range(1, a.ndim)) if "." in name else None


def leaf_norms(tree: dict) -> dict:
    """``<group>.<leaf>.<index in its stack>`` (or a top-level leaf's name)
    -> norm, computed on the device, read back as floats."""
    def norms(t):
        return {name: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                       axis=_layer_axes(name, a)))
                for name, a in flat(t).items()}
    return layerwise(jax.jit(norms)(tree))


@functools.partial(jax.jit, static_argnames="axes")
def _moved_from(a, start, axes):
    return jnp.sqrt(jnp.sum(jnp.square(a - start), axis=axes))


@functools.partial(jax.jit, static_argnames="axes")
def _moved_from_draw(a, key, index, scale, axes):
    return _moved_from(a, _draw(key, index, a.shape, scale), axes)


def change_norms(model: dict, seed: int, params: dict) -> dict:
    """The same names -> the norm of ``params - make_params(model, seed)``:
    the initial leaf is made again inside the program that reduces it, one
    leaf at a time, so no second tree is ever held. A leaf's index and
    scale are arguments: leaves of one shape share a program, and a
    process compiles it once."""
    key = seed_key(seed)
    memo = _remembered_as(model, seed)
    if _balances(model) and memo not in _BALANCED:
        make_params(model, seed)
    balanced = _BALANCED.get(memo, {})
    out = {}
    for name, arr in flat(params).items():
        group, _, leaf = name.partition(".")
        index, _shape, scale = _recipe(model, name)
        axes = _layer_axes(name, arr)
        if leaf == "expert_bias" and group in balanced:
            out[name] = _moved_from(arr, balanced[group], axes)
        elif scale is None:
            out[name] = _moved_from(arr, np.float32(1), axes)
        else:
            out[name] = _moved_from_draw(arr, key, index, np.float32(scale),
                                         axes)
    return layerwise({n: jax.device_get(v) for n, v in out.items()})
