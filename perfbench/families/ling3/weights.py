"""Seeded weights in the program's patterned parameter tree, made on the
device: ``{"embed", "final_norm", "lm_head", "layers": {kind: {leaf:
[layers of that kind, ...]}}}`` (``ray_tpu/models/transformer.py``:
parameters stacked per kind of layer).

One jitted call from ``--seed`` builds the whole tree (f32 master weights),
at the program's own scales: normal / sqrt(fan_in), embedding 0.02, norms
1, the taps normal / sqrt(taps). A KDA layer's two decay parameters take
what their mechanism's paper gives them (the configuration's
``assumed.kda_a_log`` and ``assumed.kda_dt_bias``): ``A_log = log U(lo,
hi)`` a head, and ``dt_bias`` the inverse softplus of ``dt = exp(U(log
dt_min, log dt_max))`` a channel. The plain reference is given the same
arrays; it never sees anything the program made.

The expert bias (``moe_router_enable_expert_bias``: added to the scores to
select, never to weigh) is balanced once, at set-up (``balanced_bias``), by
the rule the router's form comes from: DeepSeek-V3's auxiliary-loss-free
balancing (arXiv:2408.15664; arXiv:2412.19437, section 2.1.2), ``b_i += u
* sign(mean load - load_i)`` over all the router's experts, a load counted
**through this router's group-limited choice**, iterated with the weights
frozen on the seed's batch of index 0. What to iterate with is the
configuration's (``assumed.expert_bias.run``). No gradient reaches the
bias, and the timed step holds it. A model without that entry (the tests
of the program's layer kinds) draws it from the seed, normal *
``expert_bias_scale``.
"""

from __future__ import annotations

import json
import math
import sys

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.harness import seed_key
from perfbench.reference.numerics import mm_highest
from perfbench.reference.train_check import layerwise

from . import counts, reference

# (the model as it is run, the seed) -> {kind: [layers, router]}: the
# balanced bias on the host, made once a process. A run asks for the seed's
# tree three times (the program, the load's reader, the reference).
_BALANCED = {}


def _remembered_as(model: dict, seed: int) -> tuple:
    return json.dumps(model, sort_keys=True), int(seed)


def _balances(model: dict) -> bool:
    """Whether ``model`` carries the rule its bias is balanced by."""
    return bool(model["moe_router_enable_expert_bias"]) \
        and "expert_bias" in model


def kind_counts(model: dict) -> dict:
    """kind -> how many layers of it, in the tree's (sorted) order."""
    every = counts.kinds(model)
    return {k: every.count(k) for k in sorted(set(every))}


def kind_leaves(model: dict, kind: str) -> dict:
    """name -> (shape of one layer's slice, fan_in; None: a norm's ones;
    a string: a draw of its own, ``_leaf``)."""
    d, h, hd = (model["hidden_size"], model["num_attention_heads"],
                model["head_dim"])
    if kind.startswith("kda"):
        taps = model["short_conv_kernel_size"]
        wide, tap = ((d, h * hd), d), ((h * hd, taps), taps)
        leaves = {"kda_norm": ((d,), None), "kda_q": wide, "kda_k": wide,
                  "kda_v": wide, "kda_q_taps": tap, "kda_k_taps": tap,
                  "kda_v_taps": tap, "kda_a": wide,
                  "kda_dt_bias": ((h * hd,), "dt_bias"),
                  "kda_a_log": ((h,), "a_log"), "kda_beta": ((d, h), d),
                  "kda_gate": wide, "kda_o_norm": ((hd,), None),
                  "kda_out": ((h * hd, d), h * hd)}
    else:
        rank, rot = model["kv_lora_rank"], model["qk_rope_head_dim"]
        nope, dv = model["qk_nope_head_dim"], model["v_head_dim"]
        leaves = {"mla_norm": ((d,), None),
                  "mla_q": ((d, h * (nope + rot)), d),
                  "mla_kv_a": ((d, rank + rot), d),
                  "mla_kv_norm": ((rank,), None),
                  "mla_kv_b": ((rank, h * (nope + dv)), rank),
                  "mla_gate": ((d, h), d), "mla_out": ((h * dv, d), h * dv)}
    leaves["mlp_norm"] = ((d,), None)
    if kind.endswith("dense"):
        f = model["intermediate_size"]
        leaves.update(w_gate=((d, f), d), w_up=((d, f), d),
                      w_down=((f, d), f))
    else:
        f, held = model["moe_intermediate_size"], model["num_experts"]
        fs, router = (model["moe_shared_expert_intermediate_size"],
                      model["router_experts"])
        leaves.update(router=((d, router), d), e_gate=((held, d, f), d),
                      e_up=((held, d, f), d), e_down=((held, f, d), f),
                      s_gate=((d, fs), d), s_up=((d, fs), d),
                      s_down=((fs, d), fs))
        if model["moe_router_enable_expert_bias"]:
            leaves["expert_bias"] = ((router,), None)
    return leaves


def leaf_names(model: dict) -> list:
    """Every leaf as ``embed``, ``final_norm``, ``lm_head`` or
    ``<kind>.<leaf>``."""
    top = ["embed", "final_norm"] + (
        [] if model["tie_word_embeddings"] else ["lm_head"])
    return top + [f"{kind}.{name}" for kind in kind_counts(model)
                  for name in kind_leaves(model, kind)]


def _leaf(model: dict, key: jax.Array, name: str) -> jax.Array:
    d, v = model["hidden_size"], model["vocab_size"]
    k = jax.random.fold_in(key, leaf_names(model).index(name))
    if name == "embed":
        return jax.random.normal(k, (v, d), jnp.float32) * 0.02
    if name == "final_norm":
        return jnp.ones((d,), jnp.float32)
    if name == "lm_head":
        return jax.random.normal(k, (d, v), jnp.float32) / math.sqrt(d)
    kind, leaf = name.split(".")
    shape, fan_in = kind_leaves(model, kind)[leaf]
    shape = (kind_counts(model)[kind],) + shape
    if leaf == "expert_bias":
        if _balances(model):            # balanced_bias fills it in
            return jnp.zeros(shape, jnp.float32)
        return jax.random.normal(k, shape, jnp.float32) \
            * model["expert_bias_scale"]
    if fan_in == "a_log":
        lo, hi = model["kda_a_log"]
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, lo, hi))
    if fan_in == "dt_bias":
        lo, hi = model["kda_dt_bias"]
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        math.log(lo), math.log(hi)))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus's inverse
    if fan_in is None:
        return jnp.ones(shape, jnp.float32)
    return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)


def _tree(leaves: dict) -> dict:
    tree = {"layers": {}}
    for name, a in leaves.items():
        if "." in name:
            kind, leaf = name.split(".")
            tree["layers"].setdefault(kind, {})[leaf] = a
        else:
            tree[name] = a
    return tree


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
    [T, E], a load being how many of the T * k pairs the router's choice
    (``reference.choose``: group-limited top-k of ``scores + b``) gives an
    expert, until the fullest expert is at most ``max_over_mean`` times the
    mean or ``iterations`` have run.
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
    """Every expert layer's bias, layer by layer in depth order: a layer's
    scores come of the routing before it and of what the experts held
    here gave (the plain reference's layers, float32). The rows are one
    sequence of the rule's ``seq_len`` drawn as ``batch_of`` draws the
    batch of index 0. -> ({kind: [layers of that kind, router]},
    iterations a layer, fullest over mean a layer)."""
    rule = model["expert_bias"]
    tokens, _ = batch_of(key, 0, 1, rule["seq_len"], model["vocab_size"])
    x = params["embed"][tokens[0]]

    def balanced(kind, x, lp):
        """One expert layer: its bias balanced on x, and x after it."""
        x = reference.operator(model, kind, lp, x, mm)
        z = reference.rms_norm(x, lp["mlp_norm"], model["rms_norm_eps"])
        b, i, worst = balance(model, reference.router_scores(lp, z, mm), rule)
        x = x + reference.experts(model, {**lp, "expert_bias": b}, z, mm)
        return x, (b, i, worst)

    bias, ran, fullest = {}, [], []
    for kind, start, count in reference.layer_runs(model):
        stack = reference.run_stack(params, kind, start, count)
        if kind.endswith("dense"):
            x, _ = lax.scan(lambda x, lp, kind=kind: (
                reference.layer(model, kind, lp, x, mm), None), x, stack)
            continue
        x, (b, i, worst) = lax.scan(
            lambda x, lp, kind=kind: balanced(kind, x, lp), x, stack)
        bias.setdefault(kind, []).append(b)     # runs come in stack order
        ran.append(i)
        fullest.append(worst)
    return ({kind: jnp.concatenate(rows) for kind, rows in bias.items()},
            jnp.concatenate(ran), jnp.concatenate(fullest))


def make_params(model: dict, seed: int) -> dict:
    """The whole tree in one jitted program, then the balanced bias in a
    second, once a process for a seed."""
    key = seed_key(seed)
    params = jax.jit(lambda key: _tree(
        {n: _leaf(model, key, n) for n in leaf_names(model)}))(key)
    if not _balances(model):
        return params
    memo = _remembered_as(model, seed)
    if memo not in _BALANCED:
        bias, ran, fullest = jax.jit(
            lambda p, k: balanced_bias(model, p, k))(params, key)
        if isinstance(ran, jax.core.Tracer):        # shapes only
            return _with_bias(params, bias)
        print(f"perfbench ling3: bias balanced in {ran.tolist()} iterations "
              f"a layer, fullest over mean "
              f"{[round(float(w), 4) for w in fullest]}",
              file=sys.stderr, flush=True)
        _BALANCED[memo] = jax.device_get(bias)
    return _with_bias(params, _BALANCED[memo])


def _with_bias(params: dict, bias: dict) -> dict:
    """A fresh device array each time: the step donates its parameters."""
    bias = jax.tree.map(jnp.asarray, bias)
    layers = {kind: {**leaves, "expert_bias": bias[kind]} if kind in bias
              else leaves for kind, leaves in params["layers"].items()}
    return {**params, "layers": layers}


def flat(params: dict) -> dict:
    """``leaf_names``' name -> array."""
    out = {n: a for n, a in params.items() if n != "layers"}
    for kind, leaves in params["layers"].items():
        out.update({f"{kind}.{n}": a for n, a in leaves.items()})
    return out


def _layer_axes(name: str, a) -> tuple:
    """The axes one layer's slice of a stacked leaf is reduced over; None
    for a top-level leaf, reduced whole."""
    return tuple(range(1, a.ndim)) if "." in name else None


def leaf_norms(tree: dict) -> dict:
    """``<kind>.<leaf>.<layer of that kind>`` (or a top-level leaf's name)
    -> norm, computed on the device, read back as floats."""
    def norms(t):
        return {name: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                       axis=_layer_axes(name, a)))
                for name, a in flat(t).items()}
    return layerwise(jax.jit(norms)(tree))


def change_norms(model: dict, seed: int, params: dict) -> dict:
    """The same names -> the norm of ``params - make_params(model, seed)``:
    the initial leaf is made again inside the program that reduces it, one
    leaf at a time, so no second tree is ever held."""
    key = seed_key(seed)
    memo = _remembered_as(model, seed)
    if _balances(model) and memo not in _BALANCED:
        make_params(model, seed)
    balanced = _BALANCED.get(memo, {})
    out = {}
    for name, arr in flat(params).items():
        # The balanced bias cannot be made again from the key alone; it is
        # an argument, not a constant: one program for every seed.
        kind, _, leaf = name.partition(".")
        start = balanced.get(kind) if leaf == "expert_bias" else None
        fn = jax.jit(lambda a, k, start, name=name: jnp.sqrt(jnp.sum(
            jnp.square(a - (_leaf(model, k, name) if start is None
                            else start)),
            axis=_layer_axes(name, a))))
        out[name] = fn(arr, key, start)
    return layerwise({n: jax.device_get(v) for n, v in out.items()})
