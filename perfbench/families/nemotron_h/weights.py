"""Seeded weights in the program's patterned parameter tree, made on the
device: ``{"embed", "final_norm", "lm_head", "layers": {kind: {leaf:
[layers of that kind, ...]}}}`` (``ray_tpu/models/transformer.py``:
parameters stacked per kind of layer; the kinds here are ``none_moe``,
``mamba_none`` and ``attention_none``).

One jitted call from ``--seed`` builds the whole tree (f32 master weights),
at the program's own scales: normal / sqrt(fan_in), embedding 0.02, norms
and the skip ``D`` 1, the taps and their bias normal / sqrt(taps). A Mamba
layer's two decay parameters take what the mechanism's reference layer
gives them (the configuration's ``assumed.mamba_form``): ``A_log = log
U(1, 16)`` a head, and ``dt_bias`` the inverse softplus of ``dt = exp(U(log
time_step_min, log time_step_max))`` floored at ``time_step_floor``. The
plain reference is given the same arrays; it never sees anything the
program made.

The expert bias (added to the scores to select, never to weigh) is
balanced once, at set-up (``balanced_bias``), by the rule the router's form
comes from: DeepSeek-V3's auxiliary-loss-free balancing (arXiv:2408.15664;
arXiv:2412.19437, section 2.1.2), ``b_i += u * sign(mean load - load_i)``
over all the router's experts, a load counted through this router's top-k,
iterated with the weights frozen on the seed's batch of index 0. What to
iterate with is the configuration's (``assumed.expert_bias.run``). No
gradient reaches the bias, and the timed step holds it.
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
MOE = "none_moe"


def _remembered_as(model: dict, seed: int) -> tuple:
    return json.dumps(model, sort_keys=True), int(seed)


def kind_counts(model: dict) -> dict:
    """kind -> how many layers of it, in the tree's (sorted) order."""
    every = counts.kinds(model)
    return {k: every.count(k) for k in sorted(set(every))}


def kind_leaves(model: dict, kind: str) -> dict:
    """name -> (shape of one layer's slice, fan_in; None: ones; a string:
    a draw of its own, ``_leaf``)."""
    d = model["hidden_size"]
    if kind == "mamba_none":
        h, taps = model["mamba_num_heads"], model["conv_kernel"]
        inner, mixed = counts.mamba_inner(model), counts.mamba_mixed(model)
        return {"mamba_norm": ((d,), None),
                "mamba_in": ((d, inner + mixed + h), d),
                "mamba_taps": ((mixed, taps), taps),
                "mamba_conv_bias": ((mixed,), taps),
                "mamba_a_log": ((h,), "a_log"),
                "mamba_dt_bias": ((h,), "dt_bias"),
                "mamba_d": ((h,), None),
                "mamba_gate_norm": ((inner,), None),
                "mamba_out": ((inner, d), inner)}
    if kind == "attention_none":
        hd = model["head_dim"]
        nq = model["num_attention_heads"] * hd
        nkv = model["num_key_value_heads"] * hd
        return {"attn_norm": ((d,), None), "wq": ((d, nq), d),
                "wk": ((d, nkv), d), "wv": ((d, nkv), d), "wo": ((nq, d), nq)}
    lat, f = model["moe_latent_size"], model["moe_intermediate_size"]
    fs, held = (model["moe_shared_expert_intermediate_size"],
                model["n_routed_experts"])
    router = model["router_experts"]
    return {"mlp_norm": ((d,), None), "router": ((d, router), d),
            "latent_down": ((d, lat), d), "latent_up": ((lat, d), lat),
            "e_up": ((held, lat, f), lat), "e_down": ((held, f, lat), f),
            "expert_bias": ((router,), "bias"),
            "s_up": ((d, fs), d), "s_down": ((fs, d), fs)}


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
    if fan_in == "bias":                # balanced_bias fills it in
        return jnp.zeros(shape, jnp.float32)
    if fan_in == "a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if fan_in == "dt_bias":
        lo, hi = model["time_step_min"], model["time_step_max"]
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, math.log(lo), math.log(hi))),
            model["time_step_floor"])
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
    (``reference.choose``: top-k of ``scores + b``) gives an expert, until
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
    """Every expert layer's bias, layer by layer in depth order: a layer's
    scores come of the routing before it and of what the experts held here
    gave (the plain reference's layers, float32). The rows are one sequence
    of the rule's ``seq_len`` drawn as ``batch_of`` draws the batch of
    index 0. -> ([expert layers, router], iterations a layer, fullest over
    mean a layer)."""
    rule, eps = model["expert_bias"], model["layer_norm_epsilon"]
    tokens, _ = batch_of(key, 0, 1, rule["seq_len"], model["vocab_size"])
    x = params["embed"][tokens[0]]

    def through(kinds, x, lps):
        """One unit of layers: each expert layer's bias balanced on its
        input, and x after the unit."""
        found = []
        for kind, lp in zip(kinds, lps):
            if kind == MOE:
                u = reference.rms_norm(x, lp["mlp_norm"], eps)
                b, i, worst = balance(
                    model, reference.router_scores(lp, u, mm), rule)
                lp = {**lp, "expert_bias": b}
                found.append((b, i, worst))
            x = reference.layer(model, kind, lp, x, mm)
        return x, tuple(found)

    bias, ran, fullest = [], [], []
    for kinds, starts, count in reference.layer_units(model):
        x, found = lax.scan(
            lambda x, lps, kinds=kinds: through(kinds, x, lps), x,
            reference.unit_stacks(params, kinds, starts, count))
        for b, i, worst in found:   # at most one expert layer a unit here
            bias.append(b)
            ran.append(i)
            fullest.append(worst)
    return (jnp.concatenate(bias), jnp.concatenate(ran),
            jnp.concatenate(fullest))


def make_params(model: dict, seed: int) -> dict:
    """The whole tree in one jitted program, then the balanced bias in a
    second, once a process for a seed."""
    key = seed_key(seed)
    params = jax.jit(lambda key: _tree(
        {n: _leaf(model, key, n) for n in leaf_names(model)}))(key)
    memo = _remembered_as(model, seed)
    if memo not in _BALANCED:
        bias, ran, fullest = jax.jit(
            lambda p, k: balanced_bias(model, p, k))(params, key)
        if isinstance(ran, jax.core.Tracer):        # shapes only
            return _with_bias(params, bias)
        print(f"perfbench nemotron_h: bias balanced in {ran.tolist()} "
              f"iterations a layer, fullest over mean "
              f"{[round(float(w), 4) for w in fullest]}",
              file=sys.stderr, flush=True)
        _BALANCED[memo] = jax.device_get(bias)
    return _with_bias(params, _BALANCED[memo])


def _with_bias(params: dict, bias) -> dict:
    """A fresh device array each time: the step donates its parameters."""
    layers = dict(params["layers"])
    layers[MOE] = {**layers[MOE], "expert_bias": jnp.asarray(bias)}
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
    if memo not in _BALANCED:
        make_params(model, seed)
    out = {}
    for name, arr in flat(params).items():
        # The balanced bias cannot be made again from the key alone; it is
        # an argument, not a constant: one program for every seed.
        start = _BALANCED[memo] if name == f"{MOE}.expert_bias" else None
        fn = jax.jit(lambda a, k, start, name=name: jnp.sqrt(jnp.sum(
            jnp.square(a - (_leaf(model, k, name) if start is None
                            else start)),
            axis=_layer_axes(name, a))))
        out[name] = fn(arr, key, start)
    return layerwise({n: jax.device_get(v) for n, v in out.items()})
