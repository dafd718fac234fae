"""Seeded weights in the program's patterned parameter tree, made on the
device: ``{"embed", "final_norm", "layers": {kind: {leaf: [layers of that
kind, ...]}}}`` (``ray_tpu/models/transformer.py``: parameters stacked per
kind of layer), with no ``lm_head`` where the head is tied to the table.

One jitted call from ``--seed`` builds the whole tree (f32 master weights),
at the program's own scales: normal / sqrt(fan_in), embedding 0.02, norms
1, the conv taps normal / sqrt(taps). The plain reference is given the
same arrays; it never sees anything the program made.

The expert bias (``use_expert_bias``: added to the scores to select, never
to weigh) is drawn small from the seed, normal * ``expert_bias_scale``, and
held: no gradient reaches it, and its published per-step update is not in
the config.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.harness import seed_key
from perfbench.reference.train_check import layerwise

from . import counts


def kind_counts(model: dict) -> dict:
    """kind -> how many layers of it, in the tree's (sorted) order."""
    every = counts.kinds(model)
    return {k: every.count(k) for k in sorted(set(every))}


def kind_leaves(model: dict, kind: str) -> dict:
    """name -> (shape of one layer's slice, fan_in; None: a norm's ones)."""
    d, hd = model["hidden_size"], counts.head_dim(model)
    q, kv = model["num_attention_heads"] * hd, \
        model["num_key_value_heads"] * hd
    if kind.startswith("conv"):
        taps = model["conv_L_cache"]
        leaves = {"conv_norm": ((d,), None), "conv_in": ((d, 3 * d), d),
                  "conv_taps": ((d, taps), taps), "conv_out": ((d, d), d)}
    else:
        leaves = {"attn_norm": ((d,), None), "wq": ((d, q), d),
                  "wk": ((d, kv), d), "wv": ((d, kv), d), "wo": ((q, d), q),
                  "q_norm": ((hd,), None), "k_norm": ((hd,), None)}
    leaves["mlp_norm"] = ((d,), None)
    if kind.endswith("dense"):
        f = model["intermediate_size"]
        leaves.update(w_gate=((d, f), d), w_up=((d, f), d),
                      w_down=((f, d), f))
    else:
        f, held = model["moe_intermediate_size"], model["num_experts"]
        router = model["router_experts"]
        leaves.update(router=((d, router), d), e_gate=((held, d, f), d),
                      e_up=((held, d, f), d), e_down=((held, f, d), f))
        if model["use_expert_bias"]:
            leaves["expert_bias"] = ((router,), None)
    return leaves


def leaf_names(model: dict) -> list:
    """Every leaf as ``embed``, ``final_norm`` or ``<kind>.<leaf>``."""
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
        return jax.random.normal(k, shape, jnp.float32) \
            * model["expert_bias_scale"]
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


def make_params(model: dict, seed: int) -> dict:
    """The whole tree in one jitted program."""
    return jax.jit(lambda key: _tree(
        {n: _leaf(model, key, n) for n in leaf_names(model)}))(seed_key(seed))


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
    out = {}
    for name, arr in flat(params).items():
        fn = jax.jit(lambda a, k, name=name: jnp.sqrt(jnp.sum(
            jnp.square(a - _leaf(model, k, name)),
            axis=_layer_axes(name, a))))
        out[name] = fn(arr, key)
    return layerwise({n: jax.device_get(v) for n, v in out.items()})
