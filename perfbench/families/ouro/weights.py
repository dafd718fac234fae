"""Seeded weights in the program's parameter tree of a looped stack, made on
the device: ``{"embed", "lm_head", "final_norm", "exit_gate": {"w", "b"},
"layers": {leaf: [layers, ...]}}`` (``ray_tpu/models/transformer.py``: one
kind of layer, its leaves stacked; a layer's four norms, the sandwich's two
among them; the exit gate after every pass).

One jitted call from ``--seed`` builds the whole tree (f32 master weights),
at the program's own scales: normal / sqrt(fan_in), embedding 0.02, norms
1, the gate's bias 0. The plain reference is given the same arrays; it
never sees anything the program made.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.harness import seed_key
from perfbench.reference.train_check import layerwise

TOP = ("embed", "lm_head", "final_norm", "exit_gate.w", "exit_gate.b")
LAYER = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm",
         "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
LEAVES = TOP + LAYER


def leaf_shapes(model: dict) -> dict:
    """name -> (shape, the scale of its normal draw; None: a norm's ones,
    0: zeros)."""
    d, f, v = model["hidden_size"], model["intermediate_size"], \
        model["vocab_size"]
    hd, n = model["head_dim"], model["num_hidden_layers"]
    q, kv = model["num_attention_heads"] * hd, \
        model["num_key_value_heads"] * hd
    norm = ((n, d), None)
    return {
        "embed": ((v, d), 0.02), "lm_head": ((d, v), d ** -0.5),
        "final_norm": ((d,), None),
        "exit_gate.w": ((d, 1), d ** -0.5), "exit_gate.b": ((1,), 0.0),
        "attn_norm": norm, "attn_post_norm": norm,
        "mlp_norm": norm, "mlp_post_norm": norm,
        "wq": ((n, d, q), d ** -0.5), "wk": ((n, d, kv), d ** -0.5),
        "wv": ((n, d, kv), d ** -0.5), "wo": ((n, q, d), q ** -0.5),
        "w_gate": ((n, d, f), d ** -0.5), "w_up": ((n, d, f), d ** -0.5),
        "w_down": ((n, f, d), 1 / math.sqrt(f)),
    }


def _draw(key: jax.Array, index, shape: tuple, scale) -> jax.Array:
    """A leaf: ones for a norm, else normal times ``scale`` from the key
    folded with the leaf's place in ``LEAVES``. Index and scale may be
    arguments of the program that draws, so that leaves of one shape share
    a program."""
    if scale is None:
        return jnp.ones(shape, jnp.float32)
    return jax.random.normal(jax.random.fold_in(key, index), shape,
                             jnp.float32) * scale


def _tree(leaves: dict) -> dict:
    return {"embed": leaves["embed"], "lm_head": leaves["lm_head"],
            "final_norm": leaves["final_norm"],
            "exit_gate": {"w": leaves["exit_gate.w"],
                          "b": leaves["exit_gate.b"]},
            "layers": {n: leaves[n] for n in LAYER}}


def make_params(model: dict, seed: int) -> dict:
    """The whole tree in one jitted program."""
    shapes = leaf_shapes(model)
    scales = {n: np.float32(s) for n, (_shape, s) in shapes.items()
              if s is not None}
    return jax.jit(lambda key, scales: _tree(
        {n: _draw(key, LEAVES.index(n), shape, scales.get(n))
         for n, (shape, _s) in shapes.items()}))(seed_key(seed), scales)


def batch_of(key, index, batch: int, seq_len: int, vocab: int):
    """Step ``index``'s rows: tokens and their next tokens, ids uniform
    over the vocabulary. The reference draws its batches with this same
    function."""
    rows = jax.random.randint(jax.random.fold_in(key, index),
                              (batch, seq_len + 1), 0, vocab)
    return rows[:, :-1], rows[:, 1:]


def flat(params: dict) -> dict:
    """``LEAVES``' name -> array."""
    return {"embed": params["embed"], "lm_head": params["lm_head"],
            "final_norm": params["final_norm"],
            "exit_gate.w": params["exit_gate"]["w"],
            "exit_gate.b": params["exit_gate"]["b"], **params["layers"]}


def _layer_axes(name: str, a) -> tuple:
    """The axes one layer's slice of a stacked leaf is reduced over; None
    for a top-level leaf, reduced whole."""
    return tuple(range(1, a.ndim)) if name in LAYER else None


def leaf_norms(tree: dict) -> dict:
    """``<leaf>.<layer>`` (or a top-level leaf's name) -> norm, computed on
    the device, read back as floats."""
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
    leaf at a time, so no second tree is ever held."""
    key, shapes = seed_key(seed), leaf_shapes(model)
    out = {}
    for name, arr in flat(params).items():
        scale, axes = shapes[name][1], _layer_axes(name, arr)
        if scale is None:
            out[name] = _moved_from(arr, np.float32(1), axes)
        else:
            out[name] = _moved_from_draw(arr, key, LEAVES.index(name),
                                         np.float32(scale), axes)
    return layerwise({n: jax.device_get(v) for n, v in out.items()})
