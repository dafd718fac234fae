"""Seeded weights in the program's parameter tree, made on the device.

One jitted call from ``--seed`` builds the whole tree (f32 master weights,
the type the program keeps them in), so set-up pays one program and not
one per leaf. The scales are the program's own (normal / sqrt(fan_in),
embedding 0.02, norms 1). The plain reference is given the same arrays;
it never sees anything the program made.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LEAVES = ("embed", "lm_head", "final_norm", "attn_norm", "mlp_norm",
          "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def leaf_shapes(model: dict) -> dict:
    """name -> (shape, fan_in; None where the scale does not come from it)."""
    d, f, v = model["hidden_size"], model["intermediate_size"], \
        model["vocab_size"]
    hd, n = model["head_dim"], model["num_hidden_layers"]
    q, kv = model["num_attention_heads"] * hd, \
        model["num_key_value_heads"] * hd
    return {
        "embed": ((v, d), None), "lm_head": ((d, v), d),
        "final_norm": ((d,), None),
        "attn_norm": ((n, d), None), "mlp_norm": ((n, d), None),
        "wq": ((n, d, q), d), "wk": ((n, d, kv), d), "wv": ((n, d, kv), d),
        "wo": ((n, q, d), q), "w_gate": ((n, d, f), d),
        "w_up": ((n, d, f), d), "w_down": ((n, f, d), f),
    }


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 2**63: both 32-bit halves count."""
    seed = int(seed)
    return jax.random.wrap_key_data(jnp.array(
        [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], dtype=jnp.uint32))


def _leaf(model: dict, key: jax.Array, name: str) -> jax.Array:
    shape, fan_in = leaf_shapes(model)[name]
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, LEAVES.index(name))
    scale = 0.02 if name == "embed" else 1.0 / math.sqrt(fan_in)
    return jax.random.normal(k, shape, jnp.float32) * scale


def _tree(leaves: dict) -> dict:
    top = ("embed", "lm_head", "final_norm")
    return {**{n: leaves[n] for n in top},
            "layers": {n: a for n, a in leaves.items() if n not in top}}


def make_params(model: dict, seed: int) -> dict:
    """The whole tree in one jitted program."""
    def build(key):
        return _tree({n: _leaf(model, key, n) for n in LEAVES})
    return jax.jit(build)(seed_key(seed))


def flat(params: dict) -> dict:
    """name -> array, the stacked layer leaves beside the top-level ones."""
    return {**{n: a for n, a in params.items() if n != "layers"},
            **params["layers"]}


def change_norms(model: dict, seed: int, params: dict) -> dict:
    """Per leaf, the norm of ``params - make_params(model, seed)``, a
    stacked leaf layer by layer: the initial leaf is made again inside the
    program that reduces it, one leaf at a time, so no second tree is ever
    held."""
    key = seed_key(seed)
    out = {}
    for name, arr in flat(params).items():
        axes = tuple(range(1, arr.ndim)) if name in params["layers"] else None
        fn = jax.jit(lambda a, k, name=name, axes=axes: jnp.sqrt(jnp.sum(
            jnp.square(a - _leaf(model, k, name)), axis=axes)))
        out[name] = fn(arr, key)
    return {n: jax.device_get(v) for n, v in out.items()}
