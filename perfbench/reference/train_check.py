"""What decides ``correct`` for a training step.

The program's first three steps, taken in set-up through the window's own
compiled step, are followed by the plain reference: the same seeded
weights and batches, float32 at ``highest``, AdamW written out. Compared:

- each step's loss (the gap against the reference's);
- the first gradient as the optimizer got it, ``mu / (1 - b1)`` of the
  state after one step, by the worst leaf;
- the parameters' change after the three steps, by the worst leaf.

A leaf is one layer's slice of a stacked weight. Norms are compared as
norms: the gap between the program's and the reference's, against the
reference's norm of that leaf or of the median leaf, whichever is larger.
Leaves whose reference gradient is under a thousandth of the median
leaf's move under Adam by round-off alone and are left out of the change.
"""

from __future__ import annotations

import statistics

from perfbench import harness, weights
from perfbench.reference import model as ref


def leaf_norms(tree: dict) -> dict:
    """name.layer -> norm, computed on the device, read back as floats."""
    import jax
    import jax.numpy as jnp

    def norms(t):
        out = {}
        for name, a in weights.flat(t).items():
            a = a.astype(jnp.float32)
            if name in t.get("layers", {}):
                out[name] = jnp.sqrt(jnp.sum(
                    a * a, axis=tuple(range(1, a.ndim))))
            else:
                out[name] = jnp.sqrt(jnp.sum(a * a))
        return out

    return layerwise({n: v for n, v in jax.jit(norms)(tree).items()})


def layerwise(norms: dict) -> dict:
    """Split a stacked leaf's vector of per-layer norms into leaves; a
    scalar (a whole stacked leaf reduced at once) stays one leaf."""
    import numpy as np

    out = {}
    for name, v in norms.items():
        v = np.asarray(v, dtype=np.float64)
        if v.ndim == 0:
            out[name] = float(v)
        else:
            out.update({f"{name}.{i}": float(x) for i, x in enumerate(v)})
    return out


def reference_steps(model: dict, hp: dict, seed: int, n: int, batch_of,
                    mm: str = "highest", log=print) -> dict:
    """Losses, first-gradient norms and change norms of ``n`` reference
    steps; ``mm`` picks the matmul (``int8``: the control)."""
    import jax
    import jax.numpy as jnp

    matmul = ref.MATMULS[mm]
    key = weights.seed_key(seed)
    params = weights.make_params(model, seed)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    mu, nu = zeros(params), zeros(params)

    # The key is an argument: a constant would make every seed a program
    # of its own, compiled anew in every run.
    def step(params, mu, nu, key, index):
        tokens, targets = batch_of(key, index, hp["batch"], hp["seq_len"],
                                   model["vocab_size"])
        loss, grads = jax.value_and_grad(
            lambda p: ref.loss(model, p, tokens, targets, matmul))(params)
        params, mu, nu = ref.adamw_step(params, mu, nu, grads,
                                        (index + 1).astype(jnp.float32), hp)
        return params, mu, nu, loss

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    out = {"loss": []}
    for i in range(n):
        params, mu, nu, loss = step(params, mu, nu, key, jnp.int32(i))
        out["loss"].append(float(loss))
        if i == 0:
            out["grad"] = {k: v / (1.0 - hp["b1"])
                           for k, v in leaf_norms(mu).items()}
    out["change"] = layerwise(weights.change_norms(model, seed, params))
    log(f"reference ({mm}): losses {out['loss']}")
    return out


def worst_leaf(got: dict, want: dict, leaves=None) -> tuple:
    """(gap, leaf): the widest gap of norms over ``leaves``."""
    leaves = sorted(want) if leaves is None else leaves
    median = statistics.median(want[k] for k in leaves)
    gaps = {k: abs(got[k] - want[k]) / max(want[k], median, 1e-30)
            for k in leaves}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def moved_leaves(want: dict) -> list:
    """Leaves whose reference gradient is worth following under Adam."""
    median = statistics.median(want["grad"].values())
    return sorted(k for k, g in want["grad"].items() if g >= 1e-3 * median)


def numbers(got: dict, want: dict) -> dict:
    """Every number compared, before its limit: name -> (value, note)."""
    out = {}
    for i, (a, b) in enumerate(zip(got["loss"], want["loss"])):
        out[f"loss_gap_step{i + 1}"] = (abs(a - b) / abs(b), "")
    out["grad_norm_gap"] = worst_leaf(got["grad"], want["grad"])
    out["change_norm_gap"] = worst_leaf(got["change"], want["change"],
                                        moved_leaves(want))
    return out


def compare(got: dict, want: dict, limits: dict, log=print) -> dict:
    checks = {}
    for name, (value, note) in numbers(got, want).items():
        log(f"{name}: {value:.6g} {note}")
        if name in limits:
            checks[name] = harness.check(value, limits[name])
    return checks
