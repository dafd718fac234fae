"""The jitted AdamW step of a training cell, for any family's loss.

It is the step a ``JaxTrainer`` worker runs (``chip_smoke.py``
``_train_step``): ``value_and_grad`` of the loss with ``optax.adamw``,
parameters and optimizer state donated, and the batch drawn inside the
step from the seed's key and the step's index, so no input pipeline
stalls it. The program has no function that builds it yet; once it has,
this body becomes a call of that function (PERF.md, Open questions).
"""

from __future__ import annotations


def adamw_step(loss, batch_of, hp: dict):
    """``loss(params, tokens, targets)`` and ``batch_of(key, index)`` ->
    (step, init): the compiled step, (params, opt_state, key, index) ->
    (params, opt_state, loss) with its state donated, and the function
    that makes the optimizer's state from the parameters. ``hp`` is the
    configuration file's ``step``."""
    import jax
    import optax

    from ray_tpu.ops import backend

    opt = optax.adamw(learning_rate(hp), b1=hp["b1"], b2=hp["b2"],
                      eps=hp["eps"], weight_decay=hp["weight_decay"])

    def step(params, opt_state, key, index):
        tokens, targets = batch_of(key, index)
        value, grads = jax.value_and_grad(
            lambda p: loss(p, tokens, targets))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, value

    donate = () if backend.on_cpu() else (0, 1)
    return jax.jit(step, donate_argnums=donate), opt.init


def first_moment(opt_state):
    """Adam's first moment out of optax's state, whatever wraps it."""
    for part in opt_state:
        if hasattr(part, "mu"):
            return part.mu
    raise ValueError("no Adam state in the optimizer's state")


def learning_rate(hp: dict):
    """What ``optax.adamw`` is handed: the rate as a float, or, where the
    configuration's ``step`` names ``warmup_steps``, the linear warm-up
    from 0 to it (DeepSeek-V3's report, arXiv:2412.19437, section 4.2: 2 K
    steps, linear from 0) as a function of optax's count of updates so
    far."""
    if "warmup_steps" not in hp:
        return hp["learning_rate"]
    import jax.numpy as jnp

    return lambda count: hp["learning_rate"] * jnp.minimum(
        1.0, count / hp["warmup_steps"])
