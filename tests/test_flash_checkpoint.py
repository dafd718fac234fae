"""A checkpointed layer keeps the flash forward's output and log-sum-exp.

``ops/flash_attention.py`` names the forward's two results (``FLASH_OUT``,
``FLASH_LSE``), the residuals its backward kernels read, and the layer
checkpoint of ``models/transformer.py:_scan_layers`` saves those names
(``_KEEP_FLASH``). So the backward pass makes the rest of the layer again
and runs no flash forward a second time. On the CPU the kernels run
interpreted: a layer scanned and checkpointed at toy shapes, and the model's
own stack with the flash path forced, hold the gradient to one forward
kernel an application and to the gradients of the checkpoint that keeps
nothing, bit for bit; without a checkpoint the names change nothing.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.models import TransformerConfig, init_params, loss_fn
from ray_tpu.models import transformer
from ray_tpu.ops import flash_attention

fa = importlib.import_module("ray_tpu.ops.flash_attention")

KEEP = jax.checkpoint_policies.save_only_these_names(fa.FLASH_OUT,
                                                     fa.FLASH_LSE)
# 2 heads of 16 over 32 positions in blocks of 16; 3 layers in the scan.
B, H, S, D, LAYERS = 1, 2, 32, 16, 3


def _eqns(jaxpr):
    """Every equation under ``jaxpr``, loop, checkpoint and kernel bodies
    included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for inner in param if isinstance(param, tuple) else (param,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _kernels(fn, *args):
    """The names of the Pallas calls in ``fn``'s jaxpr, one a call."""
    return sorted(eqn.params["name"]
                  for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
                  if eqn.primitive.name == "pallas_call")


def _toy(per_body: int, remat: bool, policy):
    """A scan over ``LAYERS`` units of ``per_body`` attention layers: q, k
    and v projected from the stream, flash attention interpreted, the
    output projected back and added; each layer checkpointed under
    ``policy`` where ``remat``. -> the loss of (stream, weights)."""

    def layer(x, w):
        q, k, v = (jnp.tanh(x @ w[i]).reshape(B, S, H, D).transpose(0, 2, 1, 3)
                   for i in range(3))
        o = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
        return x + o.transpose(0, 2, 1, 3).reshape(B, S, H * D) @ w[3]

    def body(x, ws):
        for w in ws:
            x = jax.checkpoint(layer, policy=policy)(x, w) if remat \
                else layer(x, w)
        return x, None

    def loss(x, ws):
        return jnp.sum(lax.scan(body, x, ws)[0] ** 2)

    return loss


def _toy_args(per_body: int):
    kx, kw = jax.random.split(jax.random.PRNGKey(49))
    x = jax.random.normal(kx, (B, S, H * D), jnp.float32)
    ws = tuple(0.2 * jax.random.normal(key, (LAYERS, 4, H * D, H * D),
                                       jnp.float32)
               for key in jax.random.split(kw, per_body))
    return x, ws


@pytest.mark.parametrize("per_body", [1, 2])
def test_the_kept_names_leave_one_forward_kernel_an_application(per_body):
    """Under the policy the gradient's jaxpr holds one forward kernel for
    each layer of the scan's body, beside its two backward kernels; the
    checkpoint that keeps nothing holds two, the forward and the one it
    makes again."""
    args = _toy_args(per_body)
    grad = jax.grad(_toy(per_body, True, KEEP), argnums=(0, 1))
    assert _kernels(grad, *args) == sorted(
        ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"] * per_body)
    plain = jax.grad(_toy(per_body, True, None), argnums=(0, 1))
    assert _kernels(plain, *args).count("flash_fwd") == 2 * per_body


@pytest.mark.parametrize("per_body", [1, 2])
def test_the_kept_names_give_the_gradients_of_the_checkpoint_that_keeps_nothing(
        per_body):
    """The kept output and log-sum-exp are what the forward kernel makes
    again from the same inputs, so the loss and every gradient are the
    policy-less checkpoint's, bit for bit, and the unchecked layer's."""
    args = _toy_args(per_body)
    runs = [jax.jit(jax.value_and_grad(_toy(per_body, remat, policy),
                                       argnums=(0, 1)))(*args)
            for remat, policy in ((True, KEEP), (True, None), (False, None))]
    for other in runs[1:]:
        for a, b in zip(jax.tree.leaves(runs[0]), jax.tree.leaves(other)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_names_change_nothing_without_a_checkpoint(monkeypatch):
    """With no checkpoint the names are inert: the kernel's output and
    gradients, and the text the gradient lowers to, are those of the same
    kernel with its results unnamed."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(key, (B, H, S, D), jnp.float32)
               for key in (kq, kk, kv))

    def run(*a):
        return flash_attention(*a, block_q=16, block_k=16, interpret=True)

    def grad(*a):
        return jax.grad(lambda *b: jnp.sum(run(*b) ** 2),
                        argnums=(0, 1, 2))(*a)

    named = (run(q, k, v), grad(q, k, v),
             jax.jit(grad).lower(q, k, v).as_text())
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    unnamed = (run(q, k, v), grad(q, k, v),
               jax.jit(grad).lower(q, k, v).as_text())
    for a, b in zip(jax.tree.leaves(named[:2]), jax.tree.leaves(unnamed[:2])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert named[2] == unnamed[2]


# A looped stack (2 layers run 3 times) and a plain one asking for ``remat``:
# 2 heads of 16 over 32 positions, float32.
MODELS = {
    "looped": TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=48, max_seq_len=32, dtype=jnp.float32, total_ut_steps=3,
        sandwich_norm=True),
    "remat": TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=48, max_seq_len=32, dtype=jnp.float32, remat=True),
}


@pytest.fixture
def flash_on_cpu(monkeypatch):
    """The model's attention takes the flash kernel (interpreted on the
    CPU) where it would take the dense form."""
    monkeypatch.setattr(fa, "use_flash", lambda *a, **k: True)


@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_model_checkpoint_keeps_the_flash_residuals(model, monkeypatch,
                                                        flash_on_cpu):
    """``_scan_layers``' checkpoint, in a looped stack and where ``remat``
    asks for it: one forward kernel in the loss's gradient, where the
    checkpoint that keeps nothing has two, and the same loss and gradients
    bit for bit."""
    cfg = MODELS[model]
    params = init_params(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 32), 0,
                                cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)

    def step(p):
        return jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, targets))(p)

    kept = _kernels(step, params)
    kept_value = jax.jit(step)(params)
    monkeypatch.setattr(transformer, "_KEEP_FLASH", None)
    jax.clear_caches()   # the traces made under the policy
    assert kept == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    assert _kernels(step, params).count("flash_fwd") == 2
    plain_value = jax.jit(step)(params)
    for a, b in zip(jax.tree.leaves(kept_value), jax.tree.leaves(plain_value)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_policy_keeps_nothing_more_where_attention_is_dense(monkeypatch):
    """On the CPU's dense path no value carries the names, so the policy
    keeps what the checkpoint that keeps nothing keeps: the gradient's
    jaxpr is the same equations in the same order."""
    cfg = MODELS["looped"]
    params = init_params(cfg, jax.random.PRNGKey(3))
    tokens = jnp.zeros((1, 32), jnp.int32)

    def primitives():
        grad = jax.grad(lambda p: loss_fn(cfg, p, tokens, tokens))
        return [eqn.primitive.name
                for eqn in _eqns(jax.make_jaxpr(grad)(params).jaxpr)]

    kept = primitives()
    monkeypatch.setattr(transformer, "_KEEP_FLASH", None)
    jax.clear_caches()
    assert "pallas_call" not in kept and kept == primitives()
