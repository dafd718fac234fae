"""Flagship-model tests: GSPMD forward + manual SPMD train-step parity.

The strongest correctness statement in the suite: one optimizer step of the
fully-sharded (dp/fsdp/pp/tp/sp) shard_map training step must match a
single-device step bit-for-bit-ish (fp32 tolerance) — collective-by-
collective parity with the unsharded math.
"""

import jax
import jax.numpy as jnp
import optax
import pytest

pytestmark = pytest.mark.slow  # compile/learning-heavy; default keeps test_parallel + test_rl_async coverage

from ray_tpu.models import (
    TransformerConfig,
    init_params,
    loss_fn,
    make_spmd_train_step,
)
from ray_tpu.parallel import make_mesh

DENSE = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=4, n_heads=4, n_kv_heads=4,
    d_ff=64, dtype=jnp.float32)


def _data(cfg, B, S):
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    tgts = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              cfg.vocab_size)
    return toks, tgts


def test_forward_shapes_and_loss_finite():
    params = init_params(DENSE, jax.random.PRNGKey(0))
    toks, tgts = _data(DENSE, 2, 16)
    loss = loss_fn(DENSE, params, toks, tgts)
    assert jnp.isfinite(loss)
    # random init ≈ uniform over vocab
    assert abs(float(loss) - jnp.log(DENSE.vocab_size)) < 1.5


@pytest.mark.parametrize(
    "cfg,mesh_kw,B,mb",
    [
        (DENSE, dict(dp=2, tp=2, sp=2), 4, 1),
        (DENSE, dict(dp=2, fsdp=2, pp=2), 8, 2),
    ],
    ids=["dp-tp-sp", "dp-fsdp-pp"],
)
def test_spmd_step_matches_single_device(eight_device_mesh, cfg, mesh_kw,
                                         B, mb):
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks, tgts = _data(cfg, B, 16)
    l0 = float(loss_fn(cfg, params, toks, tgts))

    g = jax.grad(lambda p: loss_fn(cfg, p, toks, tgts))(params)
    pref = jax.tree.map(lambda p, gr: p - 0.1 * gr, params, g)

    mesh = make_mesh(**mesh_kw)
    opt = optax.sgd(0.1)
    step, pspec, ospec = make_spmd_train_step(
        cfg, mesh, params, optimizer=opt, n_microbatches=mb)
    p2, _, loss = step(params, opt.init(params), toks, tgts)
    assert abs(float(loss) - l0) < 1e-3
    for a, b in zip(jax.tree.leaves(pref),
                    jax.tree.leaves(jax.device_get(p2))):
        assert jnp.allclose(a, b, atol=2e-3), "param mismatch after step"


def test_graft_entry_importable():
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).parent.parent / "__graft_entry__.py"
    spec = importlib.util.spec_from_file_location("__graft_entry__", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn, args = mod.entry()
    out = jax.eval_shape(fn, *args)  # jittable: abstract eval must work
    assert out.shape == ()
