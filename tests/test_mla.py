"""Latent attention in the training body, and the flash kernels at a value
width of their own (``ops/flash_attention.py``): interpreted on the CPU
against the dense fallback, forward and all three gradients."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness
from perfbench.reference.numerics import mm_highest
from ray_tpu.models import transformer
from ray_tpu.ops import flash_attention
from test_ling3_model import FAMILY, MODEL, REF, SEED, _cfg, _rel

fa = importlib.import_module("ray_tpu.ops.flash_attention")


def _qkv(d, dv, seed=0, s=64, heads=2, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda width: (1, heads, s, width)
    return (jax.random.normal(ks[0], shape(d), dtype),
            jax.random.normal(ks[1], shape(d), dtype),
            jax.random.normal(ks[2], shape(dv), dtype),
            jax.random.normal(ks[3], shape(dv), jnp.float32))


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("d,dv", [(24, 16), (16, 24)])
def test_flash_at_two_widths_matches_the_fallback(d, dv, causal):
    q, k, v, w = _qkv(d, dv)
    kernel = lambda *a: flash_attention(*a, causal=causal, block_q=32,
                                        block_k=16, interpret=True)
    dense = lambda *a: fa._fallback(*a, causal, d ** -0.5)
    got, want = kernel(q, k, v), dense(q, k, v)
    assert got.shape == want.shape == (1, 2, 64, dv)
    assert _rel(got, want) < 2e-6
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * w),
                               argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", grads(kernel), grads(dense)):
        assert a.shape == b.shape and _rel(a, b) < 5e-6, name


def test_equal_widths_run_what_they_ran():
    """At one width for all three the kernels are the parent's (their
    traced programs at the two cells' shapes were compared with the parent
    commit's and are the same text: PERF.md, PR 39). Here: what a call with
    wider values computes for the columns a narrower call also has is the
    same to float32's rounding (each column of ``p v`` is a sum of its
    own), and the accepted shapes are the ones that were."""
    q, k, v, _w = _qkv(24, 24, seed=1)
    run = lambda v: flash_attention(q, k, v, block_q=32, block_k=32,
                                    interpret=True)
    assert _rel(run(v)[..., :16], run(v[..., :16])) < 1e-6
    for s, d in ((4096, 128), (8192, 64), (4096, 192)):
        args = (s, s, d, 2, 512, 512)
        assert fa.kernel_accepts(*args, interpret=False) \
            == fa.kernel_accepts(*args, interpret=False, dv=d)
    # the wider of the two widths is what a head's array in VMEM is held to
    assert fa.kernel_accepts(4096, 4096, 192, 2, 512, 512, interpret=False,
                             dv=128)
    assert fa.kernel_accepts(4096, 4096, 128, 2, 512, 512, interpret=False,
                             dv=192)
    assert not fa.kernel_accepts(8192, 8192, 128, 2, 512, 512,
                                 interpret=False, dv=256)
    assert not fa.kernel_accepts(64, 64, 24, 4, 32, 32, interpret=True, dv=12)


def test_the_model_path_hands_the_kernels_both_widths(monkeypatch):
    """``_attention_dense`` at q/k of 24 and v of 16: where the kernels are
    taken (forced here, interpreted) the result is the dense path's."""
    q, k, v, _w = _qkv(24, 16, seed=2)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))     # [B,S,H,D]
    want = transformer._attention_dense(q, k, v)
    seen = {}

    def use_flash(sq, sk, d, dtype, dv=None):
        seen.update(d=d, dv=dv)
        return True

    monkeypatch.setattr(fa, "use_flash", use_flash)
    monkeypatch.setattr(fa, "_auto_block", lambda seq, cap=512: 32)
    got = transformer._attention_dense(q, k, v)
    assert seen == {"d": 24, "dv": 16} and got.shape == (1, 64, 2, 16)
    assert _rel(got, want) < 2e-6


@pytest.mark.parametrize("family", ["ling3", "joyai"])
def test_the_layer_is_the_references(family):
    """One MLA layer of the program against its family's reference: the
    latent's norm, the shared rotary key, the two widths; the Ling family's
    one query matrix and gate a head, the JoyAI family's query latent with
    a norm of its own and no gate (its reference turns the rotary pairs
    interleaved and puts the tree's columns back first)."""
    if family == "ling3":
        cfg, model, ref = _cfg(), MODEL, REF
        params = FAMILY.make_params(MODEL, SEED)
        lp = jax.tree.map(lambda a: a[0], params["layers"]["mla_moe"])
        published = lp
    else:
        import test_joyai_model as joyai

        cfg, model, ref = joyai._cfg(), joyai.MODEL, joyai.REF
        params = joyai.FAMILY.make_params(model, SEED)
        lp = jax.tree.map(lambda a: a[0], params["layers"]["mla_moe"])
        published = ref.published_columns(model, lp)
        assert set(lp) >= {"mla_q_a", "mla_q_norm", "mla_q_b"}
        assert "mla_q" not in lp and "mla_gate" not in lp
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 48, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        q, k_nope, k_rope, v, gate = transformer._project_mla(
            cfg, lp, x, jnp.arange(48)[None])
    assert q.shape == (1, 48, 2, 24) and k_nope.shape == (1, 48, 2, 16)
    assert k_rope.shape == (1, 48, 1, 8) and v.shape == (1, 48, 2, 16)
    assert gate is None if family == "joyai" else gate.shape == (1, 48, 2)
    keys = transformer._mla_keys(k_nope, k_rope)
    np.testing.assert_array_equal(keys[:, :, 0, 16:], keys[:, :, 1, 16:])
    z = ref.rms_norm(x[0], lp["mla_norm"], model["rms_norm_eps"])
    want = ref.mla(model, published, z, mm_highest)
    with jax.default_matmul_precision("highest"):
        o = transformer._attention_dense(q, keys, v)
        got = transformer._mla_out(cfg, lp, x, o, gate)[0] - x[0]
    assert _rel(got, want) < 2e-5
    # a model whose heads take no rotary part runs too (rank and widths
    # are the configuration's, not the operator's)
    bare = dataclasses.replace(cfg, qk_rope_dim=0)
    leaves = transformer._kind_leaves(bare, "mla_dense")
    query = "mla_q" if family == "ling3" else "mla_q_b"
    assert leaves[query][0] == (cfg.q_lora_rank or 32, 2 * 16)
    assert leaves["mla_kv_a"][0] == (32, 16)
