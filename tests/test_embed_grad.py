"""``models/transformer.py:_embed`` and its backward pass of its own.

The look-up is one ``jax.custom_vjp``: forward the rows read and nothing
else of the table, bit for bit ``table.astype(dt)[tokens]``; backward the
table's cotangent, the rows' cotangents summed at their ids in float32.
Held here to the two lines they replace, which live in this file alone:
the one-line look-up whose transpose JAX makes itself, and the scatter-add
into float32 zeros.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import TransformerConfig, init_params, loss_fn
from ray_tpu.models import transformer

V, D = 37, 80       # a width that is no power of two and no multiple of 128


def _one_line_embed(cfg, params, tokens):
    """``_embed`` as it was until PR 46."""
    return params["embed"].astype(cfg.dtype)[tokens]


def _ids(case):
    key = jax.random.PRNGKey(3)
    if case == "drawn":
        return jax.random.randint(key, (2, 16), 0, V)
    if case == "one_id":
        return jnp.full((2, 16), 5, jnp.int32)
    if case == "ends":
        return jnp.where(jax.random.bernoulli(key, 0.5, (2, 16)), 0, V - 1)
    if case == "column":
        return jax.random.randint(key, (4, 1), 0, V)
    raise ValueError(case)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", ["drawn", "one_id", "ends", "column"])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_the_lookup_and_its_table_gradient(tied, case, dtype):
    """Untied, the rows are read and cast; tied, the head's copy of the whole
    table is read: the same rows and the same gradient either way."""
    cfg = TransformerConfig(vocab_size=V, d_model=D, n_layers=1, n_heads=2,
                            n_kv_heads=2, d_ff=16, tie_embeddings=tied,
                            dtype=dtype)
    table = jax.random.normal(jax.random.PRNGKey(0), (V, D), jnp.float32)
    tokens = _ids(case)
    rows, vjp = jax.vjp(
        lambda t: transformer._embed(cfg, {"embed": t}, tokens), table)
    assert rows.dtype == dtype and rows.shape == tokens.shape + (D,)
    np.testing.assert_array_equal(
        np.asarray(rows.astype(jnp.float32)),
        np.asarray(table.astype(dtype)[tokens].astype(jnp.float32)))
    g = jax.random.normal(jax.random.PRNGKey(1), rows.shape, dtype)
    (got,) = vjp(g)
    want = jnp.zeros((V, D), jnp.float32).at[tokens].add(
        g.astype(jnp.float32))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    if case == "one_id":
        assert not np.asarray(got)[np.arange(V) != 5].any()


def test_a_table_kept_in_the_rows_type_gets_a_gradient_of_its_type():
    """Serving's parameters may be bfloat16: the sum is still made in
    float32 and cast once."""
    cfg = TransformerConfig(vocab_size=V, d_model=D, n_layers=1, n_heads=2,
                            n_kv_heads=2, d_ff=16, dtype=jnp.bfloat16)
    table = jax.random.normal(jax.random.PRNGKey(0), (V, D), jnp.bfloat16)
    tokens = _ids("one_id")
    rows, vjp = jax.vjp(
        lambda t: transformer._embed(cfg, {"embed": t}, tokens), table)
    g = jax.random.normal(jax.random.PRNGKey(1), rows.shape, jnp.bfloat16)
    (got,) = vjp(g)
    want = jnp.zeros((V, D), jnp.float32).at[tokens].add(
        g.astype(jnp.float32)).astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_the_models_gradient_is_the_one_line_lookups(tied, monkeypatch):
    """Of a tiny ``loss_fn`` in float32, where both sums are float32: with
    a tied table the gradient holds the look-up's use and the head's."""
    cfg = TransformerConfig(vocab_size=V, d_model=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=64, tie_embeddings=tied,
                            dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, V)
    targets = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, V)
    grad = lambda: jax.grad(
        lambda p: loss_fn(cfg, p, tokens, targets))(params)
    got = grad()
    monkeypatch.setattr(transformer, "_embed", _one_line_embed)
    want = grad()
    assert ("lm_head" in params) != tied
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("width,blocks", [
    (80, [64, 16]), (64, [64]), (2560, [2048, 512])])
def test_rows_are_summed_a_power_of_two_block_of_columns_at_a_time(width,
                                                                   blocks):
    """XLA's scatter-add costs six times as much a row at 2560 columns as at
    2048 or 4096 on the v5e, and more at other widths that are no power of
    two (``PERF.md`` section 5, PR 46), so the backward pass sums each
    power-of-two block of columns into a table of its own and joins them; a
    power-of-two width is one block, summed as it is."""
    cfg = TransformerConfig(vocab_size=V, d_model=width, n_layers=1,
                            n_heads=2, n_kv_heads=2, d_ff=16,
                            dtype=jnp.bfloat16)
    tokens = _ids("drawn")
    text = jax.jit(jax.grad(lambda t: jnp.sum(transformer._embed(
        cfg, {"embed": t}, tokens).astype(jnp.float32)))).lower(
        jax.ShapeDtypeStruct((V, width), jnp.float32)).as_text()
    # the scatter's operand, ids and rows, after its body's closing line
    sums = re.findall(r"\}\) : \(tensor<(\d+)x(\d+)xf32>, tensor<[\dx]+xi32>,",
                      text)
    assert sums == [(str(V), str(w)) for w in blocks], sums
    assert ("stablehlo.concatenate" in text) == (len(blocks) > 1)
