"""The layer scan runs over the matmuls' weights in the activations' type,
cast once before ``lax.scan`` (``models/transformer.py:_scan_layers``), and
that moves nothing but time.

One tiny bfloat16 configuration of each layout: the flat dense stack, the
conv / attention pattern with held experts, the KDA / MLA pattern with a
shared expert. The reference is the scan as it was before: over the float32
masters, every cast made by the layer at its use. Then the same three
programs' text: which type the scans hold each leaf in, what the backward
scans read and hand back, and that no layer casts a matrix any more.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.models import TransformerConfig, init_params, loss_fn
from ray_tpu.models import transformer

BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
TINY = dict(vocab_size=64, d_model=32, d_ff=80, dtype=jnp.bfloat16)
EXPERTS = dict(num_dense_layers=1, moe_d_ff=16, router_score="sigmoid",
               norm_topk=True, expert_bias=True)
FLAT = TransformerConfig(n_layers=3, n_heads=4, n_kv_heads=2, **TINY)
CONFIGS = {
    "flat": FLAT,
    "flat-remat": dataclasses.replace(FLAT, remat=True),
    # LFM2's layout: a dense conv layer and one period, 3 of 8 experts held
    "conv-attention-experts": TransformerConfig(
        n_layers=5, n_heads=4, n_kv_heads=2, qk_norm=True,
        tie_embeddings=True,
        layer_types=("conv", "attention", "conv", "conv", "conv"),
        router_experts=8, experts_held=(1, 4, 6), experts_per_token=2,
        **EXPERTS, **TINY),
    # Ling's: KDA and MLA layers, a kind's stack in two runs, groups, a
    # shared expert
    "kda-mla-shared-expert": TransformerConfig(
        n_layers=5, n_heads=2, n_kv_heads=2, head_dim=16, conv_kernel=4,
        layer_types=("kda", "kda", "mla", "kda", "kda"), kv_lora_rank=16,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, router_experts=32,
        experts_held=(1, 6, 9, 17), experts_per_token=4, routed_scale=2.5,
        router_groups=4, router_groups_kept=2, shared_d_ff=24, **EXPERTS,
        **TINY),
}
# What the layer reads in float32 and must reach it so, whatever the
# description says of it.
READ_IN_F32 = ("router", "expert_bias", "kda_dt_bias", "kda_a_log")


def _parents_scan_layers(cfg, kinds, stacks, x, positions, attention,
                         tp_axis, constrain, layers=None):
    """``_scan_layers`` at the parent of the PR that cast the stacks once
    (where its caller cut a run out of the stack): the reference. It knew
    runs of one kind, which is all these configurations have."""
    (kind,), (stack,) = kinds, stacks
    if layers is not None:
        stack = jax.tree.map(lambda a: a[layers[0]], stack)

    def body(x, lp):
        run = partial(transformer._layer, cfg, kind, lp, positions=positions,
                      attention=attention, tp_axis=tp_axis)
        x, _load = jax.checkpoint(run)(x) if cfg.remat else run(x)
        return constrain(x, "batch", "sequence", "embed"), None

    return lax.scan(body, x, stack)[0]


def _params(cfg):
    """A seeded tree whose zeros and ones are drawn too: a rate, a bias or a
    norm at its initial value hides a rounding of it."""
    params = init_params(cfg, jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 256))

    def drawn(path, a):
        if a.ndim > 2 or "embed" in jax.tree_util.keystr(path):
            return a
        return a + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype)

    return dict(params, layers=jax.tree_util.tree_map_with_path(
        drawn, params["layers"]))


def _batch(cfg):
    seq = 72 if cfg.layer_types and "kda" in cfg.layer_types else 24
    draw = lambda seed: jax.random.randint(
        jax.random.PRNGKey(seed), (2, seq), 0, cfg.vocab_size)
    return draw(1), draw(2)


def _value_and_grad(cfg):
    tokens, targets = _batch(cfg)
    return jax.value_and_grad(lambda p: loss_fn(cfg, p, tokens, targets))


@pytest.mark.parametrize("layout", list(CONFIGS))
def test_casting_the_stacks_once_moves_nothing_but_time(layout, monkeypatch):
    cfg = CONFIGS[layout]
    params = _params(cfg)
    reached = {}
    layer = transformer._layer

    def spy(cfg, kind, lp, *args, **kwargs):
        reached.setdefault(kind, {}).update(
            {name: a.dtype for name, a in lp.items()})
        return layer(cfg, kind, lp, *args, **kwargs)

    monkeypatch.setattr(transformer, "_layer", spy)
    loss, grads = jax.jit(_value_and_grad(cfg))(params)
    monkeypatch.setattr(transformer, "_layer", layer)
    monkeypatch.setattr(transformer, "_scan_layers", _parents_scan_layers)
    want_loss, want = jax.jit(_value_and_grad(cfg))(params)

    # Bit for bit, every leaf: the casts and the products are the parent's,
    # made at another time. But the held experts' three: their gradient is a
    # float32 product rounded to bfloat16 and widened again, and this
    # backend's compiler drops that rounding where both casts stand side by
    # side, as in the reference (``lax.ragged_dot``'s transpose alone: the
    # parent's values there are no bfloat16 numbers). Between them now lies
    # the scan's stack, so the rounding is made: the reference's value
    # rounded, to the bit.
    assert float(loss) == float(want_loss)
    assert jax.tree.structure(grads) == jax.tree.structure(params)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        assert got.dtype == F32, name
        if path[-1].key in ("e_gate", "e_up", "e_down"):
            ref = ref.astype(BF16).astype(F32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref), name)
    assert any(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(grads))

    # Each leaf reached the layer in the type the description gives, the
    # float32 reads among them as float32 and the matrices as bfloat16.
    assert set(reached) == {k for k, _, _ in transformer.layer_runs(cfg)}
    for kind, types in reached.items():
        described = transformer._kind_leaves(cfg, kind)
        assert types == {name: leaf[3] for name, leaf in described.items()}
        for name, dtype in types.items():
            if name in READ_IN_F32 or name.endswith("norm"):
                assert dtype == F32, (kind, name)
            elif len(described[name][0]) > 1 and "taps" not in name:
                assert dtype == BF16, (kind, name)


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            if isinstance(v, jax.extend.core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jax.extend.core.Jaxpr):
                yield v


def _casts_of(jaxpr, leaves):
    """The ``convert_element_type`` equations that read one of the variables
    ``leaves`` of ``jaxpr``, here or in a program inside it to which an
    equation hands the variable on (a checkpoint, a custom derivative, a
    loop: their operands are their programs' inputs, one for one)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            a, = eqn.invars
            if isinstance(a, jax.extend.core.Var) and a in leaves:
                yield eqn
        for sub in _sub_jaxprs(eqn):
            if len(sub.invars) == len(eqn.invars):
                inner = {b for a, b in zip(eqn.invars, sub.invars)
                         if isinstance(a, jax.extend.core.Var) and a in leaves}
                if inner:
                    yield from _casts_of(sub, inner)


def _types(variables):
    return sorted((tuple(v.aval.shape), str(v.aval.dtype)) for v in variables)


def _check_the_scans(cfg, jaxpr):
    """The layer scans of ``value_and_grad(loss_fn)``'s program, a forward
    and a backward one for each run of equal layers."""
    runs = transformer.layer_runs(cfg)
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    forward = [e for e in scans if not e.params["reverse"]]
    backward = [e for e in scans if e.params["reverse"]][::-1]
    assert len(forward) == len(backward) == len(runs)
    for (kind, _start, count), fwd, bwd in zip(runs, forward, backward):
        leaves = transformer._kind_leaves(cfg, kind)
        stacked = {name: ((count,) + leaf[0], str(leaf[3]))
                   for name, leaf in leaves.items()}
        matrices = {s for s, t in stacked.values() if t == "bfloat16"}
        assert len(matrices) >= 4
        xs = lambda e: e.invars[e.params["num_consts"]
                                + e.params["num_carry"]:]
        ys = lambda e: e.outvars[e.params["num_carry"]:]
        # The scan runs over each leaf in the type the layer reads it in,
        assert _types(xs(fwd)) == sorted(stacked.values()), kind
        # keeps no matrix of its own for the backward pass,
        assert not [t for t in _types(ys(fwd)) if t[0] in matrices], kind
        # the backward scan reads the same bfloat16 stacks
        assert (sorted(t for t in _types(xs(bwd)) if t[0] in matrices)
                == sorted(t for t in stacked.values() if t[0] in matrices))
        # and hands the matrices' gradients back as bfloat16 stacks.
        assert (sorted(t for t in _types(ys(bwd)) if t[0] in matrices)
                == sorted(t for t in stacked.values() if t[0] in matrices))
        # No layer, forward or backward, casts a matrix from float32: of
        # the leaves that reach it so, it rounds the norms' weights and the
        # taps. (What a layer kept of its own forward is no leaf: a KDA
        # layer's float32 states are rounded for its products again.)
        small = {leaf[0] for name, leaf in leaves.items()
                 if name.endswith(("norm", "taps"))}
        shapes = {leaf[0] for leaf in leaves.values()}
        for scan in (fwd, bwd):
            body = scan.params["jaxpr"].jaxpr
            slices = {v for v in body.invars[scan.params["num_consts"]
                                             + scan.params["num_carry"]:]
                      if v.aval.dtype == F32}
            casts = list(_casts_of(body, slices))
            assert casts or scan is bwd     # the reading finds the norms'
            for eqn in casts:
                shape = tuple(eqn.invars[0].aval.shape)
                assert (eqn.params["new_dtype"] != BF16 or shape in small
                        or shape not in shapes), (kind, eqn)


LAYOUTS = [name for name in CONFIGS if name != "flat-remat"]


@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_scans_hold_the_matrices_in_bfloat16(layout):
    cfg = CONFIGS[layout]
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    jaxpr = jax.make_jaxpr(_value_and_grad(cfg))(params).jaxpr
    _check_the_scans(cfg, jaxpr)
    # The one cast stands before the scans, its transpose behind them: the
    # float32 masters come in and float32 gradients go out.
    assert {str(v.aval.dtype) for v in jaxpr.invars} == {"float32"}
    assert {str(v.aval.dtype) for v in jaxpr.outvars} == {"float32"}


@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_parents_scan_fails_that_reading(layout, monkeypatch):
    """The reading above tells the two programs apart: over the float32
    masters the scans hold no bfloat16 leaf and the layers cast."""
    cfg = CONFIGS[layout]
    monkeypatch.setattr(transformer, "_scan_layers", _parents_scan_layers)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    jaxpr = jax.make_jaxpr(_value_and_grad(cfg))(params).jaxpr
    with pytest.raises(AssertionError):
        _check_the_scans(cfg, jaxpr)


def test_a_float32_configuration_is_cast_nowhere():
    cfg = dataclasses.replace(CONFIGS["conv-attention-experts"],
                              dtype=jnp.float32)
    for kind, _start, _count in transformer.layer_runs(cfg):
        assert {leaf[3] for leaf in
                transformer._kind_leaves(cfg, kind).values()} == {F32}
