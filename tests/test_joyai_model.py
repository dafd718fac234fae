"""Latent attention with a query latent in every layer and a
multi-token-prediction module in the training body, against the JoyAI
family's plain reference (``perfbench/families/joyai/reference.py``, which
imports nothing of the program, writes every layer out and turns the rotary
pairs interleaved, as published).

Seeded random weights at tiny widths on the CPU. The program in float32
(its matmuls at ``highest``) has to agree with the reference tightly: the
loss, its two parts and every leaf's gradient, the table's and the head's
holding both of their uses. One test ties the cell's cut to the model: over
all the chips that share a layer, the parts add up to the uncut layer.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness
from perfbench.reference.numerics import mm_highest
from ray_tpu.models import (
    decode_step,
    init_kv_cache,
    init_params,
    loss_fn,
    loss_parts,
    TransformerConfig,
)
from ray_tpu.models import transformer

FAMILY = harness.family(["perfbench"], "joyai")
REF = FAMILY.reference
# One dense layer and two expert layers, and the module's one more; 4 of
# the router's 32 experts held.
MODEL = {
    "num_hidden_layers": 3, "hidden_size": 32, "intermediate_size": 48,
    "first_k_dense_replace": 1, "max_position_embeddings": 4096,
    "moe_intermediate_size": 16, "num_experts_per_tok": 4,
    "num_attention_heads": 2, "num_key_value_heads": 2, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "head_dim": 16, "n_routed_experts": 4,
    "n_shared_experts": 1, "rope_theta": 32000000, "rms_norm_eps": 1e-6,
    "vocab_size": 64, "routed_scaling_factor": 2.5, "n_group": 1,
    "topk_group": 1, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "num_nextn_predict_layers": 1,
    "rope_interleave": True, "tie_word_embeddings": False,
    "router_experts": 32, "experts_held": [1, 6, 9, 17],
    "layer_types": ["mla", "mla", "mla"], "mtp_loss_weight": 0.3,
    "rotary_columns": "half_rotation",
    "expert_bias_scale": 0.02}
SEED = 2 ** 31 + 45


def _cfg(model=MODEL, dtype=jnp.float32):
    return dataclasses.replace(FAMILY.model_config(model), dtype=dtype)


def _batch(model=MODEL, batch=2, seq_len=40):
    return FAMILY.batch_of(harness.seed_key(SEED), 0, batch, seq_len,
                           model["vocab_size"])


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def _program_grads(cfg, params, tokens, targets):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, targets)))(params)


@pytest.fixture(scope="module")
def reference_grads():
    params = FAMILY.make_params(MODEL, SEED)
    tokens, targets = _batch()
    loss, grads = jax.jit(jax.value_and_grad(lambda p: FAMILY.reference_loss(
        MODEL, p, tokens, targets, mm_highest)))(params)
    return params, float(loss), grads


def test_the_familys_tree_is_the_programs():
    ours = FAMILY.make_params(MODEL, SEED)
    theirs = jax.eval_shape(lambda: init_params(_cfg(),
                                                jax.random.PRNGKey(0)))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape and a.dtype == b.dtype,
        ours, theirs)))
    assert sum(a.size for a in jax.tree.leaves(ours)) \
        == FAMILY.total_params(MODEL)
    assert transformer.layer_runs(_cfg()) == (
        ("mla_dense", 0, 1), ("mla_moe", 0, 2))
    assert transformer.mtp_kind(_cfg()) == "mla_moe"
    assert set(ours["mtp"]) == {"h_norm", "e_norm", "proj", "out_norm",
                                "block"}
    assert ours["mtp"]["proj"].shape == (1, 64, 32)
    assert "mla_gate" not in ours["mtp"]["block"]
    assert "mla_q" not in ours["layers"]["mla_moe"]
    # the module's layer is drawn apart from the stack's
    assert not np.array_equal(ours["mtp"]["block"]["mla_q_a"][0],
                              ours["layers"]["mla_moe"]["mla_q_a"][0])
    drawn = init_params(_cfg(), jax.random.PRNGKey(7))
    assert not np.array_equal(drawn["mtp"]["block"]["mla_q_a"][0],
                              drawn["layers"]["mla_moe"]["mla_q_a"][0])


def test_float32_loss_its_parts_and_every_gradient_match_the_reference(
        reference_grads):
    params, want_loss, want = reference_grads
    tokens, targets = _batch()
    loss, got = _program_grads(_cfg(), params, tokens, targets)
    assert float(loss) == pytest.approx(want_loss, rel=2e-6)
    with jax.default_matmul_precision("highest"):
        main, extra = jax.jit(functools.partial(loss_parts, _cfg()))(
            params, tokens, targets)
    want_main, want_extra = jax.jit(lambda p: FAMILY.reference_losses(
        MODEL, p, tokens, targets, mm_highest))(params)
    assert float(main) == pytest.approx(float(want_main), rel=2e-6)
    assert float(extra) == pytest.approx(float(want_extra), rel=2e-6)
    assert float(loss) == pytest.approx(float(main) + 0.3 * float(extra),
                                        rel=1e-6)
    gaps = jax.tree.map(_rel, got, want)
    assert max(jax.tree.leaves(gaps)) < 1e-4, gaps
    # the bias selects and does not weigh: no gradient reaches it
    for block in (got["layers"]["mla_moe"], got["mtp"]["block"]):
        assert not np.any(np.asarray(block["expert_bias"]))
    # every leaf of the module takes a gradient
    assert all(np.abs(np.asarray(g)).max() > 0
               for name, g in FAMILY.weights.flat(got).items()
               if name.startswith("mtp") and "expert_bias" not in name)


def test_the_table_and_the_head_hold_both_uses(reference_grads):
    """One table, one head: their gradients are the next-token loss's plus
    0.3 times the module's, which the reference's (checked above) are too;
    with the weight at 0 the module's leaves get none."""
    params, _loss, want = reference_grads
    tokens, targets = _batch()
    cfg = _cfg()
    _l, both = _program_grads(cfg, params, tokens, targets)
    _l, main = _program_grads(dataclasses.replace(cfg, mtp_weight=0.0),
                              params, tokens, targets)
    with jax.default_matmul_precision("highest"):
        module = jax.jit(jax.grad(lambda p: loss_parts(
            cfg, p, tokens, targets)[1]))(params)
    for leaf in ("embed", "lm_head"):
        assert float(jnp.abs(module[leaf]).max()) > 0
        assert _rel(main[leaf] + 0.3 * module[leaf], both[leaf]) < 1e-5
        assert _rel(both[leaf], want[leaf]) < 1e-4
        assert _rel(main[leaf], want[leaf]) > 1e-2
    assert not any(np.any(np.asarray(g)) for g in jax.tree.leaves(
        main["mtp"]))


def test_bfloat16_loss_and_gradients_stay_near_the_reference(
        reference_grads):
    """The stated tolerance, as the other patterns': the loss within 0.5 %,
    the whole gradient within 35 % of the reference's norm (at 80 tokens and
    widths of 32 one top-k choice that flips moves a held expert's whole
    leaf; a missing term reads 100 % or more)."""
    params, want_loss, want = reference_grads
    tokens, targets = _batch()
    loss, got = jax.jit(jax.value_and_grad(lambda p: loss_fn(
        _cfg(dtype=jnp.bfloat16), p, tokens, targets)))(params)
    assert float(loss) == pytest.approx(want_loss, rel=5e-3)
    norm = lambda t: jnp.sqrt(sum(jnp.sum(jnp.square(a))
                                  for a in jax.tree.leaves(t)))
    diff = jax.tree.map(lambda a, b: a - b, got, want)
    assert float(norm(diff) / norm(want)) < 0.35


def test_depth_zero_is_the_program_without_the_module():
    """Bit for bit: the tree's other leaves, the loss (the next-token part
    of the two) and its gradients, and the very operations of the loss as
    it was written before the module (``_next_token_nll(forward(...))``)."""
    cfg = _cfg()
    bare = dataclasses.replace(cfg, mtp_depth=0)
    key = jax.random.PRNGKey(3)
    with_module, without = init_params(cfg, key), init_params(bare, key)
    assert "mtp" not in without
    rest = {k: v for k, v in with_module.items() if k != "mtp"}
    assert all(jax.tree.leaves(jax.tree.map(np.array_equal, rest, without)))
    tokens, targets = _batch()
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(bare, p, tokens, targets)))(without)
    main, main_grads = jax.jit(jax.value_and_grad(
        lambda p: loss_parts(cfg, p, tokens, targets)[0]))(with_module)
    assert float(loss) == float(main)
    assert all(jax.tree.leaves(jax.tree.map(
        np.array_equal, grads,
        {k: v for k, v in main_grads.items() if k != "mtp"})))
    assert loss_parts(bare, without, tokens, targets)[1] is None
    before = lambda p, t, y: transformer._next_token_nll(
        transformer.forward(bare, p, t), y)
    now = lambda p, t, y: loss_fn(bare, p, t, y)
    assert str(jax.make_jaxpr(now)(without, tokens, targets)) \
        == str(jax.make_jaxpr(before)(without, tokens, targets))


def test_the_interleaved_reference_is_the_half_rotation_program():
    """``rope_interleave``: the reference turns the pairs (2i, 2i + 1) of
    the rotary columns as published; the program turns (i, i + 32). Under
    one fixed permutation of the rotary columns of ``W_qb`` (each head's)
    and ``W_kva`` (the even ones first, then the odd) they are one model:
    the reference on a tree in the published order gives the program's
    loss on that tree with those columns moved."""
    published = dict(MODEL)
    del published["rotary_columns"]         # the tree is as published
    params = FAMILY.make_params(published, SEED)
    order = REF.half_rotation_order(MODEL["qk_rope_head_dim"])
    nope, rank = MODEL["qk_nope_head_dim"], MODEL["kv_lora_rank"]

    def moved(block):
        q_b = block["mla_q_b"]
        heads = q_b.reshape(q_b.shape[:2] + (-1, nope + order.size))
        heads = jnp.concatenate(
            [heads[..., :nope], heads[..., nope:][..., order]], -1)
        kv_a = block["mla_kv_a"]
        return {**block, "mla_q_b": heads.reshape(q_b.shape),
                "mla_kv_a": jnp.concatenate(
                    [kv_a[..., :rank], kv_a[..., rank:][..., order]], -1)}

    ours = dict(params, layers={k: moved(v)
                                for k, v in params["layers"].items()},
                mtp=dict(params["mtp"], block=moved(params["mtp"]["block"])))
    tokens, targets = _batch()
    want = jax.jit(lambda p: FAMILY.reference_loss(
        published, p, tokens, targets, mm_highest))(params)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: loss_fn(_cfg(), p, tokens, targets))(ours)
        unmoved = jax.jit(lambda p: loss_fn(_cfg(), p, tokens, targets))(
            params)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    assert abs(float(unmoved) - float(want)) > 1e-4 * float(want)
    # and the tree both sides of a cell are handed is the moved one: the
    # reference puts its columns back itself
    again = jax.jit(lambda p: FAMILY.reference_loss(
        MODEL, p, tokens, targets, mm_highest))(ours)
    assert float(again) == pytest.approx(float(want), rel=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """The deployment in small: a layer of 32 routed experts over 16 chips,
    2 each. The sixteen shares of the routed result sum to the whole
    layer's, with latent attention, the shared expert and the residual,
    which every chip computes alike, counted once."""
    experts, shares = 32, 16
    whole = dict(MODEL, n_routed_experts=experts, router_experts=experts,
                 experts_held=list(range(experts)), num_hidden_layers=2,
                 layer_types=["mla", "mla"])
    params = FAMILY.make_params(whole, SEED)
    lp = REF.layers_of(whole, params)[1][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 32), jnp.float32)
    want = REF.layer(whole, "mla_moe", lp, x, mm_highest)
    h = REF.operator(whole, lp, x, mm_highest)
    zf = REF.rms_norm(h, lp["mlp_norm"], whole["rms_norm_eps"])
    own, routed = experts // shares, 0
    for share in range(shares):
        held = list(range(share * own, (share + 1) * own))
        cut = {**lp, **{leaf: lp[leaf][share * own:(share + 1) * own]
                        for leaf in ("e_gate", "e_up", "e_down")}}
        routed = routed + REF.held_experts(
            dict(whole, n_routed_experts=own, experts_held=held), cut, zf,
            mm_highest)
    got = h + REF.shared_expert(lp, zf, mm_highest) + routed
    assert _rel(got, want) < 1e-5
    # and the program's layer, holding everything, is that whole layer
    raw = jax.tree.map(lambda a: a[0], params["layers"]["mla_moe"])
    with jax.default_matmul_precision("highest"):
        out, sizes = transformer._layer(
            _cfg(whole), "mla_moe", raw, x[None], jnp.arange(40)[None],
            transformer._attention_dense, None)
    assert _rel(out[0], want) < 2e-5
    assert int(sizes.sum()) == 40 * whole["num_experts_per_tok"]


def test_no_rank_and_a_gate_are_still_the_ling_tree():
    """A rank of ``None`` keeps the single ``mla_q`` and the gate stays what
    a configuration gets unless it says otherwise: the Ling family's MLA
    leaves, in their order (a seed's draws follow it)."""
    ling = harness.family(["perfbench"], "ling3")
    from test_ling3_model import MODEL as LING

    cfg = ling.model_config(LING)
    assert cfg.q_lora_rank is None and cfg.mla_gate and cfg.mtp_depth == 0
    leaves = transformer._kind_leaves(cfg, "mla_moe")
    assert list(leaves)[:7] == ["mla_norm", "mla_q", "mla_kv_a",
                                "mla_kv_norm", "mla_kv_b", "mla_gate",
                                "mla_out"]
    assert list(ling.weights.kind_leaves(LING, "mla_moe"))[:7] \
        == list(leaves)[:7]
    ours = transformer._kind_leaves(_cfg(), "mla_moe")
    assert list(ours)[:8] == ["mla_norm", "mla_q_a", "mla_q_norm", "mla_q_b",
                              "mla_kv_a", "mla_kv_norm", "mla_kv_b",
                              "mla_out"]
    assert "mtp" not in jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))


def test_the_load_counts_the_modules_router_too():
    cfg = _cfg()
    params = FAMILY.make_params(MODEL, SEED)
    tokens, _ = _batch()
    load = jax.jit(lambda p, t: transformer.moe_load(cfg, p, t))(params,
                                                                 tokens)
    assert set(load) == {"mla_moe", "mtp"}
    assert load["mla_moe"].shape == (2, 4) and load["mtp"].shape == (1, 4)
    assert int(load["mtp"].sum()) > 0


def test_the_cached_bodies_and_the_manual_step_refuse_the_module():
    plain = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                              n_heads=4, n_kv_heads=2, d_ff=64)
    cfg = dataclasses.replace(plain, mtp_depth=1)
    params = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    assert set(params["mtp"]["block"]) == set(params["layers"])
    cache = jax.eval_shape(functools.partial(init_kv_cache, plain, 8, 4))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    with pytest.raises(NotImplementedError, match="multi-token"):
        jax.eval_shape(lambda: decode_step(cfg, params, cache, ints(2),
                                           ints(2), ints(2, 4)))
    with pytest.raises(NotImplementedError, match="multi-token"):
        transformer.make_spmd_train_step(cfg, None, params)
    with pytest.raises(ValueError, match="mtp_depth"):
        dataclasses.replace(plain, mtp_depth=2)
    # the training body runs it for a flat stack too, and the specs follow
    specs = transformer.param_specs(cfg)
    assert jax.tree.structure(specs, is_leaf=lambda s: isinstance(
        s, jax.sharding.PartitionSpec)) == jax.tree.structure(params)
    rows = jnp.zeros((2, 9), jnp.int32)
    loss = jax.eval_shape(lambda p: loss_fn(cfg, p, rows[:, :-1],
                                            rows[:, 1:]), params)
    assert loss.shape == ()
