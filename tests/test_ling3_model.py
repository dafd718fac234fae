"""Kimi Delta Attention and latent-attention layers, group-limited routing
and a shared expert in the training body, against the Ling family's plain
reference (``perfbench/families/ling3/reference.py``, which imports nothing
of the program and computes KDA position by position).

Seeded random weights at tiny widths on the CPU. The program in float32
(its matmuls at ``highest``) has to agree with the reference tightly; in
bfloat16 within what 8 bits of mantissa leave. One test ties the cell's cut
to the model: over all the chips that share a layer, the parts add up to
the uncut layer.
"""

import dataclasses
import functools
import os

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
    TransformerConfig,
)
from ray_tpu.models import transformer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = harness.family(["perfbench"], "ling3")
REF = FAMILY.reference
# One dense layer and four of a period, every kind the cell holds; 4 of the
# router's 32 experts held, in two of its four groups.
MODEL = {
    "num_hidden_layers": 5, "hidden_size": 32, "intermediate_size": 48,
    "first_k_dense_replace": 1, "max_position_embeddings": 4096,
    "moe_intermediate_size": 16, "num_experts_per_tok": 4,
    "num_attention_heads": 2, "num_key_value_heads": 2, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_experts": 4, "rope_theta": 6000000, "rms_norm_eps": 1e-6,
    "head_dim": 16, "vocab_size": 64, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "n_group": 4, "topk_group": 2,
    "score_function": "sigmoid", "moe_shared_expert_intermediate_size": 16,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5,
    "norm_topk_prob": True, "router_experts": 32,
    "experts_held": [1, 6, 9, 17],
    "layer_types": ["kda", "kda", "kda", "mla", "kda"],
    "tie_word_embeddings": False, "kda_a_log": [1, 16],
    "kda_dt_bias": [0.001, 0.1], "expert_bias_scale": 0.02}
SEED = 2 ** 31 + 39
CONFIG = os.path.join(ROOT, "perfbench", "configs", "ling3-flash-train.json")


def _cfg(model=MODEL, dtype=jnp.float32):
    return dataclasses.replace(FAMILY.model_config(model), dtype=dtype)


def _batch(model=MODEL, batch=2, seq_len=72):
    """72 positions: a whole chunk of the scan and a padded one."""
    return FAMILY.batch_of(harness.seed_key(SEED), 0, batch, seq_len,
                           model["vocab_size"])


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


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
        ("kda_dense", 0, 1), ("kda_moe", 0, 2), ("mla_moe", 0, 1),
        ("kda_moe", 2, 1))
    # the decay's two parameters are drawn as their mechanism's paper says
    a_log = np.asarray(ours["layers"]["kda_moe"]["kda_a_log"])
    assert (a_log >= 0).all() and (a_log <= np.log(16)).all()
    dt = np.log1p(np.exp(np.asarray(ours["layers"]["kda_moe"]["kda_dt_bias"])))
    assert (dt > 0.9e-3).all() and (dt < 0.11).all()


def test_float32_loss_and_every_gradient_match_the_reference(
        reference_grads):
    params, want_loss, want = reference_grads
    tokens, targets = _batch()
    with jax.default_matmul_precision("highest"):
        loss, got = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(_cfg(), p, tokens, targets)))(params)
    assert float(loss) == pytest.approx(want_loss, rel=2e-6)
    gaps = jax.tree.map(_rel, got, want)
    # float32 rounding in another order of operations (a chunked solve
    # against a recurrence), nothing more
    assert max(jax.tree.leaves(gaps)) < 1e-4, gaps
    # the bias selects and does not weigh: no gradient reaches it
    for kind in ("kda_moe", "mla_moe"):
        assert not np.any(np.asarray(got["layers"][kind]["expert_bias"]))
        # and the shared expert takes one from every token
        assert np.all(np.abs(np.asarray(got["layers"][kind]["s_down"]))
                      .max(axis=(1, 2)) > 0)


def test_bfloat16_loss_and_gradients_stay_near_the_reference(
        reference_grads):
    """The stated tolerance, as the LFM2 pattern's: the loss within 0.5 %,
    the whole gradient within 35 % of the reference's norm. At 72 tokens and
    widths of 32 nothing averages out and one top-k choice that flips moves
    a held expert's whole leaf; a missing term reads 100 % or more. At the
    cell's widths the chip run's ``correct`` holds the same path to
    thousandths."""
    params, want_loss, want = reference_grads
    tokens, targets = _batch()
    loss, got = jax.jit(jax.value_and_grad(lambda p: loss_fn(
        _cfg(dtype=jnp.bfloat16), p, tokens, targets)))(params)
    assert float(loss) == pytest.approx(want_loss, rel=5e-3)
    norm = lambda t: jnp.sqrt(sum(jnp.sum(jnp.square(a))
                                  for a in jax.tree.leaves(t)))
    diff = jax.tree.map(lambda a, b: a - b, got, want)
    assert float(norm(diff) / norm(want)) < 0.35


@pytest.mark.parametrize("kind,at", [("kda_moe", 1), ("mla_moe", 0)])
def test_each_operator_matches_the_reference_and_is_causal(kind, at):
    cfg = _cfg()
    params = FAMILY.make_params(MODEL, SEED)
    lp = jax.tree.map(lambda a: a[at], params["layers"][kind])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 72, 32), jnp.float32)
    positions = jnp.arange(72)[None]

    def operator(lp, x):
        if kind.startswith("kda"):
            return transformer._kda_residual(cfg, lp, x)
        q, k_nope, k_rope, v, gate = transformer._project_mla(
            cfg, lp, x, positions)
        o = transformer._attention_dense(
            q, transformer._mla_keys(k_nope, k_rope), v)
        return transformer._mla_out(cfg, lp, x, o, gate)

    run = jax.jit(operator)
    with jax.default_matmul_precision("highest"):
        got = run(lp, x)[0]
        moved = run(lp, x.at[0, 40].add(1.0))[0]
    want = jax.jit(lambda lp, x: REF.operator(MODEL, kind, lp, x,
                                              mm_highest))(lp, x[0])
    assert _rel(got, want) < 2e-5
    # changing token 40 leaves every output before it as it was, and moves
    # every one from it on: both operators carry the whole past
    assert bool((moved[:40] == got[:40]).all())
    assert not bool((moved[40:] == got[40:]).all(axis=-1).any())


def _uncut(model, heads, experts):
    """``model`` with all of its heads and experts on one chip."""
    return dict(model, num_attention_heads=heads, num_key_value_heads=heads,
                num_experts=experts, experts_held=list(range(experts)))


def _head_slice(leaf, a, part, parts, heads):
    """Head share ``part`` of ``parts`` of one layer's leaf ``a`` cut for
    ``heads`` heads; what every share holds alike comes back whole."""
    own = heads // parts
    lo, hi = part * own, (part + 1) * own
    per_head = {"kda_q": -1, "kda_k": -1, "kda_v": -1, "kda_a": -1,
                "kda_gate": -1, "kda_beta": -1, "kda_q_taps": 0,
                "kda_k_taps": 0, "kda_v_taps": 0, "kda_dt_bias": 0,
                "kda_a_log": 0, "kda_out": 0, "mla_q": -1, "mla_kv_b": -1,
                "mla_gate": -1, "mla_out": 0}
    if leaf not in per_head:
        return a
    axis = per_head[leaf] % a.ndim
    width = a.shape[axis] // heads
    return jax.lax.slice_in_dim(a, lo * width, hi * width, axis=axis)


@pytest.mark.parametrize("kind", ["kda_moe", "mla_moe"])
def test_the_shares_add_up_to_the_uncut_layer(kind):
    """The deployment in small: a layer of 4 heads and 16 routed experts,
    its heads over 2 chips and its experts over 4. The heads' shares of the
    operator (each through its own rows of ``W_o``) sum to the whole
    operator's output, and the experts' shares of the feed-forward sum to
    the whole routed result, the shared expert and the residual, which every
    chip computes alike, counted once."""
    heads, experts = 4, 16
    whole = dict(_uncut(MODEL, heads, experts), router_experts=experts,
                 n_group=4, topk_group=2, num_hidden_layers=2,
                 layer_types=["kda", kind.split("_")[0]])
    params = FAMILY.make_params(whole, SEED)
    lp = jax.tree.map(lambda a: a[0], params["layers"][kind])
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 32), jnp.float32)
    eps = whole["rms_norm_eps"]
    name = "kda_norm" if kind.startswith("kda") else "mla_norm"
    z = REF.rms_norm(x, lp[name], eps)
    operator = REF.kda if kind.startswith("kda") else REF.mla
    want_op = operator(whole, lp, z, mm_highest)
    parts = 2
    got_op = sum(
        operator(dict(whole, num_attention_heads=heads // parts),
                 {leaf: _head_slice(leaf, a, part, parts, heads)
                  for leaf, a in lp.items()}, z, mm_highest)
        for part in range(parts))
    assert _rel(got_op, want_op) < 1e-5

    h = x + want_op
    zf = REF.rms_norm(h, lp["mlp_norm"], eps)
    want = REF.layer(whole, kind, lp, x, mm_highest)
    shares, own = 4, experts // 4
    routed = 0
    for share in range(shares):
        held = list(range(share * own, (share + 1) * own))
        cut = {**lp, **{leaf: lp[leaf][share * own:(share + 1) * own]
                        for leaf in ("e_gate", "e_up", "e_down")}}
        routed = routed + REF.held_experts(
            dict(whole, num_experts=own, experts_held=held), cut, zf,
            mm_highest)
    got = h + REF.shared_expert(lp, zf, mm_highest) + routed
    assert _rel(got, want) < 1e-5
    # and the program's layer, holding everything, is that whole layer
    cfg = _cfg(whole)
    with jax.default_matmul_precision("highest"):
        out, sizes = transformer._layer(
            cfg, kind, lp, x[None], jnp.arange(40)[None],
            transformer._attention_dense, None)
    assert _rel(out[0], want) < 2e-5
    assert int(sizes.sum()) == 40 * whole["num_experts_per_tok"]


def test_the_cached_bodies_refuse_the_new_kinds():
    cfg = _cfg()
    params = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    plain = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                              n_heads=4, n_kv_heads=2, d_ff=64)
    cache = jax.eval_shape(functools.partial(init_kv_cache, plain, 8, 4))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    with pytest.raises(NotImplementedError, match="layer pattern"):
        jax.eval_shape(lambda: decode_step(cfg, params, cache, ints(2),
                                           ints(2), ints(2, 4)))
    with pytest.raises(ValueError, match="layer_types"):
        TransformerConfig(n_layers=2, layer_types=("kda", "retention"))
    with pytest.raises(ValueError, match="router_groups"):
        dataclasses.replace(cfg, router_groups=5)
    with pytest.raises(ValueError, match="router_groups"):
        dataclasses.replace(cfg, router_groups_kept=5)


def test_the_source_keys_stand_at_the_top_level_as_in_model():
    """The driver's comparison with the catalog reads the source's keys at
    the top level of the file; the harness reads ``model``. One value each."""
    body = harness.load_json(CONFIG)
    assert body["model"] and body["model_why"]
    assert {k: body[k] for k in body["model"]} == body["model"]
    assert body["num_experts"] == 8 and body["q_lora_rank"] is None
    assert body["expert_swiglu_limit_list"] == [0] * 7


def test_the_configuration_keeps_every_width_and_counts_578m():
    body = harness.load_json(CONFIG)
    assert body["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "num_attention_heads", "num_key_value_heads", "vocab_size",
        "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"]
    widths = {"hidden_size": 2560, "head_dim": 128, "qk_nope_head_dim": 128,
              "qk_rope_head_dim": 64, "v_head_dim": 128, "kv_lora_rank": 512,
              "intermediate_size": 6144, "moe_intermediate_size": 768,
              "moe_shared_expert_intermediate_size": 768, "n_group": 8,
              "topk_group": 4, "num_experts_per_tok": 8,
              "short_conv_kernel_size": 4, "kda_lower_bound": -5,
              "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
              "rope_theta": 6000000, "layer_group_size": 6}
    assert {k: body["model"][k] for k in widths} == widths
    assert body["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "num_attention_heads": 32,
        "num_key_value_heads": 32, "vocab_size": 157184,
        "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
        "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2}
    assert body["deployment"]["chips_per_layer"] == 64
    assert all(isinstance(v, str) or v.get("why")
               for v in body["assumed"].values())
    model = harness.run_model(body)
    assert model["router_experts"] == 512 and model["num_experts"] == 8
    assert model["experts_held"] == list(range(8))
    # source layers 1-7 by the layer_group_size rule
    counts = FAMILY.counts
    assert model["layer_types"] == counts.source_layer_types(
        42, model["layer_group_size"])[1:8]
    assert counts.source_layer_types(42, 6).count("mla") == 7
    # the issue's arithmetic, term by term: the dense layer, a KDA expert
    # layer, the MLA expert layer, two table slices and the final norm
    assert [counts.layer_params(model, k) for k in counts.kinds(model)] == [
        62_953_608] + [70_163_080] * 3 + [63_498_240] + [70_163_080] * 2
    full = dict(model, num_attention_heads=32)
    assert counts.operator_params(full, "kda_moe") == 63_052_448
    assert counts.operator_params(full, "mla_moe") == 31_968_256
    tree = jax.eval_shape(lambda: FAMILY.make_params(model, 0))
    held = sum(a.size for a in jax.tree.leaves(tree))
    assert held == FAMILY.total_params(model) == 577_867_568
    cfg = FAMILY.model_config(model)
    assert (cfg.head_dim, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
            cfg.kv_lora_rank) == (128, 128, 64, 128, 512)
    assert (cfg.router_experts, cfg.router_groups, cfg.router_groups_kept,
            cfg.experts_per_token, cfg.shared_d_ff) == (512, 8, 4, 8, 768)
    assert transformer.layer_runs(cfg) == (
        ("kda_dense", 0, 1), ("kda_moe", 0, 3), ("mla_moe", 0, 1),
        ("kda_moe", 3, 2))
    assert counts.expected_pairs(model, 1, 4096) == 512


def test_the_counts_follow_the_shapes():
    model = harness.run_model(harness.load_json(CONFIG))
    counts = FAMILY.counts
    # a chunk of 64 of one head at widths of 128, forward
    assert counts.kda_chunk_flops(128, 128) == 10_485_760
    kda = FAMILY.kda_train_cost(model, 1, 4096)
    assert kda["flops"] == 3 * 6 * 8 * 64 * 10_485_760
    assert kda["bytes"] == 6 * 8 * 4096 * (3 * 1284 + 2 * 256)
    flash = FAMILY.flash_train_cost(model, 1, 4096)
    pairs = 4096 * 4097 // 2
    assert flash["flops"] == 2 * 8 * (4 * 192 + 3 * 128) * pairs
    assert flash["bytes"] == 8 * 4096 * (8 * 192 + 7 * 128) * 2
    # at one width for both it is the dense family's count
    square = dict(model, qk_rope_head_dim=0)
    dense = harness.family(["perfbench"], "dense").flash_train_cost(
        {"num_attention_heads": 8, "head_dim": 128,
         "num_hidden_layers": 1}, 1, 4096)
    assert FAMILY.flash_train_cost(square, 1, 4096) == dense
    per_token = FAMILY.train_flops_per_token(model, 4096)
    n = counts.token_matmul_params(model)
    assert per_token == 6 * n + 3 * (2 * 4096 * 8 * 320
                                     + 6 * 8 * 10_485_760 / 64)
    # the experts at their expected share: an eighth of one expert a token
    assert n == pytest.approx(
        2560 * 19648 + 6 * (2560 * 512 + 1.125 * 5_898_240)
        + 3 * 2560 * 6144 + 6 * counts.operator_matmul_params(model, "kda")
        + counts.operator_matmul_params(model, "mla"))
