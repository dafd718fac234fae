"""Mamba-2 layers, attention layers without rope and latent expert layers,
each a mixer or a feed-forward alone, in the training body, against the
Nemotron-H family's plain reference (``perfbench/families/nemotron_h/
reference.py``, which imports nothing of the program, computes Mamba-2
position by position and the experts as a loop).

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
from perfbench import step as train_step
from perfbench.reference import numerics
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
FAMILY = harness.family(["perfbench"], "nemotron_h")
REF = FAMILY.reference
# The cell's whole pattern at tiny widths: 6 Mamba heads in 3 groups, 4
# query heads over 2 KV heads, 4 of the router's 32 experts held, 6 a token.
MODEL = {
    "num_hidden_layers": 11, "hybrid_override_pattern": "EMEMEMEMEM*",
    "hidden_size": 32, "max_position_embeddings": 4096,
    "layer_norm_epsilon": 1e-5, "mamba_num_heads": 6, "mamba_head_dim": 8,
    "ssm_state_size": 16, "n_groups": 3, "conv_kernel": 4, "chunk_size": 16,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "head_dim": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_rope": False, "moe_latent_size": 16,
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 40,
    "mlp_hidden_act": "relu2", "num_experts_per_tok": 6,
    "n_routed_experts": 4, "router_experts": 32,
    "experts_held": [1, 6, 9, 17], "routed_scaling_factor": 5,
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "vocab_size": 64, "tie_word_embeddings": False,
    "expert_bias": {"u": 0.001, "max_over_mean": 1.5, "iterations": 50,
                    "seq_len": 64}}
SEED = 2 ** 31 + 41
CONFIG = os.path.join(ROOT, "perfbench", "configs",
                      "nemotron3-super-train.json")
POSITIONS = 72      # four whole chunks of the scan and a padded one


def _cfg(model=MODEL, dtype=jnp.float32):
    return dataclasses.replace(FAMILY.model_config(model), dtype=dtype)


def _batch(model=MODEL, batch=2, seq_len=POSITIONS):
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
    # the whole pattern is two scans: five (expert, Mamba) units, then the
    # attention layer; the reference finds the same units by itself
    units = ((("none_moe", "mamba_none"), (0, 0), 5),
             (("attention_none",), (0,), 1))
    assert transformer.layer_units(_cfg()) == units
    assert tuple(REF.layer_units(MODEL)) == units
    assert len(transformer.layer_runs(_cfg())) == 11
    # the decay's two parameters are drawn as the mechanism's layer does
    mamba = ours["layers"]["mamba_none"]
    a_log = np.asarray(mamba["mamba_a_log"])
    assert (a_log >= 0).all() and (a_log <= np.log(16)).all()
    dt = np.log1p(np.exp(np.asarray(mamba["mamba_dt_bias"])))
    assert (dt > 0.9e-3).all() and (dt < 0.11).all()
    assert np.all(np.asarray(mamba["mamba_d"]) == 1)
    assert np.abs(np.asarray(mamba["mamba_conv_bias"])).max() > 0


def test_float32_loss_and_every_gradient_match_the_reference(
        reference_grads):
    params, want_loss, want = reference_grads
    tokens, targets = _batch()
    with jax.default_matmul_precision("highest"):
        loss, got = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(_cfg(), p, tokens, targets)))(params)
    assert float(loss) == pytest.approx(want_loss, rel=2e-6)
    gaps = jax.tree.map(_rel, got, want)
    # float32 rounding in another order of operations (a chunked scan
    # against a recurrence, sorted rows against a loop), nothing more
    assert max(jax.tree.leaves(gaps)) < 1e-4, gaps
    moe = got["layers"]["none_moe"]
    # the bias selects and does not weigh: no gradient reaches it
    assert not np.any(np.asarray(moe["expert_bias"]))
    # every token passes the shared expert and both latent projections
    for leaf in ("s_down", "latent_down", "latent_up"):
        assert np.all(np.abs(np.asarray(moe[leaf])).max(axis=(1, 2)) > 0)


@pytest.mark.parametrize("per_token,within", [(32, 0.12), (6, 0.5)],
                         ids=["every-expert", "top-6"])
def test_bfloat16_loss_and_gradients_stay_near_the_reference(per_token,
                                                             within):
    """The stated tolerance: the loss within 0.5 %, and the whole gradient
    within 12 % of the reference's norm where every token takes every
    expert (8 % read: what 8 bits of mantissa leave over 11 layers at
    widths of 32), within 50 % under the top-6 of 32 (37 % read): at 144
    tokens nothing averages out, and a top-k choice that bfloat16 flips
    moves a token's whole path through five expert layers whose gates are
    scaled by 5. A missing term reads 100 % or more. At the cell's widths
    the chip run's ``correct`` holds the same path to hundredths."""
    model = dict(MODEL, num_experts_per_tok=per_token)
    params = FAMILY.make_params(model, SEED)
    tokens, targets = _batch()
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: FAMILY.reference_loss(model, p, tokens, targets,
                                        mm_highest)))(params)
    loss, got = jax.jit(jax.value_and_grad(lambda p: loss_fn(
        _cfg(model, dtype=jnp.bfloat16), p, tokens, targets)))(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=5e-3)
    norm = lambda t: jnp.sqrt(sum(jnp.sum(jnp.square(a))
                                  for a in jax.tree.leaves(t)))
    diff = jax.tree.map(lambda a, b: a - b, got, want)
    assert float(norm(diff) / norm(want)) < within


def test_one_adamw_step_is_the_references(reference_grads):
    """The window's own step (``perfbench/step.py``) on the program's
    float32 loss against AdamW written out on the reference's gradient:
    the same parameters after one update at a rate of 3e-4."""
    params, _loss, grads = reference_grads
    hp = {"batch": 2, "seq_len": POSITIONS, "learning_rate": 3e-4,
          "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4}
    zeros = jax.tree.map(jnp.zeros_like, params)
    want, _mu, _nu = numerics.adamw_step(params, zeros, zeros, grads, 1.0,
                                         hp)
    step, init = train_step.adamw_step(
        functools.partial(loss_fn, _cfg()),
        lambda key, index: FAMILY.batch_of(key, index, 2, POSITIONS, 64), hp)
    with jax.default_matmul_precision("highest"):
        got, _state, _ = step(params, init(params), harness.seed_key(SEED),
                              0)
    moved = jax.tree.map(lambda a, b, p: _rel(a - p, b - p) if jnp.any(b - p)
                         else 0.0, got, want, params)
    # Adam's first update is the gradient's sign times the rate: the same
    # to float32's rounding of ``p + update`` (1e-4 of the move) but where a
    # single gradient is as small as Adam's eps (1e-8: one entry of ``wo``
    # and of ``mamba_in`` here), whose update follows the gradient's own
    # last bits (1 % of the largest move read)
    assert max(jax.tree.leaves(moved)) < 3e-2, moved
    assert sorted(jax.tree.leaves(moved))[-3] < 1e-3, moved


@pytest.mark.parametrize("kind,at", [("mamba_none", 2), ("attention_none", 0),
                                     ("none_moe", 3)])
def test_each_layer_alone_matches_the_reference(kind, at):
    cfg = _cfg()
    params = FAMILY.make_params(MODEL, SEED)
    lp = jax.tree.map(lambda a: a[at], params["layers"][kind])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, POSITIONS, 32),
                          jnp.float32)
    positions = jnp.arange(POSITIONS)[None]
    run = jax.jit(lambda lp, x: transformer._layer(
        cfg, kind, lp, x, positions, transformer._attention_dense, None)[0])
    with jax.default_matmul_precision("highest"):
        got = run(lp, x)[0]
        moved = run(lp, x.at[0, 40].add(1.0))[0]
    want = jax.jit(lambda lp, x: REF.layer(MODEL, kind, lp, x,
                                           mm_highest))(lp, x[0])
    assert _rel(got, want) < 2e-5
    # changing token 40 leaves every output before it as it was; a mixer
    # over the sequence moves every one from it on, an expert layer that
    # token's alone
    assert bool((moved[:40] == got[:40]).all())
    after = (moved[40:] != got[40:]).any(axis=-1)
    assert bool(after[0])
    assert bool(after.all()) is (kind != "none_moe")
    if kind == "attention_none":
        # no rotary embedding: with it the layer is another function
        roped = jax.jit(lambda lp, x: transformer._layer(
            dataclasses.replace(cfg, rope=True), kind, lp, x, positions,
            transformer._attention_dense, None)[0])(lp, x)[0]
        assert _rel(roped, want) > 1e-2


def _mamba_share(model, lp, group):
    """Group ``group``'s share of one Mamba layer's leaves: its heads'
    columns of ``W_in`` (z, x, dt), its own B and C, the matching taps, and
    its channels' rows of ``W_out``."""
    h, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n = model["n_groups"], model["ssm_state_size"]
    inner, own = h * p, h // g * p
    heads = np.arange(group * (h // g), (group + 1) * (h // g))
    chan = np.arange(group * own, (group + 1) * own)
    state = np.arange(group * n, (group + 1) * n)
    mixed = np.concatenate([chan, inner + state, inner + g * n + state])
    cols = np.concatenate([chan, inner + mixed, 2 * inner + 2 * g * n + heads])
    return {**lp, "mamba_in": lp["mamba_in"][:, cols],
            "mamba_taps": lp["mamba_taps"][mixed],
            "mamba_conv_bias": lp["mamba_conv_bias"][mixed],
            "mamba_a_log": lp["mamba_a_log"][heads],
            "mamba_dt_bias": lp["mamba_dt_bias"][heads],
            "mamba_d": lp["mamba_d"][heads],
            "mamba_gate_norm": lp["mamba_gate_norm"][chan],
            "mamba_out": lp["mamba_out"][chan]}


def _attention_share(model, lp, part, parts):
    """Share ``part`` of an attention layer's heads: its query heads'
    columns of ``W_q`` and rows of ``W_o``, its KV heads' of ``W_k``,
    ``W_v``."""
    hd = model["head_dim"]
    q = model["num_attention_heads"] // parts * hd
    kv = model["num_key_value_heads"] // parts * hd
    return {**lp, "wq": lp["wq"][:, part * q:(part + 1) * q],
            "wk": lp["wk"][:, part * kv:(part + 1) * kv],
            "wv": lp["wv"][:, part * kv:(part + 1) * kv],
            "wo": lp["wo"][part * q:(part + 1) * q]}


def test_the_shares_add_up_to_the_uncut_layers():
    """The deployment in small: an expert layer of 64 routed experts over
    64 chips, a Mamba layer of 8 groups of 2 heads over 8, an attention
    layer of 4 query and 2 KV heads over 2. The 64 shares' routed parts,
    each through the latent's up projection, with the router, the latent's
    down projection, the shared expert and the residual, which every chip
    computes alike, counted once, sum to the whole expert layer; the 8
    shares of the Mamba layer (by group, each through its own rows of
    ``W_out``) and the 2 of the attention layer (through ``W_o``) sum to
    the whole mixers' outputs."""
    whole = dict(MODEL, router_experts=64, n_routed_experts=64,
                 experts_held=list(range(64)), mamba_num_heads=16,
                 n_groups=8, num_hidden_layers=3,
                 hybrid_override_pattern="EM*")
    params = FAMILY.make_params(whole, SEED)
    eps = whole["layer_norm_epsilon"]
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 32), jnp.float32)
    first = lambda kind: jax.tree.map(lambda a: a[0], params["layers"][kind])

    lp = first("none_moe")
    u = REF.rms_norm(x, lp["mlp_norm"], eps)
    routed = sum(
        REF.held_experts(
            dict(whole, n_routed_experts=1, experts_held=[e]),
            {**lp, "e_up": lp["e_up"][e:e + 1],
             "e_down": lp["e_down"][e:e + 1]}, u, mm_highest)
        @ lp["latent_up"] for e in range(64))
    want = REF.layer(whole, "none_moe", lp, x, mm_highest)
    with jax.default_matmul_precision("highest"):
        assert _rel(x + routed + REF.shared_expert(lp, u, mm_highest),
                    want) < 1e-5
        # and the program's layer, holding everything, is that whole layer
        out, sizes = transformer._layer(
            _cfg(whole), "none_moe", lp, x[None], jnp.arange(40)[None],
            transformer._attention_dense, None)
    assert _rel(out[0], want) < 2e-5
    assert int(sizes.sum()) == 40 * whole["num_experts_per_tok"]

    lp = first("mamba_none")
    u = REF.rms_norm(x, lp["mamba_norm"], eps)
    got = sum(REF.mamba(dict(whole, mamba_num_heads=2, n_groups=1),
                        _mamba_share(whole, lp, g), u, mm_highest)
              for g in range(8))
    assert _rel(got, REF.mamba(whole, lp, u, mm_highest)) < 1e-5

    lp = first("attention_none")
    u = REF.rms_norm(x, lp["attn_norm"], eps)
    half = dict(whole, num_attention_heads=2, num_key_value_heads=1)
    got = sum(REF.attention(half, _attention_share(whole, lp, part, 2), u,
                            mm_highest) for part in range(2))
    assert _rel(got, REF.attention(whole, lp, u, mm_highest)) < 1e-5


def test_the_cached_bodies_and_the_configuration_refuse():
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
    two = functools.partial(dataclasses.replace, plain)
    for options, match in [
            (dict(layer_types=("mamba", "attention")), "mamba_heads"),
            (dict(layer_types=("mamba", "attention"), mamba_heads=6,
                  mamba_groups=4), "mamba_heads"),
            (dict(layer_types=("none", "attention"),
                  layer_ffns=("none", "dense")), "layer kinds"),
            (dict(layer_ffns=("moe", "dense")), "router_experts"),
            (dict(layer_ffns=("dense", "swiglu")), "layer_ffns"),
            (dict(ffn_act="gelu"), "ffn_act"),
            (dict(ffn_act="relu2"), "ffn_act")]:
        with pytest.raises(ValueError, match=match):
            two(**options)


def test_the_source_keys_stand_at_the_top_level_as_in_model():
    """The driver's comparison with the catalog reads the source's keys at
    the top level of the file; the harness reads ``model``. One value each."""
    body = harness.load_json(CONFIG)
    assert body["model"] and body["model_why"]
    assert {k: body[k] for k in body["model"]} == body["model"]
    assert body["n_routed_experts"] == 8 and body["sliding_window"] is None
    assert body["hybrid_override_pattern"] == "EMEMEMEMEM*"


def test_the_configuration_keeps_every_width_and_counts_713m():
    body = harness.load_json(CONFIG)
    assert body["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "mamba_num_heads", "n_groups", "num_attention_heads",
        "num_key_value_heads", "vocab_size", "num_nextn_predict_layers",
        "mtp_hybrid_override_pattern"]
    widths = {"hidden_size": 4096, "mamba_head_dim": 64,
              "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128,
              "head_dim": 128, "moe_latent_size": 1024,
              "moe_intermediate_size": 2688, "intermediate_size": 2688,
              "moe_shared_expert_intermediate_size": 5376, "expand": 2,
              "num_experts_per_tok": 22, "routed_scaling_factor": 5,
              "n_group": 1, "topk_group": 1, "layer_norm_epsilon": 1e-5,
              "mlp_hidden_act": "relu2", "n_shared_experts": 1}
    assert {k: body["model"][k] for k in widths} == widths
    published = body["published"]
    assert {k: published[k] for k in body["reduced"] if k not in (
        "hybrid_override_pattern", "mtp_hybrid_override_pattern")} == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "mamba_num_heads": 128, "n_groups": 8, "num_attention_heads": 32,
        "num_key_value_heads": 2, "vocab_size": 131072,
        "num_nextn_predict_layers": 1}
    source = published["hybrid_override_pattern"]
    assert (len(source), source.count("M"), source.count("E"),
            source.count("*")) == (88, 40, 40, 8)
    # source layers 26-36: the first whole period
    assert source[26:37] == body["model"]["hybrid_override_pattern"]
    assert source.index("EMEMEMEMEM*") == 26
    assert published["mtp_hybrid_override_pattern"] == "*E"
    assert body["deployment"]["chips_per_layer"] == 64
    assert all(isinstance(v, str) or v.get("why")
               for v in body["assumed"].values())
    model = harness.run_model(body)
    assert model["router_experts"] == 512 and model["n_routed_experts"] == 8
    assert model["experts_held"] == list(range(8))
    assert model["attention_rope"] is False
    # the issue's arithmetic, term by term: an expert layer, a Mamba layer,
    # the attention layer, two table slices and the final norm
    counts = FAMILY.counts
    assert [counts.layer_params(model, k) for k in counts.kinds(model)] == [
        98_570_752, 13_708_592] * 5 + [17_829_888]
    full = dict(model, mamba_num_heads=128, n_groups=8,
                num_attention_heads=32, num_key_value_heads=2,
                n_routed_experts=512)
    assert counts.layer_params(full, "mamba_none") == 109_640_064
    assert counts.layer_params(full, "attention_none") == 35_655_680
    assert counts.layer_params(full, "none_moe") == 2_873_102_848
    tree = jax.eval_shape(lambda: FAMILY.make_params(model, 0))
    held = sum(a.size for a in jax.tree.leaves(tree))
    assert held == FAMILY.total_params(model) == 713_448_432
    assert held - 5 * 98_570_752 - 5 * 13_708_592 - 17_829_888 \
        == 2 * 16384 * 4096 + 4096
    cfg = FAMILY.model_config(model)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state,
            cfg.mamba_groups, cfg.mamba_chunk, cfg.conv_kernel) \
        == (16, 64, 128, 1, 128, 4)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rope) \
        == (16, 1, 128, False)
    assert (cfg.router_experts, cfg.experts_per_token, cfg.moe_d_ff,
            cfg.moe_latent, cfg.shared_d_ff, cfg.ffn_act,
            cfg.routed_scale) == (512, 22, 2688, 1024, 5376, "relu2", 5.0)
    assert transformer.layer_units(cfg) == (
        (("none_moe", "mamba_none"), (0, 0), 5),
        (("attention_none",), (0,), 1))
    assert counts.expected_pairs(model, 1, 4096) == 1408


def test_the_counts_follow_the_shapes():
    model = harness.run_model(harness.load_json(CONFIG))
    counts = FAMILY.counts
    # a chunk of 128 of one head of 64 with a state of 128, 16 heads a group
    assert counts.ssd_chunk_flops(model) == 262_144 + 2_097_152 + 4_194_304
    ssd = FAMILY.ssd_train_cost(model, 1, 4096)
    assert ssd["flops"] == 3 * 5 * 16 * 32 * 6_553_600
    assert ssd["bytes"] == 5 * 4096 * 16 * (3 * (128 + 4 + 32) + 2 * 128)
    flash = FAMILY.flash_train_cost(model, 1, 4096)
    dense = harness.family(["perfbench"], "dense").flash_train_cost(
        {"num_attention_heads": 16, "head_dim": 128,
         "num_hidden_layers": 1}, 1, 4096)
    assert flash == dense
    experts = FAMILY.experts_train_cost(model, 1, 4096)
    assert experts["flops"] == 5 * 6 * 2 * 1408 * 1024 * 2688
    assert experts["bytes"] == 5 * 6 * 2 * (
        1408 * 1024 + 1408 * 2688 + 8 * 1024 * 2688)
    per_token = FAMILY.train_flops_per_token(model, 4096)
    n = counts.token_matmul_params(model)
    assert per_token == 6 * n + 3 * (4 * 4096 * 16 * 128
                                     + 5 * 16 * 6_553_600 / 128)
    # the routed experts at their expected share: 22 * 8 / 512 of one
    assert n == pytest.approx(
        4096 * 16384 + 5 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
                            + 0.34375 * 2 * 1024 * 2688)
        + 5 * (4096 * 2320 + 1024 * 4096)
        + 2 * 4096 * 128 * 17)
