"""The JoyAI family's cell: its configuration file against the catalog's
shape and the issue's arithmetic, and its toy cell through the driver on
the CPU: the contract's last line, every one of the cell's per-layer
readers, ``seg.mtp`` in the join, the balancing pass over five routers, and
what ``correct`` refuses.

The toy is the tests' own (``data/configs/tiny-joyai.json`` under
``data/manifest-joyai.json``, which names the same per-layer metrics as the
benchmark's cell), never a benchmark configuration.
"""

import contextlib
import dataclasses
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, run, segments, trace_reduce
from perfbench import step as train_step
from perfbench.reference import train_check
from ray_tpu.models import transformer
from ray_tpu.util import profiling
from test_perfbench_line import RECORDED, _recorded_planes

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "data", "manifest-joyai.json")
CELL = "tiny-joyai.tiny-steps"
REAL = "joyai-flash-train.seq4k"
CONFIG = os.path.join(harness.ROOT, "perfbench", "configs",
                      "joyai-flash-train.json")
SEED = 2 ** 31 + 145
NEW = ("mtp", "attn_proj", "attn_core", "mlp", "moe_route", "moe_shared",
       "moe_experts", "embed", "head_loss", "update")
NEW_READERS = tuple(f"train.seg.{s}_ms.joyai" for s in NEW) + (
    "train.seg.unattributed_share.joyai", "kernel.flash_fwd_ms.joyai",
    "kernel.flash_dq_ms.joyai", "kernel.flash_dkv_ms.joyai",
    "kernel.moe_gmm_roofline.joyai", "kernel.moe_gmm_rows_multiplied_x.joyai",
    "train.moe_load_max_over_mean.joyai", "train.moe_rows_worked_share.joyai")


# ------------------------------------------------- the configuration file

def test_the_source_keys_stand_at_the_top_level_as_in_model():
    """The driver's comparison with the catalog reads the source's keys at
    the top level of the file; the harness reads ``model``. One value each."""
    body = harness.load_json(CONFIG)
    assert body["model"] and body["model_why"]
    assert {k: body[k] for k in body["model"]} == body["model"]
    assert body["num_nextn_predict_layers"] == 1
    assert body["q_lora_rank"] == 1536 and body["rope_interleave"] is True


def test_only_counts_are_reduced_and_every_width_is_the_sources():
    body = harness.load_json(CONFIG)
    assert body["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert body["published"] == {"num_hidden_layers": 40,
                                 "n_routed_experts": 256,
                                 "vocab_size": 129280}
    assert {k: body["model"][k] for k in body["reduced"]} == {
        "num_hidden_layers": 5, "n_routed_experts": 16, "vocab_size": 16160}
    source = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    assert {k: v for k, v in body["model"].items()
            if k not in body["reduced"]} == source
    assert body["deployment"]["chips_per_layer"] == 16
    assert all(isinstance(v, str) or v.get("why")
               for v in body["assumed"].values())
    assert {"router_experts", "experts_held", "layer_types", "mtp_input",
            "mtp_join_order", "mtp_loss_weight", "rope_interleave",
            "expert_bias", "optimizer", "weights"} <= set(body["assumed"])
    model = harness.run_model(body)
    assert model["router_experts"] == 256 and model["n_routed_experts"] == 16
    assert model["experts_held"] == list(range(16))
    assert model["mtp_loss_weight"] == 0.3
    assert model["rope_interleave"] is True          # the source's, kept
    assert model["rotary_columns"] == "half_rotation"
    assert body["step"] == harness.load_cell("ling3-flash-train.seq4k")[
        "config"]["step"]


def test_the_tree_counts_680m_term_by_term():
    """The issue's arithmetic, from the family's counts and from the tree
    the family makes: a latent-attention operator, an expert layer, the
    dense layer, the module, a table slice, the whole."""
    loaded = harness.load_cell(REAL)
    family = harness.family(loaded["paths"], "joyai")
    model, counts = harness.run_model(loaded["config"]), family.counts
    assert counts.operator_params(model) == 26_349_568
    assert counts.layer_params(model, "mla_moe") == 107_092_224
    assert counts.layer_params(model, "mla_dense") == 70_391_808
    assert counts.module_params(model) == 115_486_976
    assert model["vocab_size"] * model["hidden_size"] == 33_095_680
    assert family.total_params(model) == 680_441_088 == (
        70_391_808 + 4 * 107_092_224 + 115_486_976 + 2 * 33_095_680 + 2_048)
    # whole, one expert layer's routed experts do not fit beside a second
    whole = dict(model, n_routed_experts=256)
    assert counts.layer_params(whole, "mla_moe") == 1_239_554_304
    tree = jax.eval_shape(lambda: family.make_params(model, 0))
    size = lambda t: sum(a.size for a in jax.tree.leaves(t))
    assert size(tree) == 680_441_088
    assert size(tree["mtp"]) == 115_486_976
    assert size(tree["layers"]["mla_moe"]) == 4 * 107_092_224
    assert size(tree["layers"]["mla_dense"]) == 70_391_808
    program = jax.eval_shape(lambda: transformer.init_params(
        family.model_config(model), jax.random.PRNGKey(0)))
    assert jax.tree.structure(program) == jax.tree.structure(tree)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape, program, tree)))
    cfg = family.model_config(model)
    assert (cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim, cfg.mla_gate) == (
                32, 1536, 512, 128, 64, 128, False)
    assert (cfg.router_experts, cfg.router_groups, cfg.experts_per_token,
            cfg.shared_d_ff, cfg.mtp_depth, cfg.mtp_weight) == (
                256, 1, 8, 768, 1, 0.3)
    assert transformer.layer_runs(cfg) == (("mla_dense", 0, 1),
                                           ("mla_moe", 0, 4))


def test_the_counts_follow_the_shapes():
    loaded = harness.load_cell(REAL)
    family = harness.family(loaded["paths"], "joyai")
    model, counts = harness.run_model(loaded["config"]), family.counts
    assert counts.expected_pairs(model, 1, 4096) == 2048
    flash = family.flash_train_cost(model, 1, 4096)
    pairs = 4096 * 4097 // 2
    assert flash["flops"] == 6 * 2 * 32 * (4 * 192 + 3 * 128) * pairs
    assert flash["bytes"] == 6 * 32 * 4096 * (8 * 192 + 7 * 128) * 2
    # one layer body of 8 heads is the Ling cell's count
    ling = harness.family(loaded["paths"], "ling3")
    ling_model = harness.run_model(harness.load_cell(
        "ling3-flash-train.seq4k")["config"])
    assert flash["flops"] == 6 * 4 * ling.flash_train_cost(
        ling_model, 1, 4096)["flops"]
    experts = family.experts_train_cost(model, 1, 4096)
    assert experts["flops"] == 5 * 9 * 2 * 2048 * 2048 * 768
    n = counts.token_matmul_params(model)
    operator = 26_349_568 - 2048 - 1536 - 512
    assert counts.operator_matmul_params(model) == operator
    # the head twice, six operators, the join, the dense SwiGLU, and five
    # routers with a shared expert and half of one routed expert a token
    assert n == 2 * 2048 * 16160 + 6 * operator + 2 * 2048 * 2048 \
        + 3 * 2048 * 7168 + 5 * (2048 * 256 + 1.5 * 4_718_592)
    assert family.train_flops_per_token(model, 4096) \
        == 6 * n + 3 * 6 * 2 * 4096 * 32 * 320


# ------------------------------------------------------------ the toy cell

@pytest.fixture(scope="module")
def lines():
    """(the loaded cell, its untraced line, its traced line): the traced
    one lent the recorded chip trace and the v5e's peaks, as
    ``test_perfbench_line.py`` does."""
    loaded = harness.load_cell(CELL, MANIFEST)
    real, real_peak = trace_reduce.load, harness.peak
    real_reduce = trace_reduce.reduce
    trace_reduce.load = _recorded_planes
    trace_reduce.reduce = lambda planes, _window_s: real_reduce(
        planes, RECORDED["span_ns"] / 1e9)
    harness.peak = lambda kind: real_peak("TPU v5 lite")
    try:
        out = [run.run_cell(loaded, SEED, 1.0, trace, time.perf_counter(),
                            allow_cpu=True) for trace in (False, True)]
    finally:
        trace_reduce.load, harness.peak = real, real_peak
        trace_reduce.reduce = real_reduce
    return loaded, out[0], out[1]


def test_the_toy_names_the_cells_own_metrics():
    toy = harness.load_cell(CELL, MANIFEST)
    real = harness.load_cell(REAL)
    assert [m["name"] for m in toy["per_layer"]] \
        == [m["name"] for m in real["per_layer"]]
    assert len(toy["per_layer"]) == 27
    assert set(NEW_READERS) <= {m["name"] for m in real["per_layer"]}
    assert toy["config"]["family"] == real["config"]["family"] == "joyai"
    keys = set(harness.run_model(toy["config"]))
    assert keys <= set(harness.run_model(real["config"]))
    assert real["traffic"] == harness.load_cell("mistral7b-train.seq4k")[
        "traffic"]                       # the mix that was there
    # the new readers are this cell's alone
    manifest = harness.load_json(harness.MANIFEST)
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW_READERS:
            assert metric["workloads"] == [REAL]
            assert metric["moves"] == "train_tokens_per_s"


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_last_line_has_the_contracts_shape(lines, trace):
    loaded, plain, traced = lines
    line = json.loads(json.dumps(traced if trace else plain))
    assert harness.line_faults(line, loaded, trace) == []
    assert list(line)[:5] == list(harness.LINE_KEYS)
    assert list(line)[-1] == "checks" and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["checks"]) == {"grad_norm_gap", "change_norm_gap",
                                   "loss_not_finite"}
    if trace:
        # the recorded trace's instructions are another program's: every
        # segment reader reads 0 there and the unattributed share 100, and
        # a roofline over no time is left out, not infinite
        names = set(line["metrics"])
        assert {"train.step_mfu", "train.step_ms", "device.idle_share.train",
                "train.moe_load_max_over_mean.joyai",
                "train.moe_rows_worked_share.joyai",
                "kernel.moe_gmm_rows_multiplied_x.joyai",
                "train.seg.mtp_ms.joyai",
                "train.seg.unattributed_share.joyai"} <= names
        assert "kernel.moe_gmm_roofline.joyai" not in names
        assert line["metrics"]["train.moe_load_max_over_mean.joyai"][
            "value"] >= 1.0
        assert line["metrics"]["train.seg.unattributed_share.joyai"][
            "value"] > 90.0
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("name", NEW_READERS)
def test_every_new_reader_reads_a_number_on_the_toy_cell(name):
    """Each of the cell's new readers against a join that holds every
    segment and kernel and a family that made a tree: a number, and nothing
    (no raise) where the program has no such span, counter or family."""
    from perfbench import flops

    loaded = harness.load_cell(CELL, MANIFEST)
    family = harness.family(loaded["paths"], "joyai")
    model = harness.run_model(loaded["config"])
    family.make_params(model, SEED)
    names = segments.vocabulary()
    joined = {"segment": dict({s: 0.002 for s in names[0]}, update=0.003,
                              unattributed=0.001),
              "way": {}, "busy_s": 0.1,
              "kernel": {k: 0.0005 for k in names[1]}}
    key = family.join._rule({"cell": loaded}).KEY
    ctx = {"family": family, "model": model, "cell": loaded,
           "step_cfg": loaded["config"]["step"], "flops": flops,
           "peak": harness.peak("TPU v5 lite"), key: joined,
           "segments": joined, "traced_steps": 8}
    read = harness.reader(loaded["paths"], name)
    value = read(ctx)
    assert isinstance(value, float) and np.isfinite(value) and value > 0
    assert read({"family": object(), "model": model, "cell": loaded,
                 "step_cfg": loaded["config"]["step"], key: None,
                 "segments": None}) is None


def test_the_join_gives_the_module_its_segment():
    """``seg.mtp`` is in the vocabulary, and of the toy's compiled step the
    join gives the module's layer, head pass and loss to it, forward and
    backward, beside the stack's own segments; the flash kernels' and the
    grouped kernels' names are on both."""
    assert profiling.SEGMENTS[-1] == "seg.mtp"
    loaded = harness.load_cell(CELL, MANIFEST)
    text = segments.compiled_text(loaded)
    table = segments.attribute(text, *segments.vocabulary())
    ways = {}
    for row in table.values():
        ways.setdefault(row["segment"], set()).add(row["way"])
    for seg in ("seg.mtp", "seg.attn_proj", "seg.attn_core", "seg.mlp",
                "seg.moe_route", "seg.moe_shared", "seg.moe_experts",
                "seg.head_loss"):
        assert {"forward", "backward"} <= ways[seg] | {"both"} \
            or "both" in ways[seg], (seg, ways.get(seg))
    assert "seg.embed" in ways
    assert not {"seg.conv", "seg.kda_core", "seg.mamba_core"} & set(ways)
    assert segments.UPDATE in ways
    # the module's matmuls (its join, six of latent attention, its head
    # pass) lie under seg.mtp although they carry the inner names too
    inner = [path for instrs in segments.parse(text).values()
             for instr in instrs for path in instr.paths
             if "seg.mtp" in path and ("seg.attn_proj" in path
                                       or "seg.head_loss" in path)]
    assert inner
    assert all(segments.classify([(p, True)], segments.vocabulary()[0])[0]
               == "seg.mtp" for p in inner)


def test_the_bias_of_all_five_routers_is_balanced():
    loaded = harness.load_cell(CELL, MANIFEST)
    model = harness.run_model(loaded["config"])
    family = harness.family(loaded["paths"], "joyai")
    weights, ref = family.weights, family.reference
    params = family.make_params(model, SEED)
    rule = model["expert_bias"]
    stack = np.asarray(params["layers"]["mla_moe"]["expert_bias"])
    module = np.asarray(params["mtp"]["block"]["expert_bias"])
    assert stack.shape == (2, 32) and module.shape == (1, 32)
    assert np.abs(stack).max(axis=1).min() > 0 and np.abs(module).max() > 0
    # the module's router again by hand, through the reference's own
    # choice: the loads its balanced bias gives over all the rule's
    # sequences meet the rule's stop, which no bias at all does not
    key, lp = harness.seed_key(SEED), ref.module_layer(model, params)
    e, k = model["router_experts"], model["num_experts_per_tok"]

    def scores_of(index):
        tokens, targets = family.batch_of(key, index, 1, rule["seq_len"],
                                          model["vocab_size"])
        h = ref.last_hidden(model, params, tokens[0])
        x = ref.mtp_input(model, params, h, targets[0], ref.mm_highest)
        x = ref.operator(model, lp, x, ref.mm_highest)
        return ref.router_scores(
            lp, ref.rms_norm(x, lp["mlp_norm"], model["rms_norm_eps"]),
            ref.mm_highest)

    scores = np.concatenate([scores_of(i) for i in range(rule["sequences"])])
    loads = lambda b: np.bincount(np.asarray(
        ref.choose(model, scores + b)).ravel(), minlength=e)
    mean = rule["sequences"] * rule["seq_len"] * k / e
    assert loads(lp["expert_bias"]).max() <= rule["max_over_mean"] * mean
    assert loads(0.0).max() > rule["max_over_mean"] * mean
    # one sequence alone would have stopped the rule elsewhere
    alone, _ran, _worst = weights.balance(model, scores[:rule["seq_len"]],
                                          rule)
    assert np.abs(np.asarray(alone) - module[0]).max() > rule["u"]
    # a second tree of the seed is the first, bias and all
    again = weights.make_params(model, SEED)
    np.testing.assert_array_equal(
        again["mtp"]["block"]["expert_bias"], module)


def test_change_norms_makes_each_leaf_again_in_one_program_a_shape():
    """A tree that has not moved reads nothing on every leaf (to the
    rounding of a product the CPU's compiler fuses into the difference),
    one that has reads how far; leaves of one shape share a program: a
    leaf's index and scale are its arguments."""
    loaded = harness.load_cell(CELL, MANIFEST)
    model = harness.run_model(loaded["config"])
    weights = harness.family(loaded["paths"], "joyai").weights
    params = weights.make_params(model, SEED)
    still = weights.change_norms(model, SEED, params)
    sizes = weights.leaf_norms(params)
    assert set(still) == set(sizes)
    assert all(still[n] <= 1e-6 * sizes[n] for n in sizes)
    moved = weights.change_norms(
        model, SEED, jax.tree.map(lambda a: a + 0.5, params))
    leaves = weights.flat(params)
    for name, value in moved.items():
        leaf = leaves[name.rsplit(".", 1)[0] if name.count(".") == 2
                      else name]
        rows = leaf.shape[0] if "." in name else 1
        np.testing.assert_allclose(value, 0.5 * (leaf.size / rows) ** 0.5,
                                   rtol=1e-5)
    drawn = [n for n in weights.leaf_names(model)
             if weights._recipe(model, n)[2] is not None]
    assert weights._moved_from_draw._cache_size() < len(drawn) - 10


# ---------------------------------------------------- what correct refuses

def _program_steps(family, model, hp, loss):
    step, init = train_step.adamw_step(
        loss,
        lambda key, index: family.batch_of(key, index, hp["batch"],
                                           hp["seq_len"],
                                           model["vocab_size"]), hp)
    key = harness.seed_key(SEED)
    params = family.make_params(model, SEED)
    opt_state = jax.jit(init)(params)
    got = {"loss": []}
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            params, opt_state, loss = step(params, opt_state, key, i)
            got["loss"].append(float(loss))
            if i == 0:
                got["grad"] = {
                    n: v / (1.0 - hp["b1"]) for n, v in family.leaf_norms(
                        family.first_moment(opt_state)).items()}
    got["change"] = family.change_norms(model, SEED, params)
    return got


@pytest.fixture(scope="module")
def control():
    """The toy at the real cell's limits, in float32 (at 128 tokens one
    top-k choice flipped by bfloat16 is a hundredth of the pairs):
    (family, model, hp, the program's configuration, the reference's three
    steps, the limits)."""
    loaded = harness.load_cell(CELL, MANIFEST)
    model = harness.run_model(loaded["config"])
    family = harness.family(loaded["paths"], "joyai")
    hp = loaded["config"]["step"]
    cfg = dataclasses.replace(family.model_config(model), dtype=jnp.float32)
    want = train_check.reference_steps(family, model, hp, SEED, 3,
                                       log=lambda *_: None)
    limits = harness.load_cell(REAL)["config"]["correct"]
    return family, model, hp, cfg, want, limits


def _correct(got, want, limits):
    checks = train_check.compare(got, want, limits, log=lambda *_: None)
    assert set(checks) == set(limits)
    return {name: c["ok"] for name, c in checks.items()}


@contextlib.contextmanager
def _no_norm_of_width(width):
    """While a step is traced, an RMSNorm over ``width`` channels passes its
    input on, times its weight: the query latent's norm left out. The one
    planted fault no configuration and no parameter reaches (a norm's
    weight scales what it has normed); it replaces nothing in the tree."""
    real = transformer.rms_norm

    def rms_norm(x, w, eps=1e-6):
        if x.shape[-1] == width:
            return x * w.astype(x.dtype)
        return real(x, w, eps)

    transformer.rms_norm = rms_norm
    try:
        yield
    finally:
        transformer.rms_norm = real


@pytest.mark.parametrize("fault,expect", [
    ("none", True), ("no_module_loss", False), ("no_query_norm", False),
    ("half_batch", False)])
def test_what_the_cells_limits_pass_and_refuse(control, fault, expect):
    """A sound program reads true at the real cell's limits; one whose
    module's loss counts nothing (lambda = 0 over the same weights: the
    module's leaves get no gradient), one whose query latent is not normed
    and one trained on the first half of each sequence read false. On the
    chip the same were read at the cell's size (PERF.md section 6, PR
    45)."""
    from ray_tpu.models import loss_fn

    family, model, hp, cfg, want, limits = control
    loss = functools.partial(loss_fn, cfg)
    planted = contextlib.nullcontext()
    if fault == "no_module_loss":
        loss = functools.partial(
            loss_fn, dataclasses.replace(cfg, mtp_weight=0.0))
    elif fault == "no_query_norm":
        planted = _no_norm_of_width(cfg.q_lora_rank)
    elif fault == "half_batch":
        half = hp["seq_len"] // 2
        loss = lambda p, tokens, targets: loss_fn(
            cfg, p, tokens[:, :half], targets[:, :half])
    with planted:
        got = _program_steps(family, model, hp, loss)
    ok = _correct(got, want, limits)
    assert all(ok.values()) is expect, ok


def test_the_int8_control_is_not_correct(control):
    family, model, hp, _cfg, want, limits = control
    got = train_check.reference_steps(family, model, hp, SEED, 3, mm="int8",
                                      log=lambda *_: None)
    assert not all(_correct(got, want, limits).values())
