"""The Ouro family's cell: its configuration file against the catalog's
shape and the cut's arithmetic, and its toy cell through the driver on the
CPU: the contract's last line, every one of the cell's per-layer readers,
``seg.exit`` and the recomputation in the join, and what ``correct``
refuses.

The toy is the tests' own (``data/configs/tiny-ouro.json`` under
``data/manifest-ouro.json``, which names the same per-layer metrics as the
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
MANIFEST = os.path.join(HERE, "data", "manifest-ouro.json")
CELL = "tiny-ouro.tiny-steps"
REAL = "ouro-2.6b-train.seq4k"
CONFIG = os.path.join(harness.ROOT, "perfbench", "configs",
                      "ouro-2.6b-train.json")
SEED = 2 ** 31 + 148
NEW = ("exit", "attn_proj", "attn_core", "mlp", "embed", "head_loss",
       "update")
NEW_READERS = tuple(f"train.seg.{s}_ms.ouro" for s in NEW) + (
    "train.seg.unattributed_share.ouro", "train.recompute_ms.ouro",
    "train.exit_weight_last.ouro", "kernel.flash_fwd_ms.ouro",
    "kernel.flash_dq_ms.ouro", "kernel.flash_dkv_ms.ouro")
# The catalog's config of the source, whole but for its depth.
SOURCE = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4,
    "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152}


# ------------------------------------------------- the configuration file

def test_the_source_keys_stand_at_the_top_level_as_in_model():
    """A comparison with the model catalog reads the source's keys at the
    top level of the file; the harness reads ``model``. One value each."""
    body = harness.load_json(CONFIG)
    assert body["model"] and body["model_why"]
    assert {k: body[k] for k in body["model"]} == body["model"]
    assert body["source"] == ("https://huggingface.co/ByteDance/Ouro-2.6B/"
                              "blob/main/config.json")


def test_only_the_depth_is_reduced_and_every_width_is_the_sources():
    body = harness.load_json(CONFIG)
    assert body["reduced"] == ["num_hidden_layers"]
    assert body["published"] == {"num_hidden_layers": 48}
    model = body["model"]
    # the source's list of 48 layers stands whole; the stage runs the first 8
    assert (model["num_hidden_layers"], model["max_window_layers"]) == (8, 48)
    assert model["layer_types"] == ["full_attention"] * 48
    assert {k: v for k, v in model.items()
            if k not in body["reduced"]} == SOURCE
    assert body["deployment"]["stages"] == 6
    assert body["deployment"]["chips_per_layer"] == 1
    assert all(set(v) == {"run", "published", "why"}
               for v in body["assumed"].values())
    assert set(body["assumed"]) == {
        "sandwich_norm", "loop_input", "exit_gate", "exit_entropy_beta",
        "attention_bias", "weights", "optimizer", "in_flight"}
    ran = harness.run_model(body)
    assert ran["sandwich_norm"] is True and ran["exit_entropy_beta"] == 0.1
    assert ran["total_ut_steps"] == 4
    assert body["step"] == harness.load_cell("mistral7b-train.seq4k")[
        "config"]["step"]
    assert set(body["correct"]) == {"grad_norm_gap", "change_norm_gap"}


def test_the_tree_counts_612m_term_by_term():
    """The cut's arithmetic, from the family's counts and from the tree the
    family makes: a layer, the table and the head, the gate, the whole."""
    loaded = harness.load_cell(REAL)
    family = harness.family(loaded["paths"], "ouro")
    model, counts = harness.run_model(loaded["config"]), family.counts
    assert counts.layer_matmul_params(model) == 16_777_216 + 34_603_008
    assert counts.layer_params(model) == 51_388_416
    assert counts.head_params(model) == 100_663_296
    assert family.total_params(model) == 612_438_017 == (
        8 * 51_388_416 + 2 * 100_663_296 + 2048 + 2049)
    # whole, the 48 layers alone take 39.5 GB at 16 bytes a parameter
    assert 48 * counts.layer_params(model) * 16 > 39.4e9
    tree = jax.eval_shape(lambda: family.make_params(model, 0))
    size = lambda t: sum(a.size for a in jax.tree.leaves(t))
    assert size(tree) == 612_438_017
    assert size(tree["layers"]) == 8 * 51_388_416
    assert size(tree["exit_gate"]) == 2049
    cfg = family.model_config(model)
    program = jax.eval_shape(lambda: transformer.init_params(
        cfg, jax.random.PRNGKey(0)))
    assert jax.tree.structure(program) == jax.tree.structure(tree)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape, program, tree)))
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.total_ut_steps, cfg.sandwich_norm, cfg.exit_entropy_beta) \
        == (16, 16, 128, 5632, 4, True, 0.1)


def test_the_counts_follow_the_shapes():
    loaded = harness.load_cell(REAL)
    family = harness.family(loaded["paths"], "ouro")
    model = harness.run_model(loaded["config"])
    # 6 (T L 51,380,224 + T 100,663,296) + 12 T L S D: 15.5 GFLOP a token
    assert family.train_flops_per_token(model, 4096) == 15_502_147_584
    flash = family.flash_train_cost(model, 1, 4096)
    pairs = 4096 * 4097 // 2
    assert flash["flops"] == 32 * 7 * 2 * 16 * 128 * pairs
    assert flash["bytes"] == 32 * 15 * 16 * 4096 * 128 * 2
    # a layer application is the dense family's layer at these shapes
    dense = harness.family(loaded["paths"], "dense")
    one = dict(model, num_hidden_layers=1)
    assert flash["flops"] == 32 * dense.flash_train_cost(one, 1, 4096)[
        "flops"]


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
    assert len(toy["per_layer"]) == 22
    assert set(NEW_READERS) <= {m["name"] for m in real["per_layer"]}
    assert toy["config"]["family"] == real["config"]["family"] == "ouro"
    assert set(harness.run_model(toy["config"])) \
        <= set(harness.run_model(real["config"]))
    assert real["traffic"] == harness.load_cell("mistral7b-train.seq4k")[
        "traffic"]                       # the mix that was there
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
    assert set(line["checks"]) == {"loss_gap_step3", "grad_norm_gap",
                                   "change_norm_gap", "loss_not_finite"}
    if trace:
        # the recorded trace's instructions are another program's: every
        # segment reader reads 0 there and the unattributed share 100, and
        # a kernel that is not in this step is left out
        names = set(line["metrics"])
        assert {"train.step_mfu", "train.step_ms", "device.idle_share.train",
                "train.exit_weight_last.ouro", "train.seg.exit_ms.ouro",
                "train.recompute_ms.ouro",
                "train.seg.unattributed_share.ouro"} <= names
        assert "kernel.flash_fwd_ms.ouro" not in names
        assert 0 < line["metrics"]["train.exit_weight_last.ouro"][
            "value"] < 100
        assert line["metrics"]["train.seg.unattributed_share.ouro"][
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
    family = harness.family(loaded["paths"], "ouro")
    model = harness.run_model(loaded["config"])
    family.make_params(model, SEED)
    names = segments.vocabulary()
    joined = {"segment": dict({s: 0.002 for s in names[0]}, update=0.003,
                              unattributed=0.001),
              "way": {}, "busy_s": 0.1,
              "kernel": {k: 0.0005 for k in names[1]}}
    ctx = {"family": family, "model": model, "cell": loaded,
           "step_cfg": loaded["config"]["step"], "flops": flops,
           "peak": harness.peak("TPU v5 lite"), "segments": joined,
           "recompute_ms": 0.7, "traced_steps": 8}
    read = harness.reader(loaded["paths"], name)
    value = read(ctx)
    assert isinstance(value, float) and np.isfinite(value) and value > 0
    assert read({"family": object(), "model": model, "cell": loaded,
                 "step_cfg": loaded["config"]["step"],
                 "segments": None}) is None


def test_the_join_gives_the_exit_its_segment_and_finds_the_recomputation():
    """``seg.exit`` is in the vocabulary, and of the toy's compiled step the
    join gives the gates and the weighing to it, forward and backward. The
    checkpoints' recomputation is found by JAX's name for it,
    ``rematted_computation``, under the backward pass: the layer's products
    and the head's, each in its own segment."""
    assert "seg.exit" in profiling.SEGMENTS
    loaded = harness.load_cell(CELL, MANIFEST)
    family = harness.family(loaded["paths"], "ouro")
    text = segments.compiled_text(loaded)
    table = segments.attribute(text, *segments.vocabulary())
    ways = {}
    for row in table.values():
        ways.setdefault(row["segment"], set()).add(row["way"])
    for seg in ("seg.exit", "seg.attn_proj", "seg.attn_core", "seg.mlp",
                "seg.head_loss"):
        assert {"forward", "backward"} <= ways[seg] | {"both"} \
            or "both" in ways[seg], (seg, ways.get(seg))
    assert not {"seg.conv", "seg.mtp", "seg.moe_route"} & set(ways)
    again = family.join.recomputed(text)
    assert again and again <= set(table)
    # (a reduction's own computation carries the name without the path
    # above the checkpoint; the matmuls carry it whole)
    remade = [path for instrs in segments.parse(text).values()
              for instr in instrs for path in instr.paths
              if instr.opcode in segments.MATMUL_OPCODES
              and family.join.REMAT in path]
    assert all(p.startswith("jit(step)/transpose(jvp(") for p in remade)
    by_segment = {segments.classify([(p, True)], segments.vocabulary()[0])[0]
                  for p in remade}
    assert by_segment == {"seg.attn_proj", "seg.attn_core", "seg.mlp",
                          "seg.head_loss"}
    # a trace of those instructions alone reads their time, a step at a time
    events = [(name, 1000 * i, 500) for i, name in enumerate(sorted(again))]
    ctx = {"cell": loaded, "traced_steps": 2,
           "planes": {"/device:TPU:0": {"XLA Ops": events}}}
    np.testing.assert_allclose(family.recompute_ms(ctx),
                               len(again) * 500 / 1e6 / 2)


def test_change_norms_makes_each_leaf_again():
    """A tree that has not moved reads nothing on every leaf, one that has
    reads how far."""
    loaded = harness.load_cell(CELL, MANIFEST)
    model = harness.run_model(loaded["config"])
    weights = harness.family(loaded["paths"], "ouro").weights
    params = weights.make_params(model, SEED)
    still = weights.change_norms(model, SEED, params)
    sizes = weights.leaf_norms(params)
    assert set(still) == set(sizes)
    assert all(still[n] <= 1e-6 * max(sizes[n], 1e-6) for n in sizes)
    moved = weights.change_norms(
        model, SEED, jax.tree.map(lambda a: a + 0.5, params))
    leaves = weights.flat(params)
    for name, value in moved.items():
        base = name.rsplit(".", 1)[0] if name.rsplit(".", 1)[0] in \
            weights.LAYER else name
        leaf = leaves[base]
        rows = leaf.shape[0] if base in weights.LAYER else 1
        np.testing.assert_allclose(value, 0.5 * (leaf.size / rows) ** 0.5,
                                   rtol=1e-5)


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
    """The toy at the real cell's limits, in float32: (family, model, hp,
    the program's configuration, the reference's three steps, the
    limits)."""
    loaded = harness.load_cell(CELL, MANIFEST)
    model = harness.run_model(loaded["config"])
    family = harness.family(loaded["paths"], "ouro")
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
def _uniform_exit():
    """While a step is traced, the exit distribution is uniform over the
    passes whatever the gates say: the gate's weights ignored. The planted
    fault no configuration reaches; it replaces nothing in the tree."""
    real = transformer._exit_log_probs
    transformer._exit_log_probs = lambda logits: jnp.full_like(
        logits, -np.log(logits.shape[0]))
    try:
        yield
    finally:
        transformer._exit_log_probs = real


@pytest.mark.parametrize("fault,expect", [
    ("none", True), ("a_pass_dropped", False), ("no_post_norms", False),
    ("gate_ignored", False)])
def test_what_the_cells_limits_pass_and_refuse(control, fault, expect):
    """A sound program reads true at the real cell's limits; one that runs
    three passes of four, one without the sandwich's post-norms (their
    leaves get no gradient) and one whose exit distribution is uniform
    (the gate's leaves get none) read false."""
    from ray_tpu.models import loss_fn

    family, model, hp, cfg, want, limits = control
    loss = functools.partial(loss_fn, cfg)
    planted = contextlib.nullcontext()
    if fault == "a_pass_dropped":
        loss = functools.partial(
            loss_fn, dataclasses.replace(cfg, total_ut_steps=3))
    elif fault == "no_post_norms":
        loss = functools.partial(
            loss_fn, dataclasses.replace(cfg, sandwich_norm=False))
    elif fault == "gate_ignored":
        planted = _uniform_exit()
    with planted:
        got = _program_steps(family, model, hp, loss)
    ok = _correct(got, want, limits)
    assert all(ok.values()) is expect, ok


def test_the_int8_control_is_not_correct(control):
    family, model, hp, _cfg, want, limits = control
    got = train_check.reference_steps(family, model, hp, SEED, 3, mm="int8",
                                      log=lambda *_: None)
    assert not all(_correct(got, want, limits).values())
