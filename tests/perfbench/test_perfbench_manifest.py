"""BENCHMARK.json against the contract's shape, and against the files it
names: every cell finds its configuration, its mix and each metric's
reader without an edit to a file that is there."""

import json
import os
import re

import pytest

from perfbench import harness

ROOT = harness.ROOT
MANIFEST = harness.load_json(harness.MANIFEST)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    assert all(os.path.isdir(os.path.join(ROOT, p))
               for p in MANIFEST["paths"])
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_is_well_formed(metric):
    end_to_end = metric in MANIFEST["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        moved = {m["name"]: m for m in MANIFEST["end_to_end"]}
        assert metric["moves"] in moved
        for cell in metric.get("workloads", CELLS):
            assert harness.applies(moved[metric["moves"]], cell), (
                f"{cell} does not report {metric['moves']}")
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
        # its reader is a file of its own, found by the metric's name
        assert callable(harness.reader(MANIFEST["paths"], metric["name"]))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_finds_its_files_and_reports_enough(cell):
    loaded = harness.load_cell(cell)
    entry = {w["name"]: w for w in MANIFEST["workloads"]}[cell]
    assert NAME.match(cell) and NAME.match(entry["traffic"])
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    names = [m["name"] for m in loaded["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2 and loaded["per_layer"]
    # the mix says which end-to-end metrics it reports; the manifest agrees
    assert set(loaded["traffic"]["reports"]) | {"setup_s"} == set(names)
    assert os.path.exists(os.path.join(
        ROOT, "perfbench", "drivers", loaded["config"]["driver"] + ".py"))


CONFIG_FILES = sorted(os.listdir(os.path.join(ROOT, "perfbench", "configs")))


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_configuration_file_states_its_cut(name):
    """Every configuration file under perfbench/configs."""
    path = os.path.join("perfbench", "configs", name)
    body = harness.load_json(os.path.join(ROOT, path))
    for config in MANIFEST["configs"]:
        assert NAME.match(config["name"])
        assert config["file"].startswith(tuple(MANIFEST["paths"]))
        assert any(w["config"] == config["name"]
                   for w in MANIFEST["workloads"])
        if config["file"] == path:
            assert body["source"] == config["source"]
            assert body["reduced"] == config["reduced"]
    assert body["source"].startswith("https://huggingface.co/mistralai/")
    for key in body["reduced"]:
        assert key in body["model"] and key in body["published"]
        assert not key.endswith(("_dim", "_rank", "_size"))
    for key in ("assumed", "deployment", "precision", "correct", "driver"):
        assert body.get(key), key
    # Mistral-7B-v0.3's published widths, none cut
    widths = {"vocab_size": 32768, "hidden_size": 4096,
              "intermediate_size": 14336, "num_attention_heads": 32,
              "num_key_value_heads": 8, "head_dim": 128,
              "rope_theta": 1000000.0}
    assert {k: body["model"][k] for k in widths} == widths


def test_only_depth_is_reduced_and_the_eps_that_runs_is_assumed():
    """``model`` keeps the source's values; what the program runs in a
    value's place stands under ``assumed`` with the published one beside
    it, and ``run_model`` hands that to the program's weights and to the
    reference alike."""
    from perfbench.drivers import train

    body = harness.load_json(os.path.join(
        ROOT, "perfbench", "configs", "mistral7b-train.json"))
    assert body["reduced"] == ["num_hidden_layers"]
    assert body["model"]["rms_norm_eps"] == 1e-5
    eps = body["assumed"]["rms_norm_eps"]
    assert eps["published"] == body["model"]["rms_norm_eps"]
    ran = train.run_model(body)
    assert ran["rms_norm_eps"] == eps["run"] == 1e-6
    assert {k: v for k, v in ran.items() if k != "rms_norm_eps"} == {
        k: v for k, v in body["model"].items() if k != "rms_norm_eps"}
    assert train.run_model({"model": {"a": 1}}) == {"a": 1}


def test_the_step_readers_leave_the_profilers_bracket_out():
    """The driver hands the readers the plain steps and their seconds;
    the readers divide the one by the other and nothing else."""
    from perfbench import flops

    body = harness.load_json(os.path.join(
        ROOT, "perfbench", "configs", "mistral7b-train.json"))
    ctx = {"seconds": 7.1, "steps": 50, "tokens": 50 * 4096,
           "model": body["model"], "step_cfg": body["step"], "chips": 1,
           "peak": harness.peak("TPU v5 lite"), "flops": flops}
    paths = MANIFEST["paths"]
    assert harness.reader(paths, "train.step_ms")(ctx) == pytest.approx(142.0)
    want = 100 * 3_825_205_248 * 50 * 4096 / (7.1 * 197e12)
    assert harness.reader(paths, "train.step_mfu")(ctx) == pytest.approx(want)
    assert harness.reader(paths, "train.step_ms")(dict(ctx, steps=0)) is None
    assert harness.reader(paths, "train.step_mfu")(dict(ctx, tokens=0)) is None


@pytest.mark.parametrize("mix", sorted(os.listdir(
    os.path.join(ROOT, "perfbench", "traffic"))))
def test_traffic_file_is_data_the_generator_reads(mix):
    body = harness.load_json(os.path.join(ROOT, "perfbench", "traffic", mix))
    assert body["loop"] == "steps" and body["reports"]
    assert NAME.match(mix[:-len(".json")])


def test_peaks_are_keyed_by_the_real_device_kind():
    assert harness.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert harness.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("v5e", "cpu", "source", "TPU v4"):
        with pytest.raises(SystemExit):
            harness.peak(kind)


def test_no_four_chip_cell_beyond_the_share():
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4)
