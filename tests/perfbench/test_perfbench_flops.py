"""flops.py against counts made by hand for the configuration."""

import pytest

from perfbench import flops, harness, weights

def _model(name):
    return harness.load_json(harness.find(
        ["perfbench"], "configs", name + ".json"))["model"]


TRAIN = _model("mistral7b-train")


def test_layer_and_head_parameters_by_hand():
    # attention: 4096x4096 (q) + 2 x 4096x1024 (k, v) + 4096x4096 (o)
    attn = 4096 * 4096 * 2 + 4096 * 1024 * 2
    mlp = 3 * 4096 * 14336
    assert attn == 41_943_040 and mlp == 176_160_768
    assert flops.layer_params(TRAIN) == attn + mlp == 218_103_808
    assert flops.head_params(TRAIN) == 4096 * 32768 == 134_217_728


@pytest.mark.parametrize("model,layers,total", [
    (TRAIN, 2, 2 * 218_103_808 + 2 * 134_217_728 + 2 * 8192 + 4096),
    (dict(TRAIN, num_hidden_layers=32), 32,            # as published
     32 * 218_103_808 + 2 * 134_217_728 + 32 * 8192 + 4096),
])
def test_total_parameters_match_the_tree_the_benchmark_builds(
        model, layers, total):
    assert model["num_hidden_layers"] == layers
    assert flops.total_params(model) == total
    shapes = weights.leaf_shapes(model)
    built = 0
    for shape, _ in shapes.values():
        n = 1
        for d in shape:
            n *= d
        built += n
    assert built == total
    assert flops.matmul_params(model) == layers * 218_103_808 + 134_217_728


def test_train_flops_per_token_by_hand():
    # 6 x (2 x 218.1M + 134.2M) + 12 x 2 layers x 4096 seq x 4096 width
    n = 2 * 218_103_808 + 134_217_728
    assert n == 570_425_344
    want = 6 * n + 12 * 2 * 4096 * 4096
    assert flops.train_flops_per_token(TRAIN, 4096) == want == 3_825_205_248
    # the head's share of the matmul FLOPs at this depth, and as published
    assert round(134_217_728 / n, 3) == 0.235
    assert round(134_217_728 / (32 * 218_103_808 + 134_217_728), 3) == 0.019


def test_flash_cost_is_compute_bound_at_4k_and_counts_the_causal_half():
    cost = flops.flash_train_cost(TRAIN, 1, 4096)
    one_matmul = 2 * 32 * 128 * (4096 * 4097 // 2)
    assert cost["flops"] == 2 * 7 * one_matmul
    assert cost["bytes"] == 2 * 15 * 32 * 4096 * 128 * 2
    least, bound = flops.roofline_seconds(cost, harness.peak("TPU v5 lite"))
    assert bound == "compute"
    assert least == pytest.approx(cost["flops"] / 197e12)
