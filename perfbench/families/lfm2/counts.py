"""Parameters, operations and bytes of the LFM2 mixture-of-experts decoder
as one chip of a share holds and runs it, counted from a configuration's
shapes: what the readers of an LFM2 cell divide by.

The yardstick's arithmetic: nothing here imports the program. ``model`` is
the configuration as it is run (``harness.run_model``): the file's
``model`` with ``router_experts`` (the router's published width) and
``experts_held`` beside ``num_experts`` (the count held here). A
multiply-add counts as two operations. A layer's kind is
``<operator>_<feed-forward>``: ``conv`` or ``attention``, ``dense`` (the
``num_dense_layers`` leading layers) or ``moe``.
"""

from __future__ import annotations

OPERATORS = {"conv": "conv", "full_attention": "attention"}


def kinds(model: dict) -> list:
    """The kind of each layer, in published order."""
    return [OPERATORS[op] + ("_dense" if i < model["num_dense_layers"]
                             else "_moe")
            for i, op in enumerate(model["layer_types"])]


def head_dim(model: dict) -> int:
    return model["hidden_size"] // model["num_attention_heads"]


def expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def operator_matmul_params(model: dict, kind: str) -> int:
    d, hd = model["hidden_size"], head_dim(model)
    if kind.startswith("conv"):
        return d * 3 * d + d * d                       # W_in, W_out
    q, kv = model["num_attention_heads"], model["num_key_value_heads"]
    return d * hd * (2 * q + 2 * kv)


def layer_params(model: dict, kind: str) -> int:
    """Every parameter one layer of ``kind`` holds here: the operator with
    its norm (a conv layer's taps, an attention layer's q and k norms),
    the feed-forward's norm, and the dense MLP or the router, its bias and
    the experts held."""
    d = model["hidden_size"]
    n = operator_matmul_params(model, kind) + 2 * d
    n += (d * model["conv_L_cache"] if kind.startswith("conv")
          else 2 * head_dim(model))
    if kind.endswith("dense"):
        return n + 3 * d * model["intermediate_size"]
    router = model["router_experts"]
    return (n + d * router + (router if model["use_expert_bias"] else 0)
            + model["num_experts"] * expert_params(model))


def total_params(model: dict) -> int:
    """Every parameter held: the table (the head too, where tied), the
    layers, the final norm."""
    d = model["hidden_size"]
    table = model["vocab_size"] * d
    return (table * (1 if model["tie_word_embeddings"] else 2) + d
            + sum(layer_params(model, k) for k in kinds(model)))


def token_matmul_params(model: dict) -> float:
    """N of the 6*N rule: the weights a token is multiplied with on this
    chip. An expert layer: the router, and the experts at their expected
    share, ``num_experts_per_tok x held / router width`` of one expert a
    token (0.5 at 4 of 64 with 8 held): the count does not follow a
    batch's routing, so no reading can pass what the chip was given. The
    table's lookup and the conv taps are no matmuls and are left out."""
    d = model["hidden_size"]
    share = (model["num_experts_per_tok"] * model["num_experts"]
             / model["router_experts"])
    n = d * model["vocab_size"]                        # the head
    for kind in kinds(model):
        n += operator_matmul_params(model, kind)
        n += (3 * d * model["intermediate_size"] if kind.endswith("dense")
              else d * model["router_experts"] + share * expert_params(model))
    return n


def attention_layers(model: dict) -> int:
    return sum(k.startswith("attention") for k in kinds(model))


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """6*N + 12*L*S*D a token, by the dense family's convention (full, not
    causal-halved attention; recomputed operations do not count), L the
    attention layers alone: a conv layer's sequence mixing is three taps."""
    d_attn = model["num_attention_heads"] * head_dim(model)
    return (6 * token_matmul_params(model)
            + 12 * attention_layers(model) * seq_len * d_attn)


def flash_train_cost(model: dict, batch: int, seq_len: int,
                     itemsize: int = 2) -> dict:
    """Least work of the three flash kernels of one training step (forward,
    dq, dk/dv), causal, over the attention layers: the dense family's
    count (7 matmuls over the causal half; 15 passes over a [H, S, Dh]
    array, K/V at the query-head width the training path repeats them to)
    at this model's heads."""
    h, hd = model["num_attention_heads"], head_dim(model)
    n = attention_layers(model) * batch
    pairs = seq_len * (seq_len + 1) // 2
    return {"flops": n * (2 + 5) * 2 * h * hd * pairs,
            "bytes": n * (4 + 5 + 6) * h * seq_len * hd * itemsize}


def expected_pairs(model: dict, batch: int, seq_len: int) -> float:
    """(token, expert) pairs a step routes to the experts held here, in
    one expert layer, under even routing."""
    return (batch * seq_len * model["num_experts_per_tok"]
            * model["num_experts"] / model["router_experts"])


def experts_train_cost(model: dict, batch: int, seq_len: int,
                       itemsize: int = 2) -> dict:
    """Least work of the held experts' three grouped products of one
    training step, forward and backward, over the expert layers, for the
    expected pairs: the same work whatever implements it. Operations: a
    product of P rows is 2*P*D*F forward and twice that backward (its
    input's gradient and its weight's). Bytes: each of the nine products
    reads its two operands and writes its result once: the P x D rows, the
    P x F rows and the held experts' D x F weights, nine times each."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    p = expected_pairs(model, batch, seq_len)
    layers = sum(k.endswith("moe") for k in kinds(model))
    return {"flops": layers * 9 * 2 * p * d * f,
            "bytes": layers * 9 * itemsize * (
                p * d + p * f + model["num_experts"] * d * f)}
