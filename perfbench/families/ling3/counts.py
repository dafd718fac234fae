"""Parameters, operations and bytes of the Ling 3.0 language model as one
chip of a share holds and runs it, counted from a configuration's shapes:
what the readers of a Ling cell divide by.

The yardstick's arithmetic: nothing here imports the program. ``model`` is
the configuration as it is run (``harness.run_model``): the file's
``model`` with ``router_experts`` (the router's published width),
``experts_held`` and ``layer_types`` beside ``num_experts`` and
``num_attention_heads`` (the counts held here). A multiply-add counts as
two operations. A layer's kind is ``<operator>_<feed-forward>``: ``kda``
or ``mla``, ``dense`` (the ``first_k_dense_replace`` leading layers) or
``moe``.
"""

from __future__ import annotations

KDA_CHUNK = 64      # the chunk of the algorithm ``kda_train_cost`` counts


def kinds(model: dict) -> list:
    """The kind of each layer, in published order."""
    return [op + ("_dense" if i < model["first_k_dense_replace"] else "_moe")
            for i, op in enumerate(model["layer_types"])]


def source_layer_types(n_layers: int, group: int) -> list:
    """The operators of the source's layers ``0 .. n_layers-1`` by its
    ``layer_group_size`` rule: layer ``i`` is latent attention where
    ``(i + 1) % group == 0`` and KDA otherwise."""
    return ["mla" if (i + 1) % group == 0 else "kda" for i in range(n_layers)]


def qk_dim(model: dict) -> int:
    return model["qk_nope_head_dim"] + model["qk_rope_head_dim"]


def expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def shared_params(model: dict) -> int:
    return (3 * model["hidden_size"]
            * model["moe_shared_expert_intermediate_size"])


def operator_matmul_params(model: dict, kind: str) -> int:
    """The weights of a layer's sequence operator that a token is
    multiplied with."""
    d, h, hd = (model["hidden_size"], model["num_attention_heads"],
                model["head_dim"])
    if kind.startswith("kda"):
        # W_q, W_k, W_v, W_a, W_g, W_o and the [D, H] beta
        return 6 * d * h * hd + d * h
    rank, rot = model["kv_lora_rank"], model["qk_rope_head_dim"]
    return (d * h * qk_dim(model) + d * (rank + rot)
            + rank * h * (model["qk_nope_head_dim"] + model["v_head_dim"])
            + d * h + h * model["v_head_dim"] * d)


def operator_params(model: dict, kind: str) -> int:
    """Every parameter of the operator, its norms among them."""
    d, h, hd = (model["hidden_size"], model["num_attention_heads"],
                model["head_dim"])
    n = operator_matmul_params(model, kind) + d            # the layer norm
    if kind.startswith("kda"):
        # three sets of taps, dt_bias, A_log, the output norm
        return (n + 3 * h * hd * model["short_conv_kernel_size"] + h * hd
                + h + hd)
    return n + model["kv_lora_rank"]                        # the latent's norm


def layer_params(model: dict, kind: str) -> int:
    d = model["hidden_size"]
    n = operator_params(model, kind) + d                    # the ffn's norm
    if kind.endswith("dense"):
        return n + 3 * d * model["intermediate_size"]
    router = model["router_experts"]
    return (n + d * router
            + (router if model["moe_router_enable_expert_bias"] else 0)
            + model["num_experts"] * expert_params(model)
            + shared_params(model))


def total_params(model: dict) -> int:
    """Every parameter held: the table's slice, the head's, the layers,
    the final norm."""
    d = model["hidden_size"]
    table = model["vocab_size"] * d
    return (table * (1 if model["tie_word_embeddings"] else 2) + d
            + sum(layer_params(model, k) for k in kinds(model)))


def token_matmul_params(model: dict) -> float:
    """N of the 6*N rule: the weights a token is multiplied with on this
    chip. An expert layer: the router, the shared expert, and the routed
    experts at their expected share, ``num_experts_per_tok x held / router
    width`` of one expert a token (0.125 at 8 of 512 with 8 held). The
    table's lookup, the taps and the norms are no matmuls."""
    d = model["hidden_size"]
    share = (model["num_experts_per_tok"] * model["num_experts"]
             / model["router_experts"])
    n = d * model["vocab_size"]                             # the head
    for kind in kinds(model):
        n += operator_matmul_params(model, kind)
        n += (3 * d * model["intermediate_size"] if kind.endswith("dense")
              else d * model["router_experts"] + shared_params(model)
              + share * expert_params(model))
    return n


def layers_of(model: dict, operator: str) -> int:
    return sum(k.startswith(operator) for k in kinds(model))


def kda_chunk_flops(k: int, v: int, c: int = KDA_CHUNK) -> int:
    """Operations of one chunk of one head, forward, by the chunked
    algorithm (``kda_train_cost``)."""
    return (2 * 2 * c * c * k            # the k-k and q-k scores
            + c * c * (k + v)            # the unit-triangular solve
            + 3 * 2 * c * k * v          # W S, (Q exp G) S, K^T U
            + 2 * c * c * v)             # Aqk U


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """6*N a token, plus the sequence operators' own products, forward
    and twice that backward, recomputed operations not counted: an MLA
    layer's scores and values over the full (not causal-halved) sequence,
    by the dense family's convention, ``2 S H (qk + v)`` forward; a KDA
    layer's core at its chunk form, ``kda_chunk_flops`` over the chunk's
    positions a head."""
    h, hd = model["num_attention_heads"], model["head_dim"]
    mla = 2 * seq_len * h * (qk_dim(model) + model["v_head_dim"])
    kda = h * kda_chunk_flops(hd, hd) / KDA_CHUNK
    return (6 * token_matmul_params(model)
            + 3 * (layers_of(model, "mla") * mla
                   + layers_of(model, "kda") * kda))


def flash_train_cost(model: dict, batch: int, seq_len: int,
                     itemsize: int = 2) -> dict:
    """Least work of the three flash kernels of one training step
    (forward, dq, dk/dv), causal, over the MLA layers, the dense family's
    count at two widths: of the 7 matmuls over the causal half, 4 contract
    or produce the query-key width (scores forward, scores again, dq, dk)
    and 3 the values' (p v, dp, dv); of the 15 passes over a ``[H, S, .]``
    array, 8 are as wide as q and k (q, k forward; q, k, dq; k, q, dk) and
    7 as v (v, o; v, do; v, do, dv). The keys count at every head's width:
    the training path hands each head its own."""
    h, qk, dv = model["num_attention_heads"], qk_dim(model), model["v_head_dim"]
    n = layers_of(model, "mla") * batch
    pairs = seq_len * (seq_len + 1) // 2
    return {"flops": n * 2 * h * (4 * qk + 3 * dv) * pairs,
            "bytes": n * h * seq_len * (8 * qk + 7 * dv) * itemsize}


def kda_train_cost(model: dict, batch: int, seq_len: int,
                   itemsize: int = 2) -> dict:
    """Least work of the KDA layers' recurrence in one training step by the
    chunk-64 algorithm, whatever implements it.

    The algorithm, a head and a chunk of C = 64 positions, keys of width K,
    values of width V, state ``S [K, V]``: two ``[C, C]`` score matrices
    over K (keys with keys for the WY solve, queries with keys for the
    output), 2 * 2 C C K; the unit-lower-triangular solve of ``[C, C]``
    against ``K + V`` columns, C C (K + V); three products with the state
    (``W S``, ``(Q exp G) S``, ``K^T U``), 3 * 2 C K V; the scores times
    ``U``, 2 C C V. That is ``kda_chunk_flops`` forward; the backward pass
    is the transpose of each product, twice the forward's operations, so 3
    times in all, for ``tokens / C`` chunks of every head of every KDA
    layer. The decays' exponentials and the elementwise scalings are not
    counted.

    Bytes: the state and the chunk's matrices can stay on the chip, so the
    least traffic is each operand once a pass. Forward reads q, k, v
    (``itemsize`` each), the log-decay (float32) and beta (float32), and
    writes o; backward reads those five and do, and writes dq, dk, dv, dg
    (float32) and dbeta."""
    h, hd = model["num_attention_heads"], model["head_dim"]
    k = v = hd
    tokens = layers_of(model, "kda") * batch * seq_len
    wide = (2 * k + v) * itemsize + 4 * k + 4      # q, k, v, g, beta
    return {"flops": 3 * tokens * h * kda_chunk_flops(k, v) / KDA_CHUNK,
            "bytes": tokens * h * (wide + v * itemsize             # forward
                                   + wide + v * itemsize + wide)}  # backward


def expected_pairs(model: dict, batch: int, seq_len: int) -> float:
    """(token, expert) pairs a step routes to the experts held here, in
    one expert layer, under even routing."""
    return (batch * seq_len * model["num_experts_per_tok"]
            * model["num_experts"] / model["router_experts"])


def experts_train_cost(model: dict, batch: int, seq_len: int,
                       itemsize: int = 2) -> dict:
    """Least work of the held experts' three grouped products of one
    training step, forward and backward, over the expert layers, for the
    expected pairs: the same work whatever implements it, counted as the
    LFM2 family counts it. Operations: a product of P rows is 2*P*D*F
    forward and twice that backward. Bytes: each of the nine products reads
    its two operands and writes its result once: the P x D rows, the P x F
    rows and the held experts' D x F weights. At 64 tokens an expert the
    weights are nine tenths of the bytes, and the bytes bound the cost."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    p = expected_pairs(model, batch, seq_len)
    layers = sum(k.endswith("moe") for k in kinds(model))
    return {"flops": layers * 9 * 2 * p * d * f,
            "bytes": layers * 9 * itemsize * (
                p * d + p * f + model["num_experts"] * d * f)}
