"""Parameters, operations and bytes of JoyAI-LLM-Flash's language model
(DeepSeek-V3's layers: latent attention with a query latent in every layer,
a leading dense SwiGLU, then routed experts beside a shared one, and a
multi-token-prediction module) as one chip of a share holds and runs it,
counted from a configuration's shapes: what the readers of such a cell
divide by.

The yardstick's arithmetic: nothing here imports the program. ``model`` is
the configuration as it is run (``harness.run_model``): the file's
``model`` with ``router_experts`` (the router's published width) and
``experts_held`` beside ``n_routed_experts`` (the count held here). A
multiply-add counts as two operations. A layer's kind is the program's name
for it: ``mla_dense`` (the ``first_k_dense_replace`` leading layers) or
``mla_moe``; the module's layer is one ``mla_moe`` more.
"""

from __future__ import annotations

MODULE_KIND = "mla_moe"


def kinds(model: dict) -> list:
    """The kind of each layer of the stack, in published order."""
    return ["mla_dense" if i < model["first_k_dense_replace"] else "mla_moe"
            for i in range(model["num_hidden_layers"])]


def bodies(model: dict) -> list:
    """The kind of every layer body a step runs: the stack's, then one for
    each multi-token-prediction module."""
    return kinds(model) + [MODULE_KIND] * model["num_nextn_predict_layers"]


def qk_dim(model: dict) -> int:
    return model["qk_nope_head_dim"] + model["qk_rope_head_dim"]


def expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def shared_params(model: dict) -> int:
    return model["n_shared_experts"] * expert_params(model)


def operator_matmul_params(model: dict) -> int:
    """The weights of latent attention that a token is multiplied with:
    ``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``, ``W_o``."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    rank_q, rank, rot = (model["q_lora_rank"], model["kv_lora_rank"],
                         model["qk_rope_head_dim"])
    return (d * rank_q + rank_q * h * qk_dim(model) + d * (rank + rot)
            + rank * h * (model["qk_nope_head_dim"] + model["v_head_dim"])
            + h * model["v_head_dim"] * d)


def operator_params(model: dict) -> int:
    """Every parameter of the operator: its pre-norm and the two latents'
    norms among them."""
    return (operator_matmul_params(model) + model["hidden_size"]
            + model["q_lora_rank"] + model["kv_lora_rank"])


def layer_params(model: dict, kind: str) -> int:
    d = model["hidden_size"]
    n = operator_params(model) + d                          # the ffn's norm
    if kind.endswith("dense"):
        return n + 3 * d * model["intermediate_size"]
    router = model["router_experts"]
    return (n + d * router + router                         # and its bias
            + model["n_routed_experts"] * expert_params(model)
            + shared_params(model))


def module_params(model: dict) -> int:
    """One multi-token-prediction module: its layer, the two norms of its
    inputs, the joint projection and its output norm."""
    d = model["hidden_size"]
    return layer_params(model, MODULE_KIND) + 3 * d + 2 * d * d


def total_params(model: dict) -> int:
    """Every parameter held: the table's slice, the head's, the layers,
    the final norm, the module."""
    d = model["hidden_size"]
    table = model["vocab_size"] * d
    return (table * (1 if model["tie_word_embeddings"] else 2) + d
            + sum(layer_params(model, k) for k in kinds(model))
            + model["num_nextn_predict_layers"] * module_params(model))


def ffn_matmul_params(model: dict, kind: str) -> float:
    """A layer's feed-forward as a token meets it: the dense SwiGLU, or the
    router, the shared expert, and the routed experts at their expected
    share, ``num_experts_per_tok x held / router width`` of one expert a
    token (0.5 at 8 of 256 with 16 held)."""
    d = model["hidden_size"]
    if kind.endswith("dense"):
        return 3 * d * model["intermediate_size"]
    share = (model["num_experts_per_tok"] * model["n_routed_experts"]
             / model["router_experts"])
    return (d * model["router_experts"] + shared_params(model)
            + share * expert_params(model))


def token_matmul_params(model: dict) -> float:
    """N of the 6*N rule: the weights a token is multiplied with on this
    chip. Every layer body (the module's among them) its operator and its
    feed-forward, the module's joint projection, and the head once for each
    loss. The table's lookups and the norms are no matmuls."""
    d, modules = model["hidden_size"], model["num_nextn_predict_layers"]
    return ((1 + modules) * d * model["vocab_size"] + modules * 2 * d * d
            + sum(operator_matmul_params(model) + ffn_matmul_params(model, k)
                  for k in bodies(model)))


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """6*N a token, plus every layer body's scores and values over the full
    (not causal-halved) sequence, by the dense family's convention, ``2 S H
    (qk + v)`` forward and twice that backward; recomputed operations not
    counted. The module is counted over every position (its layer runs them
    all; the last is left out of its loss alone)."""
    attn = 2 * seq_len * model["num_attention_heads"] * (
        qk_dim(model) + model["v_head_dim"])
    return (6 * token_matmul_params(model)
            + 3 * len(bodies(model)) * attn)


def flash_train_cost(model: dict, batch: int, seq_len: int,
                     itemsize: int = 2) -> dict:
    """Least work of the three flash kernels of one training step
    (forward, dq, dk/dv), causal, over every layer body, the dense family's
    count at two widths as the Ling family has it: of the 7 matmuls over
    the causal half, 4 contract or produce the query-key width and 3 the
    values'; of the 15 passes over a ``[H, S, .]`` array, 8 are as wide as
    q and k and 7 as v. The keys count at every head's width: the training
    path hands each head its own."""
    h, qk, dv = (model["num_attention_heads"], qk_dim(model),
                 model["v_head_dim"])
    n = len(bodies(model)) * batch
    pairs = seq_len * (seq_len + 1) // 2
    return {"flops": n * 2 * h * (4 * qk + 3 * dv) * pairs,
            "bytes": n * h * seq_len * (8 * qk + 7 * dv) * itemsize}


def expert_layers(model: dict) -> int:
    return sum(k.endswith("moe") for k in bodies(model))


def expected_pairs(model: dict, batch: int, seq_len: int) -> float:
    """(token, expert) pairs a step routes to the experts held here, in
    one expert layer, under even routing."""
    return (batch * seq_len * model["num_experts_per_tok"]
            * model["n_routed_experts"] / model["router_experts"])


def experts_train_cost(model: dict, batch: int, seq_len: int,
                       itemsize: int = 2) -> dict:
    """Least work of the held experts' three grouped products of one
    training step, forward and backward, over the expert layers (the
    module's among them), for the expected pairs, counted as the LFM2 and
    Ling families count it. Operations: a product of P rows is 2*P*D*F
    forward and twice that backward. Bytes: each of the nine products reads
    its two operands and writes its result once: the P x D rows, the P x F
    rows and the held experts' D x F weights."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    p = expected_pairs(model, batch, seq_len)
    return {"flops": expert_layers(model) * 9 * 2 * p * d * f,
            "bytes": expert_layers(model) * 9 * itemsize * (
                p * d + p * f + model["n_routed_experts"] * d * f)}
