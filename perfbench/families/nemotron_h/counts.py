"""Parameters, operations and bytes of a Nemotron-H model (Mamba-2 layers,
attention layers and latent expert layers, each a mixer or a feed-forward
alone) as one chip of a share holds and runs it, counted from a
configuration's shapes: what the readers of such a cell divide by.

The yardstick's arithmetic: nothing here imports the program. ``model`` is
the configuration as it is run (``harness.run_model``): the file's
``model`` with ``router_experts`` (the router's published width) and
``experts_held`` beside ``n_routed_experts``, ``mamba_num_heads``,
``n_groups``, ``num_attention_heads`` and ``num_key_value_heads`` (the
counts held here). A multiply-add counts as two operations. A layer's kind
is the program's name for it: ``none_moe`` (``E`` of
``hybrid_override_pattern``), ``mamba_none`` (``M``) or ``attention_none``
(``*``).
"""

from __future__ import annotations

KINDS = {"E": "none_moe", "M": "mamba_none", "*": "attention_none"}


def kinds(model: dict) -> list:
    """The kind of each layer, in published order."""
    return [KINDS[c] for c in model["hybrid_override_pattern"]]


def layers_of(model: dict, kind: str) -> int:
    return kinds(model).count(kind)


def mamba_inner(model: dict) -> int:
    return model["mamba_num_heads"] * model["mamba_head_dim"]


def mamba_mixed(model: dict) -> int:
    """The channels that go through the taps: x, B and C."""
    return mamba_inner(model) + 2 * model["n_groups"] * model["ssm_state_size"]


def expert_params(model: dict) -> int:
    return 2 * model["moe_latent_size"] * model["moe_intermediate_size"]


def shared_params(model: dict) -> int:
    return (2 * model["hidden_size"]
            * model["moe_shared_expert_intermediate_size"])


def matmul_params(model: dict, kind: str) -> float:
    """The weights of a layer that a token is multiplied with on this chip.
    An expert layer: the router, the two latent projections, the shared
    expert, and the routed experts at their expected share,
    ``num_experts_per_tok x held / router width`` of one expert a token
    (0.34375 at 22 of 512 with 8 held). Taps and norms are no matmuls."""
    d = model["hidden_size"]
    if kind == "mamba_none":
        inner = mamba_inner(model)
        return (d * (inner + mamba_mixed(model) + model["mamba_num_heads"])
                + inner * d)
    if kind == "attention_none":
        hd = model["head_dim"]
        return 2 * d * hd * (model["num_attention_heads"]
                             + model["num_key_value_heads"])
    share = (model["num_experts_per_tok"] * model["n_routed_experts"]
             / model["router_experts"])
    return (d * model["router_experts"] + 2 * d * model["moe_latent_size"]
            + shared_params(model) + share * expert_params(model))


def layer_params(model: dict, kind: str) -> int:
    """Every parameter of one layer held here, its norm among them."""
    d = model["hidden_size"]
    if kind == "mamba_none":
        # W_in, W_out; taps with their bias; A_log, D, dt_bias; the gated
        # norm; the pre-norm
        return (int(matmul_params(model, kind))
                + mamba_mixed(model) * (model["conv_kernel"] + 1)
                + 3 * model["mamba_num_heads"] + mamba_inner(model) + d)
    if kind == "attention_none":
        return int(matmul_params(model, kind)) + d
    router = model["router_experts"]
    return (d * router + router + 2 * d * model["moe_latent_size"]
            + shared_params(model)
            + model["n_routed_experts"] * expert_params(model) + d)


def total_params(model: dict) -> int:
    """Every parameter held: the table's slice, the head's, the layers,
    the final norm."""
    d = model["hidden_size"]
    table = model["vocab_size"] * d
    return (table * (1 if model["tie_word_embeddings"] else 2) + d
            + sum(layer_params(model, k) for k in kinds(model)))


def token_matmul_params(model: dict) -> float:
    """N of the 6*N rule: the head and every layer's ``matmul_params``. The
    table's lookup is no matmul."""
    return (model["hidden_size"] * model["vocab_size"]
            + sum(matmul_params(model, k) for k in kinds(model)))


def ssd_chunk_flops(model: dict) -> float:
    """Operations of one chunk of one head, forward, by the chunked
    algorithm (``ssd_train_cost``)."""
    q, p, n = (model["chunk_size"], model["mamba_head_dim"],
               model["ssm_state_size"])
    per_group = model["mamba_num_heads"] // model["n_groups"]
    return (2 * q * q * n / per_group    # C B^T, shared by a group's heads
            + 2 * q * q * p              # (C B^T * L) (dt X)
            + 2 * 2 * q * p * n)         # the chunk's state, and C S^T


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """6*N a token, plus the sequence operators' own products, forward and
    twice that backward, recomputed operations not counted: an attention
    layer's scores and values over the full (not causal-halved) sequence,
    by the dense family's convention, ``4 S H head_dim`` forward; a Mamba
    layer's core at its chunk form, ``ssd_chunk_flops`` over the chunk's
    positions a head."""
    attention = (4 * seq_len * model["num_attention_heads"]
                 * model["head_dim"])
    mamba = (model["mamba_num_heads"] * ssd_chunk_flops(model)
             / model["chunk_size"])
    return (6 * token_matmul_params(model)
            + 3 * (layers_of(model, "attention_none") * attention
                   + layers_of(model, "mamba_none") * mamba))


def flash_train_cost(model: dict, batch: int, seq_len: int,
                     itemsize: int = 2) -> dict:
    """Least work of the three flash kernels of one training step (forward,
    dq, dk/dv), causal, over the attention layers: the dense family's count
    (7 matmuls over the causal half, 15 passes over a ``[H, S, head_dim]``
    array, K and V at the query heads' width the training path repeats
    them to)."""
    h, hd = model["num_attention_heads"], model["head_dim"]
    n = layers_of(model, "attention_none") * batch
    pairs = seq_len * (seq_len + 1) // 2
    return {"flops": n * 7 * 2 * h * hd * pairs,
            "bytes": n * 15 * h * seq_len * hd * itemsize}


def ssd_train_cost(model: dict, batch: int, seq_len: int,
                   itemsize: int = 2) -> dict:
    """Least work of the Mamba layers' selective scan in one training step
    by the chunked algorithm (SSD, arXiv:2405.21060, section 6) at the
    configuration's ``chunk_size``, whatever implements it.

    The algorithm, a head and a chunk of Q positions, inputs of width P,
    state ``[P, N]``: the ``[Q, Q]`` scores ``C B^T`` over N, once a group
    and so ``1 / heads a group`` of 2 Q Q N a head; those scores under the
    decay's segment sums times the inputs, 2 Q Q P; the chunk's own state,
    2 Q P N; the carried state read by ``C``, 2 Q P N. That is
    ``ssd_chunk_flops`` forward; the backward pass is the transpose of each
    product, twice the forward's operations, so 3 times in all, for
    ``tokens / Q`` chunks of every head of every Mamba layer. The decays'
    exponentials, the skip and the elementwise scalings are not counted.

    Bytes: the state and a chunk's matrices can stay on the chip, so the
    least traffic is each operand once a pass. Forward reads x
    (``itemsize`` a value), dt (float32) and a head's share of its group's
    B and C, and writes y; backward reads those and dy, and writes dx, ddt
    and the head's share of dB and dC."""
    h, p, n = (model["mamba_num_heads"], model["mamba_head_dim"],
               model["ssm_state_size"])
    tokens = layers_of(model, "mamba_none") * batch * seq_len
    read = p * itemsize + 4 + 2 * n * itemsize * model["n_groups"] / h
    return {"flops": 3 * tokens * h * ssd_chunk_flops(model)
            / model["chunk_size"],
            "bytes": tokens * h * (read + p * itemsize               # forward
                                   + read + p * itemsize + read)}    # backward


def expected_pairs(model: dict, batch: int, seq_len: int) -> float:
    """(token, expert) pairs a step routes to the experts held here, in
    one expert layer, under even routing."""
    return (batch * seq_len * model["num_experts_per_tok"]
            * model["n_routed_experts"] / model["router_experts"])


def experts_train_cost(model: dict, batch: int, seq_len: int,
                       itemsize: int = 2) -> dict:
    """Least work of the held experts' two grouped products of one training
    step, forward and backward, over the expert layers, for the expected
    pairs: the same work whatever implements it, counted as the LFM2 and
    Ling families count it. Operations: a product of P rows between the
    latent width L and the expert's width F is 2*P*L*F forward and twice
    that backward, six products in all. Bytes: each of the six reads its
    two operands and writes its result once: the P x L rows, the P x F rows
    and the held experts' L x F weights. (The kernels run eight: the
    forward's two are made again in the backward pass, so their share of
    this has a ceiling of 75 %.)"""
    lat, f = model["moe_latent_size"], model["moe_intermediate_size"]
    p = expected_pairs(model, batch, seq_len)
    layers = layers_of(model, "none_moe")
    return {"flops": layers * 6 * 2 * p * lat * f,
            "bytes": layers * 6 * itemsize * (
                p * lat + p * f + model["n_routed_experts"] * lat * f)}
