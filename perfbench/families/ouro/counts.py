"""Operations and bytes of a looped decoder, counted from a configuration's
shapes: what the readers of an Ouro cell divide by.

The yardstick's arithmetic: nothing here imports the program. ``model`` is
the ``model`` object of a file under ``configs/`` as it is run (the
source's keys, ``total_ut_steps`` among them). A multiply-add counts as two
operations. The stack runs ``total_ut_steps`` (T) times a step with the same
weights, and the head once after every pass, so a token meets T times the
stack's and the head's weights; what the backward pass makes again of a
checkpointed layer or head is not counted.
"""

from __future__ import annotations


def layer_matmul_params(model: dict) -> int:
    """The products' parameters of one layer: q, k, v, o and the SwiGLU."""
    d, f = model["hidden_size"], model["intermediate_size"]
    hd = model["head_dim"]
    q, kv = model["num_attention_heads"], model["num_key_value_heads"]
    return d * hd * (2 * q + 2 * kv) + 3 * d * f


def layer_params(model: dict) -> int:
    """One layer whole: its products and its four norms (two before and
    two after its sub-layers)."""
    return layer_matmul_params(model) + 4 * model["hidden_size"]


def head_params(model: dict) -> int:
    return model["hidden_size"] * model["vocab_size"]


def total_params(model: dict) -> int:
    """Every parameter held: the table, the layers, the final norm, the
    exit gate (a weight a channel and a bias) and the head."""
    d = model["hidden_size"]
    return (2 * head_params(model) + d + (d + 1)
            + model["num_hidden_layers"] * layer_params(model))


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """6 N + 12 T L S D a token, N = T (L times a layer's products plus the
    head): forward and backward of every pass's products and of full (not
    causal-halved) attention, by the dense family's convention. The gate's
    product (D a pass) and the recomputed forward are not counted."""
    t, n = model["total_ut_steps"], model["num_hidden_layers"]
    d_attn = model["num_attention_heads"] * model["head_dim"]
    matmul = t * (n * layer_matmul_params(model) + head_params(model))
    return 6 * matmul + 12 * t * n * seq_len * d_attn


def flash_train_cost(model: dict, batch: int, seq_len: int,
                     itemsize: int = 2) -> dict:
    """Least work of the three flash kernels of one training step (forward,
    dq, dk/dv), causal, T times L layer applications. Operations: the
    forward's two matmuls over the causal half; the backward's five (S
    recomputed, dP, dQ, dK, dV) over the same half. Bytes: each kernel
    reads q, k, v (the backward also o, do) and writes its outputs once.
    The forward the backward pass makes again of a checkpointed layer is
    not counted, as the recomputation is not in the step's operations."""
    h, hd = model["num_attention_heads"], model["head_dim"]
    n = model["total_ut_steps"] * model["num_hidden_layers"] * batch
    pairs = seq_len * (seq_len + 1) // 2
    one = 2 * h * hd * pairs                      # one matmul, causal
    tensor = h * seq_len * hd * itemsize          # one [H, S, Dh] array
    return {"flops": n * (2 + 5) * one, "bytes": n * (4 + 5 + 6) * tensor}
