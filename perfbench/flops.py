"""Operations and bytes counted from a configuration's shapes.

The yardstick's arithmetic: nothing here imports the program. ``model``
is the ``model`` object of a file under ``configs/`` (the source's keys).
A multiply-add counts as two operations.
"""

from __future__ import annotations


def layer_params(model: dict) -> int:
    """Matmul parameters of one layer (the two norm vectors left out)."""
    d, f = model["hidden_size"], model["intermediate_size"]
    hd = model["head_dim"]
    q, kv = model["num_attention_heads"], model["num_key_value_heads"]
    return d * hd * (2 * q + 2 * kv) + 3 * d * f


def head_params(model: dict) -> int:
    return model["hidden_size"] * model["vocab_size"]


def matmul_params(model: dict) -> int:
    """N of the 6*N rule: every weight a token is multiplied with. The
    embedding table is a lookup, not a matmul, and is left out."""
    return (model["num_hidden_layers"] * layer_params(model)
            + head_params(model))


def total_params(model: dict) -> int:
    """Every parameter held: embedding, layers with norms, final norm, head."""
    d = model["hidden_size"]
    return (2 * head_params(model) + d
            + model["num_hidden_layers"] * (layer_params(model) + 2 * d))


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """6*N + 12*L*S*D a token: forward and backward of the matmuls, and of
    full (not causal-halved) attention, as bench.py:_model_point counted
    it; recomputed operations do not count."""
    d_attn = model["num_attention_heads"] * model["head_dim"]
    return (6 * matmul_params(model)
            + 12 * model["num_hidden_layers"] * seq_len * d_attn)


def flash_train_cost(model: dict, batch: int, seq_len: int,
                     itemsize: int = 2) -> dict:
    """Least work of the three flash kernels of one training step (forward,
    dq, dk/dv), causal, all layers. Operations: the forward's two matmuls
    over the causal half; the backward's five (S recomputed, dP, dQ, dK,
    dV) over the same half. Bytes: each kernel reads q, k, v (the backward
    also o, do) and writes its outputs once, K/V at the query-head width
    the training path repeats them to."""
    h, hd = model["num_attention_heads"], model["head_dim"]
    n = model["num_hidden_layers"] * batch
    pairs = seq_len * (seq_len + 1) // 2
    one = 2 * h * hd * pairs                      # one matmul, causal
    tensor = h * seq_len * hd * itemsize          # one [H, S, Dh] array
    return {
        "flops": n * (2 + 5) * one,
        "bytes": n * (4 + 5 + 6) * tensor,
        # fwd: q k v -> o (4); dq: q k v do (+o for delta) -> dq (5 + 1);
        # dkdv: q k v do -> dk dv (6), the delta/lse vectors are small.
    }


def roofline_seconds(cost: dict, peak: dict) -> tuple:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = cost["flops"] / peak["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
