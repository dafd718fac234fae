"""The plain reference: LFM2's mixture-of-experts decoder in
straightforward jax.numpy.

Float32 throughout, every matmul through ``mm`` (``highest`` precision for
the reference, ``mm_int8`` for the control; ``perfbench/reference/
numerics.py``). No kernels, no cache, no sorting or grouping; it imports
nothing of the program. It follows the published description
(``LiquidAI/LFM2-24B-A2B``, ``model_type`` ``lfm2_moe``), ``x`` being
``[S, D]``:

- every layer: ``h = x + op(rms(x; w_op))``, ``y = h + ffn(rms(h; w_ffn))``,
  eps ``norm_eps``; after the last layer one more RMSNorm, then the head;
- ``op`` of a conv layer, the gated short convolution: ``[b, c, u] =
  split3(z W_in)``, ``v = b * u``, ``y_t = sum_j taps[:, j] * v_{t-(K-1)+j}``
  (depthwise, causal, zeros before the sequence, no bias), ``op = (c * y)
  W_out``; no activation function;
- ``op`` of a full_attention layer: q, k, v projections, RMSNorm over each
  head's values of q and of k (own weights) before the rotary embedding
  (Hugging Face's ``rotate_half``), causal softmax attention scaled
  ``head_dim ** -0.5`` with grouped KV heads, the output projection;
- ``ffn`` of the ``num_dense_layers`` leading layers: SwiGLU;
- ``ffn`` of the others: ``s = sigmoid(z W_r)``; the experts of a token are
  the top ``num_experts_per_tok`` of ``s + bias``; their gates ``s`` at
  those experts over ``(their sum + 1e-6)`` (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``ffn = sum over them of gate * SwiGLU_e(z)``.
  Every expert held is applied to every token and masked by its gate.

Departures, each the configuration's (its file states them):

- **the share**: the router keeps its published width and the token its
  published number of experts, but only the experts of ``experts_held``
  exist here. What the absent experts would have added is left out, and
  that partial sum goes on to the next layer, as in the program;
- **the sliced vocabulary**: ids, logits and loss are over the slice; the
  head is the table, transposed (``tie_word_embeddings``);
- **the bias** is a leaf that no gradient reaches: it selects and does
  not weigh. Its values are the weights' business (``weights.py`` balances
  the experts' loads with it once, at set-up); here it is given.

One query head's scores at a time and each layer under ``jax.checkpoint``,
so that a float32 step of 8192 tokens fits beside its AdamW state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.reference.numerics import mm_highest

from . import counts


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w


def rope(x, theta):
    """x [S, H, Dh], positions 0..S-1."""
    s, _, dh = x.shape
    half = dh // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, mm):
    """Causal grouped-query attention of one sequence, one query head at a
    time. q [S, Hq, Dh], k/v [S, Hkv, Dh] -> [S, Hq, Dh]."""
    s, hq, dh = q.shape
    group = hq // k.shape[1]
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args                                  # [S, Dh] each
        sc = mm(qh, kh.T) * (dh ** -0.5)
        return mm(jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1), vh)

    kv_of = jnp.arange(hq) // group
    o = lax.map(head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2)[kv_of],
                       v.transpose(1, 0, 2)[kv_of]))
    return o.transpose(1, 0, 2)


def short_conv(model: dict, lp: dict, z, mm):
    """The gated short convolution of normed z [S, D]."""
    s, taps = z.shape[0], model["conv_L_cache"]
    b, c, u = jnp.split(mm(z, lp["conv_in"]), 3, axis=-1)
    v = jnp.pad(b * u, ((taps - 1, 0), (0, 0)))
    y = sum(v[j:j + s] * lp["conv_taps"][:, j] for j in range(taps))
    return mm(c * y, lp["conv_out"])


def full_attention(model: dict, lp: dict, z, mm):
    s, dh = z.shape[0], counts.head_dim(model)
    eps, theta = model["norm_eps"], model["rope_parameters"]["rope_theta"]
    q = mm(z, lp["wq"]).reshape(s, -1, dh)
    k = mm(z, lp["wk"]).reshape(s, -1, dh)
    v = mm(z, lp["wv"]).reshape(s, -1, dh)
    q = rope(rms_norm(q, lp["q_norm"], eps), theta)
    k = rope(rms_norm(k, lp["k_norm"], eps), theta)
    return mm(attention(q, k, v, mm).reshape(s, -1), lp["wo"])


def swiglu(z, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(z, w_gate)) * mm(z, w_up), w_down)


def router_scores(lp: dict, z, mm):
    """The router's sigmoid scores [S, E] of normed z [S, D]."""
    return jax.nn.sigmoid(mm(z, lp["router"]))


def routing(model: dict, lp: dict, z, mm):
    """(experts [S, k] of the router's published width, gates [S, k])."""
    scores = router_scores(lp, z, mm)
    select = scores + lax.stop_gradient(lp["expert_bias"]) \
        if model["use_expert_bias"] else scores
    _, experts = lax.top_k(select, model["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if model["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    return experts, gates * model["routed_scaling_factor"]


def held_experts(model: dict, lp: dict, z, mm):
    """The part of the expert layer that the experts held here give:
    every one of them applied to every token, weighed by the token's gate
    for it, zero where the token did not choose it."""
    experts, gates = routing(model, lp, z, mm)

    def one(out, held):
        expert, w_gate, w_up, w_down = held
        gate = jnp.sum(jnp.where(experts == expert, gates, 0.0), axis=-1)
        return out + gate[:, None] * swiglu(z, w_gate, w_up, w_down, mm), None

    out, _ = lax.scan(one, jnp.zeros_like(z), (
        jnp.asarray(model["experts_held"], jnp.int32),
        lp["e_gate"], lp["e_up"], lp["e_down"]))
    return out


def operator(model: dict, kind: str, lp: dict, x, mm):
    """x plus the layer's sequence operator on its normed x."""
    eps = model["norm_eps"]
    if kind.startswith("conv"):
        return x + short_conv(model, lp,
                              rms_norm(x, lp["conv_norm"], eps), mm)
    return x + full_attention(model, lp, rms_norm(x, lp["attn_norm"], eps), mm)


def layer(model: dict, kind: str, lp: dict, x, mm):
    x = operator(model, kind, lp, x, mm)
    z = rms_norm(x, lp["mlp_norm"], model["norm_eps"])
    if kind.endswith("dense"):
        return x + swiglu(z, lp["w_gate"], lp["w_up"], lp["w_down"], mm)
    return x + held_experts(model, lp, z, mm)


def hidden(model: dict, params: dict, tokens, mm=mm_highest):
    """tokens [S] -> the final-normed hidden states [S, D]. ``model`` is
    the configuration as it is run; layer ``i`` of the published order is
    the next slice of its kind's stack."""
    x = params["embed"][tokens]
    seen = {}
    for kind in counts.kinds(model):
        at = seen.get(kind, 0)
        seen[kind] = at + 1
        lp = jax.tree.map(lambda a: a[at], params["layers"][kind])
        x = jax.checkpoint(
            lambda x, lp, kind=kind: layer(model, kind, lp, x, mm))(x, lp)
    return rms_norm(x, params["final_norm"], model["norm_eps"])


def loss(model: dict, params: dict, tokens, targets, mm=mm_highest):
    """Mean next-token cross-entropy over a batch tokens/targets [B, S],
    over the vocabulary's slice."""
    head = params["embed"].T if model["tie_word_embeddings"] \
        else params["lm_head"]

    def one(tok, tgt):
        logp = jax.nn.log_softmax(
            mm(hidden(model, params, tok, mm), head), axis=-1)
        return -jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
    return jnp.mean(lax.map(lambda a: one(*a), (tokens, targets)))
