"""The plain reference: Ouro's looped decoder (ByteDance's LoopLM,
arXiv:2510.25741, "Scaling Latent Reasoning via Looped Language Models";
``model_type`` ``ouro``) in straightforward jax.numpy.

Float32 throughout, every product through ``mm``
(``perfbench/reference/numerics.py``: ``mm_highest`` for the reference,
float32 at ``highest``; ``mm_int8`` for the control). No kernels, no cache,
no batching tricks; it imports nothing of the program.

A layer, as the released modeling code writes it: attention and SwiGLU
with sandwich norms, an RMSNorm before and after each sub-layer::

    a  = u + N_attn_post(Attn(N_attn_pre(u)))
    u' = a + N_mlp_post(W_down(silu(N_mlp_pre(a) W_gate) * N_mlp_pre(a) W_up))

Attention is multi-head, causal, rotary on the two halves of a head,
softmax scale ``head_dim ** -0.5``, no bias. The loop and its objective
(the report's Stage I), per position::

    x_0 = Emb(tokens)
    x_t = N_final(Layers(x_{t-1}))             t = 1..T, the same weights
    lambda_t = sigmoid(x_t w_g + b_g);  l_t = -log softmax(x_t W_head)[target]
    p_1 = lambda_1;  p_t = lambda_t prod_{j<t} (1 - lambda_j), 1 < t < T;
    p_T = prod_{j<T} (1 - lambda_j)
    L = mean over positions of sum_t p_t l_t + beta sum_t p_t log p_t

Departures, each under the configuration's ``assumed``: the layer's norms
and the loop's input (``sandwich_norm``, ``loop_input``) are the released
code's, which the config's keys do not state; the gate reads the normed
state (``exit_gate``); ``beta`` is the report's Stage I coefficient
(``exit_entropy_beta``); no attention bias (``attention_bias``).

Attention is taken one head at a time, and each layer application and each
pass's head and loss lie under ``jax.checkpoint``: that changes no value,
and the float32 step needs it to fit one chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.reference.numerics import mm_highest


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w


def rope(x, theta):
    """x [S, H, Dh], positions 0..S-1, the halves of a head turned."""
    s, _, dh = x.shape
    half = dh // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, mm):
    """Causal multi-head attention of one sequence, a head at a time.
    q, k, v [S, H, Dh] -> [S, H, Dh]."""
    s, _, dh = q.shape
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args                                    # [S, Dh] each
        scores = mm(qh, kh.T) * (dh ** -0.5)
        return mm(jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1), vh)

    o = lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
    return o.transpose(1, 0, 2)


def layer(model: dict, lp: dict, x, mm):
    """One layer, x [S, D] -> [S, D]."""
    eps, theta, dh = (model["rms_norm_eps"], model["rope_theta"],
                      model["head_dim"])
    s = x.shape[0]
    h = rms_norm(x, lp["attn_norm"], eps)
    q = rope(mm(h, lp["wq"]).reshape(s, -1, dh), theta)
    k = rope(mm(h, lp["wk"]).reshape(s, -1, dh), theta)
    v = mm(h, lp["wv"]).reshape(s, -1, dh)
    a = mm(attention(q, k, v, mm).reshape(s, -1), lp["wo"])
    x = x + rms_norm(a, lp["attn_post_norm"], eps)
    h = rms_norm(x, lp["mlp_norm"], eps)
    m = mm(jax.nn.silu(mm(h, lp["w_gate"])) * mm(h, lp["w_up"]),
           lp["w_down"])
    return x + rms_norm(m, lp["mlp_post_norm"], eps)


def passes(model: dict, params: dict, tokens, mm=mm_highest) -> list:
    """tokens [S] -> the T passes' normed states ``x_t`` [S, D]."""
    x = params["embed"][tokens]
    out = []
    for _ in range(model["total_ut_steps"]):
        x, _ = lax.scan(jax.checkpoint(
            lambda x, lp: (layer(model, lp, x, mm), None)),
            x, params["layers"])
        x = rms_norm(x, params["final_norm"], model["rms_norm_eps"])
        out.append(x)
    return out


def exit_probs(params: dict, xs: list, mm=mm_highest):
    """The exit distribution p [T, S] of the passes' states ``xs``; the
    last pass's gate weighs nothing, and is not read."""
    p, stay = [], jnp.ones(xs[0].shape[:1], xs[0].dtype)
    for x in xs[:-1]:
        gate = params["exit_gate"]
        lam = jax.nn.sigmoid(mm(x, gate["w"])[:, 0] + gate["b"][0])
        p.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(p + [stay])


def parts(model: dict, params: dict, tokens, targets, mm=mm_highest):
    """One sequence: (p [T, S], the passes' next-token losses l [T, S])."""
    xs = passes(model, params, tokens, mm)

    @jax.checkpoint
    def nll(x):
        logp = jax.nn.log_softmax(mm(x, params["lm_head"]), axis=-1)
        return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]

    return exit_probs(params, xs, mm), jnp.stack([nll(x) for x in xs])


def loss(model: dict, params: dict, tokens, targets, mm=mm_highest):
    """The exit objective of a batch tokens/targets [B, S]: the mean over
    rows and positions of ``sum_t p_t l_t + beta sum_t p_t log p_t``."""
    beta = model["exit_entropy_beta"]

    def one(tok, tgt):
        p, l = parts(model, params, tok, tgt, mm)
        return jnp.sum(p * l + beta * p * jnp.log(p), axis=0)

    return jnp.mean(lax.map(lambda a: one(*a), (tokens, targets)))
