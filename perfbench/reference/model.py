"""The plain reference: Mistral's decoder in straightforward jax.numpy.

Float32 throughout, every matmul at ``highest`` precision (on a TPU a
float32 matmul is otherwise several bf16 passes short of float32). No
kernels, no cache, no batching tricks; it imports nothing of the program.
It follows the published description (RMSNorm, rotary embedding on the
two halves of a head as Hugging Face's ``rotate_half``, grouped-query
attention, SwiGLU, untied head, no biases). The norm's eps is an argument:
the driver hands it the eps that the program runs, which the
configuration's file states under ``assumed`` beside the published one.
Attention is taken one KV group at a time so that the score matrix of a
long sequence fits beside the weights.

``mm`` is the matmul every product goes through. ``mm_highest`` is the
reference; ``mm_int8`` is the control of "How correct is decided": the
same mathematics with both operands of every matmul rounded to int8
(absmax, per row of the left and per column of the right), forward and
backward: the precision below bf16 that a later PR would be tempted by.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def mm_highest(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _q8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


@jax.custom_vjp
def mm_int8(a, b):
    return jnp.matmul(_q8(a, -1), _q8(b, -2), precision=HIGHEST)


def _mm_int8_fwd(a, b):
    return mm_int8(a, b), (a, b)


def _mm_int8_bwd(res, g):
    a, b = res
    bt, at = jnp.swapaxes(b, -1, -2), jnp.swapaxes(a, -1, -2)
    da = jnp.matmul(_q8(g, -1), _q8(bt, -2), precision=HIGHEST)
    db = jnp.matmul(_q8(at, -1), _q8(g, -2), precision=HIGHEST)
    # b may be a plain [K, N] weight under a batched a: sum the batch out.
    while db.ndim > b.ndim:
        db = db.sum(0)
    return da, db


mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)

MATMULS = {"highest": mm_highest, "int8": mm_int8}


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
    """Causal grouped-query attention of one sequence, one KV group at a
    time. q [S, Hq, Dh], k/v [S, Hkv, Dh] -> [S, Hq, Dh]."""
    s, hq, dh = q.shape
    hkv = k.shape[1]
    qg = q.reshape(s, hkv, hq // hkv, dh).transpose(1, 2, 0, 3)  # [Hkv,G,S,Dh]
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def group(args):
        qh, kh, vh = args                      # [G,S,Dh], [S,Dh], [S,Dh]
        sc = mm(qh, kh.T) * (dh ** -0.5)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return mm(p, vh)

    o = lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return o.transpose(2, 0, 1, 3).reshape(s, hq, dh)


def hidden(model: dict, params: dict, tokens, mm=mm_highest):
    """tokens [S] -> the final-normed hidden states [S, D]. ``model`` is
    the configuration as it is run (``run_model`` of the driver)."""
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    dh = model["head_dim"]
    x = params["embed"][tokens]

    @jax.checkpoint
    def layer(x, lp):
        s = x.shape[0]
        h = rms_norm(x, lp["attn_norm"], eps)
        q = rope(mm(h, lp["wq"]).reshape(s, -1, dh), theta)
        k = rope(mm(h, lp["wk"]).reshape(s, -1, dh), theta)
        v = mm(h, lp["wv"]).reshape(s, -1, dh)
        x = x + mm(attention(q, k, v, mm).reshape(s, -1), lp["wo"])
        h = rms_norm(x, lp["mlp_norm"], eps)
        gate = jax.nn.silu(mm(h, lp["w_gate"])) * mm(h, lp["w_up"])
        return x + mm(gate, lp["w_down"]), None

    x, _ = lax.scan(layer, x, params["layers"])
    return rms_norm(x, params["final_norm"], eps)


def loss(model: dict, params: dict, tokens, targets, mm=mm_highest):
    """Mean next-token cross-entropy over a batch tokens/targets [B, S]."""
    def one(tok, tgt):
        lg = mm(hidden(model, params, tok, mm), params["lm_head"])
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
    return jnp.mean(lax.map(lambda a: one(*a), (tokens, targets)))


def adamw_step(params, mu, nu, grads, count, hp):
    """One AdamW update as published (decoupled weight decay, bias
    correction), count = the number of this update, from 1."""
    b1, b2 = hp["b1"], hp["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count

    def upd(p, m, n):
        step = (m / c1) / (jnp.sqrt(n / c2) + hp["eps"])
        return p - hp["learning_rate"] * (step + hp["weight_decay"] * p)

    return jax.tree.map(upd, params, mu, nu), mu, nu
