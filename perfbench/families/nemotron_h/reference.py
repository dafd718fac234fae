"""The plain reference: a Nemotron-H model in straightforward jax.numpy.

Float32 throughout, every matmul through ``mm`` (``highest`` precision for
the reference, ``mm_int8`` for the control; ``perfbench/reference/
numerics.py``). No kernels, no cache, no chunks, no sorting or grouping; it
imports nothing of the program. ``x`` is ``[S, D]``, ``rms`` RMSNorm at
``layer_norm_epsilon``. Every layer is one thing alone, ``x <- x +
mixer(rms(x))`` with one norm and one residual add; after the last layer
one more RMSNorm, then the (untied) head. The mixer, by the layer's letter
in ``hybrid_override_pattern``:

- ``M``, Mamba-2 (arXiv:2405.21060; the ``nemotron_h`` Mamba-2 mixer), H
  heads of P channels, G groups, state N: ``[z | xBC | dt] = u W_in`` (no
  bias), widths ``H P``, ``H P + 2 G N``, ``H``; ``xBC <- silu(conv(xBC))``,
  ``conv`` a causal depthwise convolution of ``conv_kernel`` taps with a
  bias (zeros before the sequence); split ``x [H, P]``, ``B [G, N]``,
  ``C [G, N]``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, one a
  head. **The recurrence, position by position** (``ssm_state``), per head
  ``h`` of group ``g`` from ``S = 0 [P, N]``: ``S <- exp(dt_t A) S + dt_t
  x_t B_t^T``; ``y_t = S C_t + D_h x_t``. Then ``y <- rms over each group's
  H P / G channels of (y * silu(z))``, one weight a channel, and ``W_out``
  (no bias);
- ``*``, attention: grouped queries, heads of ``head_dim``, no bias, causal
  softmax of ``q k^T head_dim ** -0.5`` over ``v``, one head at a time,
  **no rotary embedding** (the family's attention applies none), then
  ``W_o``;
- ``E``, the latent expert layer: ``s = sigmoid(u W_r)`` over the router's
  published width; the experts of a token are the top
  ``num_experts_per_tok`` of ``s + bias`` (``n_group`` 1: no group limit;
  the bias selects and never weighs); their gates ``s`` at those experts
  over ``(their sum + 1e-20)`` times ``routed_scaling_factor``; ``l = u
  W_dn`` at the latent width; expert ``e``: ``relu(l W1_e) ** 2 W2_e``;
  ``routed = (sum over them of gate_e * expert_e(l)) W_up``; ``shared =
  relu(u W_s1) ** 2 W_s2`` on ``u`` at the model's width; the mixer is
  ``routed + shared``. Every expert held is applied to every token and
  masked by its gate, one expert after another.

Departures, each the configuration's (its file states them): **the share**
(the router keeps its published width and experts a token; only the
experts of ``experts_held``, ``mamba_num_heads`` of the published Mamba
heads in ``n_groups`` of the groups, and ``num_attention_heads`` /
``num_key_value_heads`` of the attention heads exist here, and what the
absent ones would have added is left out, in program and reference alike;
router, latent projections and shared expert are whole); **the sliced
vocabulary** (ids, logits and loss over the slice); **the bias** is a leaf
no gradient reaches (``weights.py`` balances it once at set-up; here it is
given).

Each layer under ``jax.checkpoint``, one head's scores at a time in an
attention layer, and a Mamba layer's scan over positions nested (blocks of
``KEEP`` positions, each under ``jax.checkpoint``), which changes no
operation of the recurrence and keeps its backward pass to one state a
block, so that a float32 step of 4096 tokens fits beside its AdamW state.
For the compiler's sake and changing no number (at ``highest`` the v5e's
compiler takes seconds over every product it meets): a stretch of the
stack that repeats a unit of layers is one ``lax.scan`` over the unit
(``layer_units``), and products that share their left operand are one
product with the weights side by side (``beside``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.reference.numerics import mm_highest

from . import counts

KEEP = 64           # positions between the states the backward pass keeps
NORM_TOPK_EPS = 1e-20
NORMS = {"none_moe": "mlp_norm", "mamba_none": "mamba_norm",
         "attention_none": "attn_norm"}


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def beside(z, weights, mm):
    """``[mm(z, w) for w in weights]`` as one product: the weights side by
    side, the result cut where they meet."""
    widths = [w.shape[1] for w in weights]
    cuts = [sum(widths[:i + 1]) for i in range(len(widths) - 1)]
    return jnp.split(mm(z, jnp.concatenate(weights, axis=1)), cuts, axis=-1)


def causal_taps(x, taps, bias):
    """x [S, C], taps [C, K], bias [C]: y_t = bias + sum_j taps[:, j] *
    x_{t-(K-1)+j}."""
    s, k = x.shape[0], taps.shape[1]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return bias + sum(padded[j:j + s] * taps[:, j] for j in range(k))


def ssm_state(x, dt, a, b, c, d):
    """The recurrence over positions. x [S, H, P], dt [S, H], a and d [H],
    b and c [S, G, N] (head h reads group ``h // (H // G)``) -> y
    [S, H, P]."""
    s, h, p = x.shape
    per_group = h // b.shape[1]
    b, c = (jnp.repeat(v, per_group, axis=1) for v in (b, c))   # [S, H, N]

    def step(state, at):
        x, dt, b, c = at
        state = jnp.exp(dt * a)[:, None, None] * state \
            + (dt[:, None] * x)[:, :, None] * b[:, None, :]
        return state, jnp.sum(state * c[:, None, :], axis=-1) + d[:, None] * x

    @jax.checkpoint
    def block(state, ats):
        return lax.scan(step, state, ats)

    pad = -s % KEEP
    ats = [jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
           for v in (x, dt, b, c)]          # dt 0: the state stays
    ats = [v.reshape((-1, KEEP) + v.shape[1:]) for v in ats]
    _, y = lax.scan(block, jnp.zeros((h, p, b.shape[-1]), jnp.float32),
                    tuple(ats))
    return y.reshape((-1, h, p))[:s]


def mamba(model: dict, lp: dict, u, mm):
    s = u.shape[0]
    h, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n = model["n_groups"], model["ssm_state_size"]
    inner, mixed = counts.mamba_inner(model), counts.mamba_mixed(model)
    z, xbc, dt = jnp.split(mm(u, lp["mamba_in"]), [inner, inner + mixed],
                           axis=-1)
    xbc = jax.nn.silu(causal_taps(xbc, lp["mamba_taps"],
                                  lp["mamba_conv_bias"]))
    x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    y = ssm_state(x.reshape(s, h, p),
                  jax.nn.softplus(dt + lp["mamba_dt_bias"]),
                  -jnp.exp(lp["mamba_a_log"]), b.reshape(s, g, n),
                  c.reshape(s, g, n), lp["mamba_d"])
    y = y.reshape(s, inner) * jax.nn.silu(z)
    y = rms_norm(y.reshape(s, g, -1), lp["mamba_gate_norm"].reshape(g, -1),
                 model["layer_norm_epsilon"])
    return mm(y.reshape(s, inner), lp["mamba_out"])


def attention(model: dict, lp: dict, u, mm):
    """Causal grouped-query attention of one sequence, one head at a time,
    no rotary embedding."""
    s, hd = u.shape[0], model["head_dim"]
    q, k, v = (a.reshape(s, -1, hd)
               for a in beside(u, [lp["wq"], lp["wk"], lp["wv"]], mm))
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args
        sc = mm(qh, kh.T) * (hd ** -0.5)
        return mm(jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1), vh)

    o = lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
    return mm(o.transpose(1, 0, 2).reshape(s, -1), lp["wo"])


def router_scores(lp: dict, u, mm):
    """The router's sigmoid scores [S, E] of normed u [S, D]."""
    return jax.nn.sigmoid(mm(u, lp["router"]))


def choose(model: dict, select):
    """The experts [S, k] of ``select`` [S, E] (scores plus bias): the k
    largest, no group limit."""
    if model["n_group"] != 1 or model["topk_group"] != 1:
        raise ValueError("this family's router has one group")
    return lax.top_k(select, model["num_experts_per_tok"])[1]


def routing(model: dict, lp: dict, u, mm):
    """(experts [S, k] of the router's published width, gates [S, k])."""
    scores = router_scores(lp, u, mm)
    experts = choose(model, scores + lax.stop_gradient(lp["expert_bias"]))
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if model["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True)
                         + NORM_TOPK_EPS)
    return experts, gates * model["routed_scaling_factor"]


def held_experts(model: dict, lp: dict, u, mm):
    """The part of the routed result that the experts held here give, at
    the latent width, before the up projection: every one of them applied
    to every token's latent, weighed by the token's gate for it, zero where
    the token did not choose it."""
    experts, gates = routing(model, lp, u, mm)
    latent = mm(u, lp["latent_down"])

    def one(out, expert_of):
        expert, w1, w2 = expert_of
        gate = jnp.sum(jnp.where(experts == expert, gates, 0.0), axis=-1)
        return out + gate[:, None] * mm(relu2(mm(latent, w1)), w2), None

    held = jnp.asarray(model["experts_held"], jnp.int32)
    return lax.scan(one, jnp.zeros_like(latent),
                    (held, lp["e_up"], lp["e_down"]))[0]


def shared_expert(lp: dict, u, mm):
    return mm(relu2(mm(u, lp["s_up"])), lp["s_down"])


def experts(model: dict, lp: dict, u, mm):
    return mm(held_experts(model, lp, u, mm), lp["latent_up"]) \
        + shared_expert(lp, u, mm)


MIXERS = {"none_moe": experts, "mamba_none": mamba,
          "attention_none": attention}


def layer(model: dict, kind: str, lp: dict, x, mm):
    u = rms_norm(x, lp[NORMS[kind]], model["layer_norm_epsilon"])
    return x + MIXERS[kind](model, lp, u, mm)


def layer_units(model: dict) -> list:
    """(kinds, starts, count) of each stretch of the stack that is
    ``count`` times the unit ``kinds`` (distinct kinds; ``starts`` counts
    within each kind's own stack), in published order: from each layer on,
    the unit that repeats over the most layers, else the layer alone."""
    every, units, seen, i = counts.kinds(model), [], {}, 0
    while i < len(every):
        period, repeats = 1, 1
        for p in range(1, (len(every) - i) // 2 + 1):
            unit, r = every[i:i + p], 1
            while every[i + r * p:i + (r + 1) * p] == unit:
                r += 1
            if len(set(unit)) == p and r > 1 and p * r > period * repeats:
                period, repeats = p, r
        unit = tuple(every[i:i + period])
        units.append((unit, tuple(seen.get(k, 0) for k in unit), repeats))
        for k in unit:
            seen[k] = seen.get(k, 0) + repeats
        i += period * repeats
    return units


def unit_stacks(params: dict, kinds: tuple, starts: tuple, count: int):
    """The slices of the kinds' stacks that one stretch of units holds."""
    return tuple(jax.tree.map(lambda a: a[start:start + count],
                              params["layers"][kind])
                 for kind, start in zip(kinds, starts))


def hidden(model: dict, params: dict, tokens, mm=mm_highest):
    """tokens [S] -> the final-normed hidden states [S, D]. ``model`` is
    the configuration as it is run; layer ``i`` of the published order is
    the next slice of its kind's stack, and a repeating unit a scan."""
    x = params["embed"][tokens]
    for kinds, starts, count in layer_units(model):
        def unit(x, lps, kinds=kinds):
            for kind, lp in zip(kinds, lps):
                x = jax.checkpoint(
                    lambda x, lp, kind=kind: layer(model, kind, lp, x, mm)
                )(x, lp)
            return x, None
        x, _ = lax.scan(unit, x, unit_stacks(params, kinds, starts, count))
    return rms_norm(x, params["final_norm"], model["layer_norm_epsilon"])


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
