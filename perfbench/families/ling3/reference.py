"""The plain reference: the Ling 3.0 language model in straightforward
jax.numpy.

Float32 throughout, every matmul through ``mm`` (``highest`` precision for
the reference, ``mm_int8`` for the control; ``perfbench/reference/
numerics.py``). No kernels, no cache, no chunks, no sorting or grouping; it
imports nothing of the program. ``x`` is ``[S, D]``, ``H`` the heads held,
``rms`` RMSNorm at ``rms_norm_eps``:

- every layer: ``h = x + op(rms(x))``, ``y = h + ffn(rms(h))``; after the
  last layer one more RMSNorm, then the (untied) head;
- ``op`` of a KDA layer (Kimi Linear, arXiv:2510.26692, section 3):
  ``q, k, v = silu(conv(z W_q)), silu(conv(z W_k)), silu(conv(z W_v))``,
  ``conv`` a causal depthwise convolution of ``short_conv_kernel_size``
  taps (zeros before the sequence, no bias); per head ``q <- q / |q| *
  head_dim ** -0.5``, ``k <- k / |k|``; ``log alpha = kda_lower_bound *
  sigmoid(exp(A_log_h) * (z W_a + dt_bias))``, one value a channel;
  ``beta = sigmoid(z W_beta)``, one a head. **The recurrence, position by
  position** (``kda_state``), per head from ``S = 0 [head_dim, head_dim]``:
  ``S <- Diag(alpha_t) S``; ``S <- S + beta_t k_t (v_t - S^T k_t)^T``;
  ``o_t = S^T q_t``. ``op = (rms_head(o; w [head_dim]) * sigmoid(z W_g))
  W_o``. No rope;
- ``op`` of an MLA layer (DeepSeek-V2, arXiv:2405.04434, section 2.1, no
  query latent): ``q = z W_q`` -> ``[H, nope + rope]``; ``[c | k_rope] = z
  W_kva``; ``c <- rms(c)``; ``[k_nope_h | v_h] = c W_kvb``; the rotary
  embedding (half rotation) on q's rotary part and on ``k_rope``, which
  every head shares; causal softmax of ``q_h k_h^T (nope + rope) ** -0.5``
  over ``v_h``, one head at a time; each head's output under ``sigmoid(z
  W_gate)_h``, then ``W_o``;
- ``ffn`` of the ``first_k_dense_replace`` leading layers: SwiGLU;
- ``ffn`` of the others (DeepSeek-V3's router, arXiv:2412.19437, section
  2.1.2): ``s = sigmoid(z W_r)``; ``c = s + bias``; the experts are
  ``n_group`` groups of consecutive ones, a group's score the sum of its
  two largest ``c``, the ``topk_group`` best groups kept; the experts of a
  token are the top ``num_experts_per_tok`` of ``c`` among the kept
  groups'; their gates ``s`` at those experts over ``(their sum + 1e-20)``
  times ``routed_scaling_factor``; ``ffn = sum over them of gate *
  SwiGLU_e(z) + SwiGLU_shared(z)``. Every expert held is applied to every
  token and masked by its gate.

Departures, each the configuration's (its file states them): **the share**
(the router keeps its published width, groups and experts a token; only
the experts of ``experts_held`` and ``num_attention_heads`` of the
published heads exist here, and what the absent ones would have added is
left out, in program and reference alike; the shared expert, the latent
projection and the router are whole); **the sliced vocabulary** (ids,
logits and loss over the slice); **the bias** is a leaf no gradient
reaches (``weights.py`` balances it once at set-up; here it is given).

Each layer under ``jax.checkpoint``, one head's scores at a time in an MLA
layer, and a KDA layer's scan over positions nested (blocks of
``KDA_KEEP`` positions, each under ``jax.checkpoint``), which changes no
operation of the recurrence and keeps its backward pass to one state a
block, so that a float32 step of 4096 tokens fits beside its AdamW state.

Two things are as they are for the compiler's sake and change no number:
at ``highest`` the v5e's compiler takes some five seconds over every
product it meets (392 of them with every layer written out: 200 s a run,
which no cache of ours held), so each run of equal layers is one
``lax.scan`` over its slice of the kind's stack (``layer_runs``), and
products that share their left operand are one product with the weights
side by side (``beside``: a KDA layer's six input projections, an MLA
layer's three, a SwiGLU's gate and up), and a shared expert as wide as a
routed one goes through the held experts' scan (``experts``): every output
column is the same dot product as before, and the int8 control's scales are
a row's and a column's, which the neighbours do not move.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.reference.numerics import mm_highest

from . import counts

KDA_KEEP = 64       # positions between the states the backward pass keeps
NORM_TOPK_EPS = 1e-20


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w


def rope(x, theta):
    """x [S, H, Dh], positions 0..S-1, half rotation."""
    s, _, dh = x.shape
    half = dh // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def beside(z, weights, mm):
    """``[mm(z, w) for w in weights]`` as one product: the weights side by
    side, the result cut where they meet."""
    widths = [w.shape[1] for w in weights]
    cuts = [sum(widths[:i + 1]) for i in range(len(widths) - 1)]
    return jnp.split(mm(z, jnp.concatenate(weights, axis=1)), cuts, axis=-1)


def swiglu(z, w_gate, w_up, w_down, mm):
    gate, up = beside(z, [w_gate, w_up], mm)
    return mm(jax.nn.silu(gate) * up, w_down)


def causal_taps(x, taps):
    """x [S, C], taps [C, K]: y_t = sum_j taps[:, j] * x_{t-(K-1)+j}."""
    s, k = x.shape[0], taps.shape[1]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(padded[j:j + s] * taps[:, j] for j in range(k))


def l2_heads(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_state(q, k, v, log_alpha, beta):
    """The recurrence over positions. q, k, log_alpha [S, H, K], v
    [S, H, V], beta [S, H] -> o [S, H, V]."""
    def step(S, x):
        q, k, v, g, b = x
        S = jnp.exp(g)[..., None] * S
        u = b[..., None] * (v - jnp.sum(S * k[..., None], axis=-2))
        S = S + k[..., None] * u[..., None, :]
        return S, jnp.sum(S * q[..., None], axis=-2)

    @jax.checkpoint
    def block(S, xs):
        return lax.scan(step, S, xs)

    s, h, kd = q.shape
    pad = -s % KDA_KEEP
    xs = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
          for a in (q, k, v, log_alpha, beta)]      # beta 0: the state stays
    xs = [a.reshape((-1, KDA_KEEP) + a.shape[1:]) for a in xs]
    _, o = lax.scan(block, jnp.zeros((h, kd, v.shape[-1]), jnp.float32),
                    tuple(xs))
    return o.reshape((-1,) + o.shape[2:])[:s]


def kda(model: dict, lp: dict, z, mm):
    s, hd = z.shape[0], model["head_dim"]
    names = ("q", "k", "v", "a", "gate", "beta")
    proj = dict(zip(names, beside(z, [lp[f"kda_{n}"] for n in names], mm)))

    def mixed(name):
        y = causal_taps(proj[name], lp[f"kda_{name}_taps"])
        return jax.nn.silu(y).reshape(s, -1, hd)

    q = l2_heads(mixed("q")) * hd ** -0.5
    k, v = l2_heads(mixed("k")), mixed("v")
    a = (proj["a"] + lp["kda_dt_bias"]).reshape(s, -1, hd)
    log_alpha = model["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(lp["kda_a_log"])[:, None] * a)
    o = kda_state(q, k, v, log_alpha, jax.nn.sigmoid(proj["beta"]))
    o = rms_norm(o, lp["kda_o_norm"], model["rms_norm_eps"]).reshape(s, -1)
    return mm(o * jax.nn.sigmoid(proj["gate"]), lp["kda_out"])


def attention(q, k, v, mm):
    """Causal attention of one sequence, one head at a time. q, k
    [S, H, Dqk], v [S, H, Dv] -> [S, H, Dv]."""
    s, _, dqk = q.shape
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args
        sc = mm(qh, kh.T) * (dqk ** -0.5)
        return mm(jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1), vh)

    o = lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
    return o.transpose(1, 0, 2)


def mla(model: dict, lp: dict, z, mm):
    s = z.shape[0]
    nope, rank = model["qk_nope_head_dim"], model["kv_lora_rank"]
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    q, kv_a, gate = beside(z, [lp["mla_q"], lp["mla_kv_a"], lp["mla_gate"]],
                           mm)
    q = q.reshape(s, -1, counts.qk_dim(model))
    latent = rms_norm(kv_a[:, :rank], lp["mla_kv_norm"], eps)
    kv = mm(latent, lp["mla_kv_b"]).reshape(
        s, -1, nope + model["v_head_dim"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
    k_rope = rope(kv_a[:, None, rank:], theta)              # [S, 1, rope]
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(
            k_rope, (s, kv.shape[1], k_rope.shape[-1]))], -1)
    o = attention(q, k, kv[..., nope:], mm)
    gate = jax.nn.sigmoid(gate)                             # [S, H]
    return mm((o * gate[..., None]).reshape(s, -1), lp["mla_out"])


def router_scores(lp: dict, z, mm):
    """The router's sigmoid scores [S, E] of normed z [S, D]."""
    return jax.nn.sigmoid(mm(z, lp["router"]))


def choose(model: dict, select):
    """The experts [S, k] of ``select`` [S, E] (scores plus bias) by the
    group-limited choice."""
    s, e = select.shape
    groups = model["n_group"]
    if groups > 1:
        grouped = select.reshape(s, groups, e // groups)
        first = jnp.max(grouped, axis=-1, keepdims=True)
        at = jnp.argmax(grouped, axis=-1)[..., None]
        rest = jnp.where(jnp.arange(e // groups) == at, -jnp.inf, grouped)
        group_score = first[..., 0] + jnp.max(rest, axis=-1)
        _, kept = lax.top_k(group_score, model["topk_group"])
        keep = jnp.sum(jax.nn.one_hot(kept, groups, dtype=jnp.int32), axis=1)
        select = jnp.where(keep[..., None] > 0, grouped, -jnp.inf) \
            .reshape(s, e)
    return lax.top_k(select, model["num_experts_per_tok"])[1]


def routing(model: dict, lp: dict, z, mm):
    """(experts [S, k] of the router's published width, gates [S, k])."""
    scores = router_scores(lp, z, mm)
    select = scores + lax.stop_gradient(lp["expert_bias"]) \
        if model["moe_router_enable_expert_bias"] else scores
    experts = choose(model, select)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if model["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True)
                         + NORM_TOPK_EPS)
    return experts, gates * model["routed_scaling_factor"]


def _applied(z, chosen, held, stacks, mm):
    """Each expert of ``stacks`` (gate, up, down, stacked) applied to every
    token and weighed by ``chosen(expert id of held)`` [S], summed."""
    def one(out, x):
        expert, w_gate, w_up, w_down = x
        return out + chosen(expert)[:, None] * swiglu(z, w_gate, w_up,
                                                      w_down, mm), None

    return lax.scan(one, jnp.zeros_like(z), (held,) + stacks)[0]


def _held(model: dict, lp: dict):
    return (jnp.asarray(model["experts_held"], jnp.int32),
            (lp["e_gate"], lp["e_up"], lp["e_down"]))


def held_experts(model: dict, lp: dict, z, mm):
    """The part of the expert layer that the experts held here give:
    every one of them applied to every token, weighed by the token's gate
    for it, zero where the token did not choose it."""
    experts, gates = routing(model, lp, z, mm)
    return _applied(z, lambda e: jnp.sum(
        jnp.where(experts == e, gates, 0.0), axis=-1), *_held(model, lp), mm)


def shared_expert(lp: dict, z, mm):
    return swiglu(z, lp["s_gate"], lp["s_up"], lp["s_down"], mm)


def experts(model: dict, lp: dict, z, mm):
    """``shared_expert + held_experts``. Where the shared expert is as
    wide as a routed one it goes through the same scan as one expert more
    that every token chooses with a gate of 1 (id -1): the same products
    and the same sum, and three products fewer for the compiler to take
    its five seconds over, forward, made again and transposed."""
    held, stacks = _held(model, lp)
    shared = (lp["s_gate"], lp["s_up"], lp["s_down"])
    if any(s.shape != e.shape[1:] for s, e in zip(shared, stacks)):
        return shared_expert(lp, z, mm) + held_experts(model, lp, z, mm)
    experts, gates = routing(model, lp, z, mm)
    held = jnp.concatenate([held, jnp.full((1,), -1, jnp.int32)])
    stacks = tuple(jnp.concatenate([e, s[None]])
                   for e, s in zip(stacks, shared))
    return _applied(z, lambda e: jnp.where(e < 0, 1.0, jnp.sum(
        jnp.where(experts == e, gates, 0.0), axis=-1)), held, stacks, mm)


def operator(model: dict, kind: str, lp: dict, x, mm):
    """x plus the layer's sequence operator on its normed x."""
    eps = model["rms_norm_eps"]
    if kind.startswith("kda"):
        return x + kda(model, lp, rms_norm(x, lp["kda_norm"], eps), mm)
    return x + mla(model, lp, rms_norm(x, lp["mla_norm"], eps), mm)


def layer(model: dict, kind: str, lp: dict, x, mm):
    x = operator(model, kind, lp, x, mm)
    z = rms_norm(x, lp["mlp_norm"], model["rms_norm_eps"])
    if kind.endswith("dense"):
        return x + swiglu(z, lp["w_gate"], lp["w_up"], lp["w_down"], mm)
    return x + experts(model, lp, z, mm)


def layer_runs(model: dict) -> list:
    """(kind, start, count) of each run of equal layers in published order;
    ``start`` counts within the kind's own stack."""
    runs, seen = [], {}
    for kind in counts.kinds(model):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen.get(kind, 0), 1])
        seen[kind] = seen.get(kind, 0) + 1
    return [tuple(r) for r in runs]


def run_stack(params: dict, kind: str, start: int, count: int) -> dict:
    """The slice of ``kind``'s stack that one run of layers holds."""
    return jax.tree.map(lambda a: a[start:start + count],
                        params["layers"][kind])


def hidden(model: dict, params: dict, tokens, mm=mm_highest):
    """tokens [S] -> the final-normed hidden states [S, D]. ``model`` is
    the configuration as it is run; layer ``i`` of the published order is
    the next slice of its kind's stack, and a run of equal layers a scan
    over them."""
    x = params["embed"][tokens]
    for kind, start, count in layer_runs(model):
        one = jax.checkpoint(
            lambda x, lp, kind=kind: layer(model, kind, lp, x, mm))
        x, _ = lax.scan(lambda x, lp, one=one: (one(x, lp), None), x,
                        run_stack(params, kind, start, count))
    return rms_norm(x, params["final_norm"], model["rms_norm_eps"])


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
