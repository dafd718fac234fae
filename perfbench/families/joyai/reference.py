"""The plain reference: JoyAI-LLM-Flash's language model (every key of its
config is DeepSeek-V3's) in straightforward jax.numpy, with its
multi-token-prediction module and both losses.

Float32 throughout, every matmul through ``mm`` (``highest`` precision for
the reference, ``mm_int8`` for the control; ``perfbench/reference/
numerics.py``). No kernels, no cache, no sorting or grouping, no scan over
stacked layers: every layer is written out in a Python loop. It imports
nothing of the program. ``x`` is ``[S, D]``, ``H`` the heads, ``rms``
RMSNorm at ``rms_norm_eps``:

- every layer: ``h = x + op(rms(x))``, ``y = h + ffn(rms(h))``;
- ``op`` (latent attention, DeepSeek-V2, arXiv:2405.04434, section 2.1,
  with the query latent): ``c_q = rms(z W_qa)``, ``q = c_q W_qb`` ->
  ``[H, nope + rope]``; ``[c | k_rope] = z W_kva``, ``c <- rms(c)``;
  ``[k_nope_h | v_h] = c W_kvb``; the rotary embedding on q's rotary part
  and on ``k_rope``, which every head shares, **interleaved as published**
  (``rope_interleave``: the pair ``(2i, 2i + 1)`` turns by ``pos *
  theta ** (-2i / rope)``); causal softmax of ``q_h k_h^T (nope + rope) **
  -0.5`` over ``v_h``, one head at a time; ``W_o``. No gate, no bias;
- ``ffn`` of the ``first_k_dense_replace`` leading layers: SwiGLU;
- ``ffn`` of the others (DeepSeek-V3's ``noaux_tc`` router,
  arXiv:2412.19437, section 2.1.2, ``n_group`` 1: no group limit): ``s =
  sigmoid(z W_r)``; the experts of a token are the top
  ``num_experts_per_tok`` of ``s + bias``; their gates ``s`` at those
  experts over ``(their sum + 1e-20)`` times ``routed_scaling_factor``;
  ``ffn = sum over them of gate * SwiGLU_e(z) + SwiGLU_shared(z)``. Every
  expert held is applied to every token and masked by its gate;
- the next-token loss: one more RMSNorm after the last layer, the (untied)
  head, mean cross-entropy of ``targets``;
- **multi-token prediction, depth 1** (DeepSeek-V3, section 2.2): with
  ``h_i`` the last layer's output at position i **before** the final norm,
  ``h'_i = [rms_h(h_i) ; rms_e(Emb(targets_i))] W_eh``; ``g = Block(h')``,
  one more causal expert layer with weights, router, bias and shared
  expert of its own; ``logits_i = Head(rms_o(g_i))`` through the same table
  and head; the mean cross-entropy of ``targets_{i+1}`` over the ``S - 1``
  positions that have a token after next (only those positions are
  computed). ``loss = main + mtp_loss_weight * mtp``.

Departures, each the configuration's (its file states them): **the share**
(the router keeps its published width and experts a token; only the
experts of ``experts_held`` exist here, and what the absent ones would have
added is left out, in program and reference alike; attention, the shared
expert and the router are whole); **the sliced vocabulary** (ids, logits
and loss over the slice); **the bias** is a leaf no gradient reaches
(``weights.py`` balances it once at set-up; here it is given); **the
rotary columns' order** (``rotary_columns``): the tree both sides are
handed stores the 64 rotary columns of each head of ``W_qb`` and of
``W_kva`` in the program's half-rotation order (the published even columns,
then the odd ones), and ``published_columns`` puts them back before the
interleaved rope turns them, inside the differentiated function, so the
gradients come out in the tree's own order.

Each layer under ``jax.checkpoint`` and one head's scores at a time, so
that a float32 step of 4096 tokens fits beside its AdamW state. Products
that share their left operand are one product with the weights side by
side (``beside``), and a shared expert as wide as a routed one goes through
the held experts' scan as one expert more that every token takes with a
gate of 1: every output column is the same dot product as before (the v5e's
compiler takes its time over every product at ``highest``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.reference.numerics import mm_highest

from . import counts

NORM_TOPK_EPS = 1e-20


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w


def rope(x, theta):
    """x [S, H, Dh], positions 0..S-1, interleaved: the pair (2i, 2i + 1)
    turns by ``pos * theta ** (-2i / Dh)``."""
    s, _, dh = x.shape
    freqs = jnp.exp(-jnp.arange(dh // 2, dtype=jnp.float32)
                    * (math.log(theta) / (dh // 2)))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def half_rotation_order(width: int):
    """Where the program's half rotation keeps each published rotary
    column: the even ones, then the odd ones."""
    return jnp.concatenate([jnp.arange(0, width, 2),
                            jnp.arange(1, width, 2)])


def published_columns(model: dict, lp: dict) -> dict:
    """One layer's leaves with the rotary columns of ``W_qb`` (each head's
    last ``qk_rope_head_dim``) and of ``W_kva`` (its last) moved from the
    half-rotation order back to the published one."""
    rot, nope = model["qk_rope_head_dim"], model["qk_nope_head_dim"]
    back = jnp.argsort(half_rotation_order(rot))

    def restore(w, lead):
        """w [..., lead + rot] -> the same with its last ``rot`` put back."""
        return jnp.concatenate([w[..., :lead], w[..., lead:][..., back]], -1)

    q_b = lp["mla_q_b"]
    heads = q_b.reshape(q_b.shape[0], -1, nope + rot)
    return {**lp, "mla_q_b": restore(heads, nope).reshape(q_b.shape),
            "mla_kv_a": restore(lp["mla_kv_a"], model["kv_lora_rank"])}


def beside(z, weights, mm):
    """``[mm(z, w) for w in weights]`` as one product: the weights side by
    side, the result cut where they meet."""
    widths = [w.shape[1] for w in weights]
    cuts = [sum(widths[:i + 1]) for i in range(len(widths) - 1)]
    return jnp.split(mm(z, jnp.concatenate(weights, axis=1)), cuts, axis=-1)


def swiglu(z, w_gate, w_up, w_down, mm):
    gate, up = beside(z, [w_gate, w_up], mm)
    return mm(jax.nn.silu(gate) * up, w_down)


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
    """The operator on normed z [S, D]; ``lp``'s rotary columns in the
    published order."""
    s = z.shape[0]
    nope, rank = model["qk_nope_head_dim"], model["kv_lora_rank"]
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    q_a, kv_a = beside(z, [lp["mla_q_a"], lp["mla_kv_a"]], mm)
    q = mm(rms_norm(q_a, lp["mla_q_norm"], eps), lp["mla_q_b"])
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
    return mm(o.reshape(s, -1), lp["mla_out"])


def router_scores(lp: dict, z, mm):
    """The router's sigmoid scores [S, E] of normed z [S, D]."""
    return jax.nn.sigmoid(mm(z, lp["router"]))


def choose(model: dict, select):
    """The experts [S, k] of ``select`` [S, E] (scores plus bias): the
    top k, no group limit."""
    return lax.top_k(select, model["num_experts_per_tok"])[1]


def routing(model: dict, lp: dict, z, mm):
    """(experts [S, k] of the router's published width, gates [S, k])."""
    scores = router_scores(lp, z, mm)
    experts = choose(model, scores + lax.stop_gradient(lp["expert_bias"]))
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
    """``shared_expert + held_experts``: the shared expert, as wide as a
    routed one, goes through the same scan as one expert more that every
    token chooses with a gate of 1 (id -1): the same products and the same
    sum."""
    held, stacks = _held(model, lp)
    chosen, gates = routing(model, lp, z, mm)
    held = jnp.concatenate([held, jnp.full((1,), -1, jnp.int32)])
    stacks = tuple(jnp.concatenate([e, s[None]]) for e, s in zip(
        stacks, (lp["s_gate"], lp["s_up"], lp["s_down"])))
    return _applied(z, lambda e: jnp.where(e < 0, 1.0, jnp.sum(
        jnp.where(chosen == e, gates, 0.0), axis=-1)), held, stacks, mm)


def operator(model: dict, lp: dict, x, mm):
    """x plus latent attention on its normed x."""
    return x + mla(model, lp, rms_norm(x, lp["mla_norm"],
                                       model["rms_norm_eps"]), mm)


def layer(model: dict, kind: str, lp: dict, x, mm):
    x = operator(model, lp, x, mm)
    z = rms_norm(x, lp["mlp_norm"], model["rms_norm_eps"])
    if kind.endswith("dense"):
        return x + swiglu(z, lp["w_gate"], lp["w_up"], lp["w_down"], mm)
    return x + experts(model, lp, z, mm)


def layers_of(model: dict, params: dict) -> list:
    """(kind, that layer's leaves) of every layer in published order, the
    rotary columns put back where the tree keeps them in the program's
    order (``rotary_columns``: ``half_rotation``)."""
    seen, out = {}, []
    for kind in counts.kinds(model):
        at = seen.get(kind, 0)
        seen[kind] = at + 1
        out.append((kind, _one(model, params["layers"][kind], at)))
    return out


def _one(model: dict, stack: dict, at: int) -> dict:
    lp = jax.tree.map(lambda a: a[at], stack)
    if model.get("rotary_columns") == "half_rotation":
        return published_columns(model, lp)
    return lp


def module_layer(model: dict, params: dict) -> dict:
    """The multi-token-prediction module's layer's leaves, as ``layers_of``
    gives a layer's."""
    return _one(model, params["mtp"]["block"], 0)


def last_hidden(model: dict, params: dict, tokens, mm=mm_highest):
    """tokens [S] -> the last layer's output [S, D], before the final
    norm, each layer written out under ``jax.checkpoint``."""
    x = params["embed"][tokens]
    for kind, lp in layers_of(model, params):
        x = jax.checkpoint(
            lambda x, lp, kind=kind: layer(model, kind, lp, x, mm))(x, lp)
    return x


def _head(model: dict, params: dict):
    return params["embed"].T if model["tie_word_embeddings"] \
        else params["lm_head"]


def _nll(model: dict, params: dict, x, norm, targets, mm):
    """Cross-entropy [S] of ``targets`` from hidden states x [S, D] through
    ``norm`` and the head."""
    @jax.checkpoint
    def nll(x, norm, head):
        logp = jax.nn.log_softmax(
            mm(rms_norm(x, norm, model["rms_norm_eps"]), head), axis=-1)
        return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return nll(x, norm, _head(model, params))


def mtp_input(model: dict, params: dict, h, next_tokens, mm):
    """The module's joint projection: ``[rms_h(h) ; rms_e(Emb(next))]
    W_eh``, h [S, D] the last layer's output before the final norm."""
    m, eps = params["mtp"], model["rms_norm_eps"]
    joined = jnp.concatenate(
        [rms_norm(h, m["h_norm"][0], eps),
         rms_norm(params["embed"][next_tokens], m["e_norm"][0], eps)], -1)
    return mm(joined, m["proj"][0])


def sequence_losses(model: dict, params: dict, tokens, targets,
                    mm=mm_highest):
    """One sequence's (sum of the next-token cross-entropies over S, sum of
    the module's over the S - 1 positions that have a token after next)."""
    h = last_hidden(model, params, tokens, mm)
    main = jnp.sum(_nll(model, params, h, params["final_norm"], targets, mm))
    if not model["num_nextn_predict_layers"]:
        return main, jnp.zeros(())
    lp = module_layer(model, params)
    x = mtp_input(model, params, h[:-1], targets[:-1], mm)
    g = jax.checkpoint(
        lambda x, lp: layer(model, "mla_moe", lp, x, mm))(x, lp)
    return main, jnp.sum(_nll(model, params, g, params["mtp"]["out_norm"][0],
                              targets[1:], mm))


def losses(model: dict, params: dict, tokens, targets, mm=mm_highest):
    """(the mean next-token cross-entropy, the module's mean over the
    positions that have a token after next) of a batch tokens/targets
    [B, S], over the vocabulary's slice."""
    b, s = tokens.shape
    main, extra = lax.map(
        lambda a: sequence_losses(model, params, *a, mm), (tokens, targets))
    return jnp.sum(main) / (b * s), jnp.sum(extra) / (b * (s - 1))


def loss(model: dict, params: dict, tokens, targets, mm=mm_highest):
    """``main + mtp_loss_weight * mtp``."""
    main, extra = losses(model, params, tokens, targets, mm)
    if not model["num_nextn_predict_layers"]:
        return main
    return main + model["mtp_loss_weight"] * extra
