"""Flagship model: decoder-only transformer, TPU-first.

Design notes (vs the reference, which delegates all model math to torch —
SURVEY.md §2.6): everything here is built for the MXU and the Mesh:

- bfloat16 activations, f32 params/optimizer state; all FLOPs in batched
  einsums that tile onto the systolic array; static shapes throughout.
- Layers are **stacked** ([L, ...] leading axis) and run under ``lax.scan``
  → one compiled layer body regardless of depth, with optional
  ``jax.checkpoint`` rematerialisation for HBM.
- Two execution paths:
  1. ``forward`` / ``loss_fn``: GSPMD path — logical sharding constraints
     (ShardingRules) and jit; XLA inserts the dp/fsdp/tp collectives.
  2. ``make_spmd_train_step``: manual path — ``jax.shard_map`` over the
     full (dp, pp, tp, sp, ep) mesh with explicit collectives: Megatron
     column/row TP with psum, ring attention over sp, MoE all_to_all over
     ep, GPipe ppermute over pp, gradient psum-mean over dp. This is the
     multi-chip training step the driver dry-runs.

GQA attention with rotary embeddings, RMSNorm, SwiGLU MLP, optional MoE
layers every ``moe_every``-th layer.

**A layer pattern** (``forward``/``loss_fn``, the training body). A
configuration with ``layer_types`` or ``router_experts`` is a stack of
layers of several kinds: the sequence operator of a layer is causal
attention or a gated short convolution, its feed-forward the dense SwiGLU
(the ``num_dense_layers`` leading ones) or the routed experts as published
(``parallel/moe.py``: sigmoid or softmax scores, top-k over scores plus a
bias, renormalised gates, no token dropped, and only the experts this
chip holds computed). The parameters are stacked per kind
(``params["layers"][kind]``), and each run of equal layers in published
order is one ``lax.scan`` (``layer_runs``). The cached serving bodies
run one kind of layer and refuse such a configuration.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.mesh import mesh_shape
from ray_tpu.parallel import moe
from ray_tpu.parallel.moe import moe_dispatch_combine
from ray_tpu.parallel.ring_attention import ring_attention
from ray_tpu.parallel.sharding import ShardingRules


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 1376
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    # MoE: 0 = dense; otherwise every `moe_every`-th layer is MoE.
    num_experts: int = 0
    moe_every: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    remat: bool = False
    # The width of a head; None: d_model // n_heads.
    head_dim: Optional[int] = None
    norm_eps: float = 1e-6
    # RMSNorm over each head of q and of k (own weights), before rope.
    qk_norm: bool = False
    # The head is the embedding table, transposed; no ``lm_head`` leaf.
    tie_embeddings: bool = False
    # The layer pattern (training body only). ``layer_types``: the
    # sequence operator of each layer, "attention" or "conv" (None: all
    # attention); a conv layer's causal depthwise kernel has
    # ``conv_kernel`` taps.
    layer_types: Optional[Tuple[str, ...]] = None
    conv_kernel: int = 3
    # Routed experts as published, in every layer after the
    # ``num_dense_layers`` leading ones (0 experts: every layer dense).
    # ``router_experts`` is the router's width, ``experts_held`` which of
    # them live on this chip (None: all); a share computes its own
    # experts' part of the layer and nothing stands in for the rest.
    router_experts: int = 0
    num_dense_layers: int = 0
    experts_held: Optional[Tuple[int, ...]] = None
    experts_per_token: int = 1
    moe_d_ff: Optional[int] = None          # an expert's width; None: d_ff
    router_score: str = "softmax"           # or "sigmoid"
    norm_topk: bool = False                 # gates renormalised over the k
    routed_scale: float = 1.0
    expert_bias: bool = False               # added to the scores to select

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            if (len(self.layer_types) != self.n_layers
                    or set(self.layer_types) - {ATTENTION, CONV}):
                raise ValueError(
                    f"layer_types {self.layer_types}: one of {ATTENTION!r}, "
                    f"{CONV!r} for each of the {self.n_layers} layers")
        if self.router_experts:
            if self.num_experts:
                raise ValueError("router_experts (top-k, dropless) and "
                                 "num_experts (top-1, capacity) exclude "
                                 "each other")
            held = tuple(range(self.router_experts)
                         if self.experts_held is None else self.experts_held)
            if (not held or len(set(held)) != len(held)
                    or not set(held) <= set(range(self.router_experts))):
                raise ValueError(f"experts_held {held}: distinct experts of "
                                 f"the router's {self.router_experts}")
            object.__setattr__(self, "experts_held", held)
            if self.router_score not in ("softmax", "sigmoid"):
                raise ValueError(f"router_score {self.router_score!r}")
            if not 1 <= self.experts_per_token <= self.router_experts:
                raise ValueError("experts_per_token out of the router's range")

    @property
    def patterned(self) -> bool:
        """Layers of several kinds: parameters stacked per kind."""
        return self.layer_types is not None or self.router_experts > 0


ATTENTION, CONV = "attention", "conv"
DENSE, MOE = "dense", "moe"


def layer_kind(cfg: TransformerConfig, i: int) -> str:
    """The kind of layer ``i``, ``<operator>_<feed-forward>``."""
    op = cfg.layer_types[i] if cfg.layer_types is not None else ATTENTION
    ffn = MOE if cfg.router_experts and i >= cfg.num_dense_layers else DENSE
    return f"{op}_{ffn}"


def layer_runs(cfg: TransformerConfig) -> Tuple[Tuple[str, int, int], ...]:
    """(kind, start, count) of each run of equal layers in published
    order; ``start`` counts within the kind's own stack."""
    runs, seen = [], {}
    for i in range(cfg.n_layers):
        kind = layer_kind(cfg, i)
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen.get(kind, 0), 1])
        seen[kind] = seen.get(kind, 0) + 1
    return tuple(tuple(r) for r in runs)


def _kind_leaves(cfg: TransformerConfig, kind: str) -> Dict[str, tuple]:
    """name -> (shape, fan_in, PartitionSpec roles) of one layer of
    ``kind``; fan_in None: ones (a norm), 0: zeros (the bias)."""
    D, Hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_heads * Hd, cfg.n_kv_heads * Hd
    op, ffn = kind.split("_")
    if op == ATTENTION:
        leaves = {"attn_norm": ((D,), None, (None,)),
                  "wq": ((D, nq), D, ("fsdp", "tp")),
                  "wk": ((D, nkv), D, ("fsdp", "tp")),
                  "wv": ((D, nkv), D, ("fsdp", "tp")),
                  "wo": ((nq, D), nq, ("tp", "fsdp"))}
        if cfg.qk_norm:
            leaves.update(q_norm=((Hd,), None, (None,)),
                          k_norm=((Hd,), None, (None,)))
    else:
        K = cfg.conv_kernel
        leaves = {"conv_norm": ((D,), None, (None,)),
                  "conv_in": ((D, 3 * D), D, ("fsdp", "tp")),
                  "conv_taps": ((D, K), K, (None, None)),
                  "conv_out": ((D, D), D, ("tp", "fsdp"))}
    leaves["mlp_norm"] = ((D,), None, (None,))
    if ffn == DENSE:
        F = cfg.d_ff
        leaves.update(w_gate=((D, F), D, ("fsdp", "tp")),
                      w_up=((D, F), D, ("fsdp", "tp")),
                      w_down=((F, D), F, ("tp", "fsdp")))
    else:
        F, E = cfg.moe_d_ff or cfg.d_ff, len(cfg.experts_held)
        leaves.update(router=((D, cfg.router_experts), D, (None, None)),
                      e_gate=((E, D, F), D, ("expert", None, "tp")),
                      e_up=((E, D, F), D, ("expert", None, "tp")),
                      e_down=((E, F, D), F, ("expert", "tp", None)))
        if cfg.expert_bias:
            leaves["expert_bias"] = ((cfg.router_experts,), 0, (None,))
    return leaves


def _kind_counts(cfg: TransformerConfig) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for kind, _start, count in layer_runs(cfg):
        counts[kind] = counts.get(kind, 0) + count
    return counts


def _dense_init(key, shape, fan_in):
    return (jax.random.normal(key, shape, jnp.float32)
            * (1.0 / math.sqrt(fan_in)))


def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    """Stacked-layer param pytree. Weights f32 (master copy). A
    patterned configuration stacks per kind: ``layers[kind][leaf]``."""
    if cfg.patterned:
        return _init_pattern_params(cfg, key)
    D, F, Hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    nq, nkv, L = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers
    # One distinct key per weight family: same-shaped families (wq/wk/wv,
    # w_gate/w_up, e_gate/e_up) must not share init, or attention/MLP
    # branches start out identical and training silently degrades.
    ks = jax.random.split(key, 16)
    _next_family = iter(range(2, 16))

    def stack(initfn):
        keys = jax.random.split(ks[next(_next_family)], L)
        return jax.vmap(initfn)(keys)

    layers = {
        "attn_norm": jnp.ones((L, D), jnp.float32),
        "wq": stack(lambda k: _dense_init(k, (D, nq * Hd), D)),
        "wk": stack(lambda k: _dense_init(k, (D, nkv * Hd), D)),
        "wv": stack(lambda k: _dense_init(k, (D, nkv * Hd), D)),
        "wo": stack(lambda k: _dense_init(k, (nq * Hd, D), nq * Hd)),
        "mlp_norm": jnp.ones((L, D), jnp.float32),
        "w_gate": stack(lambda k: _dense_init(k, (D, F), D)),
        "w_up": stack(lambda k: _dense_init(k, (D, F), D)),
        "w_down": stack(lambda k: _dense_init(k, (F, D), F)),
    }
    if cfg.num_experts:
        E = cfg.num_experts
        layers["router"] = stack(lambda k: _dense_init(k, (D, E), D))
        layers["e_gate"] = stack(
            lambda k: _dense_init(k, (E, D, F), D))
        layers["e_up"] = stack(lambda k: _dense_init(k, (E, D, F), D))
        layers["e_down"] = stack(lambda k: _dense_init(k, (E, F, D), F))
    params = {
        "embed": jax.random.normal(ks[0], (cfg.vocab_size, D),
                                   jnp.float32) * 0.02,
        "layers": layers,
        "final_norm": jnp.ones((D,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(ks[1], (D, cfg.vocab_size), D)
    return params


def _init_pattern_params(cfg: TransformerConfig, key: jax.Array
                         ) -> Dict[str, Any]:
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    layers = {}
    for ki, (kind, n) in enumerate(sorted(_kind_counts(cfg).items())):
        k_kind = jax.random.fold_in(k_layers, ki)
        layers[kind] = {}
        for li, (name, (shape, fan_in, _roles)) in enumerate(
                _kind_leaves(cfg, kind).items()):
            if fan_in:
                layers[kind][name] = _dense_init(
                    jax.random.fold_in(k_kind, li), (n,) + shape, fan_in)
            else:
                fill = jnp.ones if fan_in is None else jnp.zeros
                layers[kind][name] = fill((n,) + shape, jnp.float32)
    params = {
        "embed": jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model),
                                   jnp.float32) * 0.02,
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(
            k_head, (cfg.d_model, cfg.vocab_size), cfg.d_model)
    return params


def param_specs(cfg: TransformerConfig,
                rules: Optional[ShardingRules] = None) -> Dict[str, Any]:
    """PartitionSpec tree matching init_params (GSPMD path).

    Layer weights carry a leading stacked-layer axis: sharded on pp when a
    pipeline mesh is used (stages = contiguous layer blocks), else None.
    2D weights shard wide-axis on tp, narrow on fsdp (ZeRO-3).
    """
    r = rules or ShardingRules()
    st, tp, fs = r.stage, r.mlp, r.fsdp_shard
    if cfg.patterned:
        # A kind's stack is no contiguous block of layers: no stage axis.
        role = {"tp": tp, "fsdp": fs, "expert": r.expert, None: None}
        specs = {
            "embed": P(r.vocab, None),
            "layers": {kind: {name: P(None, *(role[x] for x in roles))
                              for name, (_s, _f, roles)
                              in _kind_leaves(cfg, kind).items()}
                       for kind in _kind_counts(cfg)},
            "final_norm": P(None),
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = P(fs, r.vocab)
        return specs
    layers = {
        "attn_norm": P(st, None),
        "wq": P(st, fs, tp), "wk": P(st, fs, tp), "wv": P(st, fs, tp),
        "wo": P(st, tp, fs),
        "mlp_norm": P(st, None),
        "w_gate": P(st, fs, tp), "w_up": P(st, fs, tp),
        "w_down": P(st, tp, fs),
    }
    if cfg.num_experts:
        layers.update({
            "router": P(st, None, None),
            "e_gate": P(st, r.expert, None, tp),
            "e_up": P(st, r.expert, None, tp),
            "e_down": P(st, r.expert, tp, None),
        })
    specs = {
        "embed": P(r.vocab, None),
        "layers": layers,
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(fs, r.vocab)
    return specs


@jax.named_scope("norm")
def rms_norm(x, w, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


@jax.named_scope("rope")
def rope(x, positions, theta):
    # x: [B, S, H, Dh]; rotate pairs (even, odd halves).
    Dh = x.shape[-1]
    half = Dh // 2
    freqs = jnp.exp(
        -jnp.arange(0, half, dtype=jnp.float32) * (math.log(theta) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B?, S, half]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1)


@jax.named_scope("seg.attn_core")
def _attention_dense(q, k, v, causal=True, grad=True):
    """q [B,S,Hq,Dh], k/v [B,S,Hkv,Dh] -> [B,S,Hq,Dh].

    Where ``ops.flash_attention.use_flash`` accepts the shape (never on
    the CPU backend) this dispatches to the Pallas flash
    kernel (ops/flash_attention.py, differentiable via its blockwise
    custom_vjp) — the [S, S] score matrix never hits HBM, which is what
    unlocks long sequences and large batches under grad. The kernel's
    FA2 backward wants matched head counts, so GQA repeat-expands K/V
    only on the differentiable (``grad=True``, training) path;
    inference callers pass ``grad=False`` and take the GROUPED flash
    forward (``flash_attention_grouped``), whose K/V block specs
    index-map each query head to its kv group — no n_heads-wide K/V
    exists anywhere on the serving path. The dense einsum path keeps
    GQA GROUPED too: queries fold to [B, S, Hkv, group, Dh] and
    contract against K/V at n_kv_heads width (the same grouped form the
    paged decode cache relies on).
    """
    from ray_tpu.ops.flash_attention import (
        flash_attention,
        flash_attention_grouped,
        use_flash,
    )

    B, S, Hq, Dh = q.shape
    Hkv = k.shape[2]
    if use_flash(S, S, Dh, q.dtype):
        if Hq != Hkv and not grad:
            o = flash_attention_grouped(q.transpose(0, 2, 1, 3),
                                        k.transpose(0, 2, 1, 3),
                                        v.transpose(0, 2, 1, 3),
                                        causal=causal)
            return o.transpose(0, 2, 1, 3)
        if Hq != Hkv:
            k = jnp.repeat(k, Hq // Hkv, axis=2)
            v = jnp.repeat(v, Hq // Hkv, axis=2)
        o = flash_attention(q.transpose(0, 2, 1, 3),
                            k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), causal=causal)
        return o.transpose(0, 2, 1, 3)
    group = Hq // Hkv
    qg = q.reshape(B, S, Hkv, group, Dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * (Dh ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return o.reshape(B, S, Hq, Dh)


@jax.named_scope("seg.embed")
def _embed(cfg, params, tokens):
    return params["embed"].astype(cfg.dtype)[tokens]


@jax.named_scope("seg.attn_proj")
def _project_qkv(cfg, lp, x, positions):
    """Attention norm, q/k/v projection + rope, shared by the training
    layer body and the cached prefill/decode paths. x [B, S, D] ->
    q [B,S,Hq,Dh], k/v [B,S,Hkv,Dh] (k/v at n_kv_heads width)."""
    dt = cfg.dtype
    B, S, _ = x.shape
    Hd = cfg.head_dim
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = (h @ lp["wq"].astype(dt)).reshape(B, S, -1, Hd)
    k = (h @ lp["wk"].astype(dt)).reshape(B, S, -1, Hd)
    v = (h @ lp["wv"].astype(dt)).reshape(B, S, -1, Hd)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


@jax.named_scope("seg.attn_proj")
def _attn_out(cfg, lp, x, o, tp_axis=None):
    """Output projection of the attention heads o [B, S, Hq, Dh] and the
    residual add onto x [B, S, D]."""
    B, S, _ = x.shape
    o = o.reshape(B, S, -1) @ lp["wo"].astype(cfg.dtype)
    if tp_axis is not None:
        o = lax.psum(o, tp_axis)  # row-parallel output proj
    return x + o


def _layer_fn(cfg: TransformerConfig, lp: Dict[str, jax.Array], x: jax.Array,
              positions: jax.Array, layer_idx: jax.Array,
              sp_axis: Optional[str] = None,
              ep_axis: Optional[str] = None,
              tp_axis: Optional[str] = None) -> jax.Array:
    """One transformer block. In manual mode the weights arriving here are
    the local TP shard (wide axis pre-sliced) and attention/MoE take the
    collective axes to use; in GSPMD mode all axes are None."""
    q, k, v = _project_qkv(cfg, lp, x, positions)
    if sp_axis is not None:
        with jax.named_scope("seg.attn_core"):
            Hq, Hkv = q.shape[2], k.shape[2]
            if Hq != Hkv:
                k = jnp.repeat(k, Hq // Hkv, axis=2)
                v = jnp.repeat(v, Hq // Hkv, axis=2)
            o = ring_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), axis_name=sp_axis, causal=True,
            ).transpose(0, 2, 1, 3)
    else:
        o = _attention_dense(q, k, v)
    x = _attn_out(cfg, lp, x, o, tp_axis)
    return _mlp_residual(cfg, lp, x, layer_idx,
                         tp_axis=tp_axis, ep_axis=ep_axis)


@jax.named_scope("seg.mlp")
def _mlp_residual(cfg, lp, x, layer_idx, tp_axis=None, ep_axis=None):
    """MLP norm, ``_mlp_block`` and the residual add, x [B, S, D]."""
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    return x + _mlp_block(cfg, lp, h, layer_idx,
                          tp_axis=tp_axis, ep_axis=ep_axis)


def _mlp_block(cfg: TransformerConfig, lp: Dict[str, jax.Array],
               h: jax.Array, layer_idx: jax.Array,
               tp_axis: Optional[str] = None,
               ep_axis: Optional[str] = None) -> jax.Array:
    """Post-norm MLP/MoE for one layer over ``h`` [B, S, D] — shared
    between the training layer body and the decode path (where S == 1)."""
    dt = cfg.dtype
    B, S, D = h.shape
    if cfg.num_experts and "router" in lp:
        is_moe = (layer_idx % cfg.moe_every) == (cfg.moe_every - 1)
        logits = (h.astype(jnp.float32)
                  @ lp["router"].astype(jnp.float32)).reshape(
            B * S, cfg.num_experts)

        def expert_fn(tok):  # [E_local, C, D]
            g = jnp.einsum("ecd,edf->ecf", tok, lp["e_gate"].astype(dt))
            u = jnp.einsum("ecd,edf->ecf", tok, lp["e_up"].astype(dt))
            out = jnp.einsum(
                "ecf,efd->ecd", jax.nn.silu(g) * u, lp["e_down"].astype(dt))
            if tp_axis is not None:
                out = lax.psum(out, tp_axis)  # row-parallel e_down
            return out

        if ep_axis is not None:
            moe_out = moe_dispatch_combine(
                h.reshape(B * S, D), logits, expert_fn,
                num_experts=cfg.num_experts,
                capacity_factor=cfg.capacity_factor,
                axis_name=ep_axis).reshape(B, S, D)
        else:
            # Dense fallback: run all experts, weight by top-1 gate.
            probs = jax.nn.softmax(logits, axis=-1)
            top = jnp.argmax(probs, axis=-1)
            gate = probs[jnp.arange(B * S), top].astype(dt)
            toks = jnp.broadcast_to(
                h.reshape(1, B * S, D), (cfg.num_experts, B * S, D))
            outs = expert_fn(toks)
            moe_out = (outs[top, jnp.arange(B * S)]
                       * gate[:, None]).reshape(B, S, D)
        if cfg.moe_every == 1:
            return moe_out  # all layers MoE: skip the dense branch
        dense_out = _swiglu(cfg, lp, h, tp_axis)
        return jnp.where(is_moe, moe_out, dense_out)
    return _swiglu(cfg, lp, h, tp_axis)


def _swiglu(cfg, lp, h, tp_axis):
    dt = cfg.dtype
    g = h @ lp["w_gate"].astype(dt)
    u = h @ lp["w_up"].astype(dt)
    out = (jax.nn.silu(g) * u) @ lp["w_down"].astype(dt)
    if tp_axis is not None:
        out = lax.psum(out, tp_axis)  # row-parallel down proj
    return out


@jax.named_scope("seg.conv")
def _conv_residual(cfg, lp, x):
    """The gated short convolution as a layer's sequence operator, with
    its norm and residual add, x [B, S, D]: ``[b, c, u] = split3(z W_in)``,
    a causal depthwise convolution of ``b * u`` over ``conv_kernel`` taps
    (zeros before the sequence, no bias), gated by ``c``, then ``W_out``.
    No activation function. Three taps are three shifted multiply-adds."""
    dt = cfg.dtype
    S, K = x.shape[1], cfg.conv_kernel
    z = rms_norm(x, lp["conv_norm"], cfg.norm_eps)
    b, c, u = jnp.split(z @ lp["conv_in"].astype(dt), 3, axis=-1)
    v = jnp.pad(b * u, ((0, 0), (K - 1, 0), (0, 0)))
    taps = lp["conv_taps"].astype(dt)                       # [D, K]
    y = sum(v[:, j:j + S] * taps[:, j] for j in range(K))   # tap K-1: now
    return x + (c * y) @ lp["conv_out"].astype(dt)


def _moe_residual(cfg, lp, x):
    """The routed experts as a layer's feed-forward, with its norm and
    residual add, x [B, S, D]: this chip's experts' part of the layer
    (``parallel/moe.py``), and the tokens each held expert got. Two
    segments, so not under ``seg.mlp``."""
    B, S, D = x.shape
    with jax.named_scope("seg.moe_route"):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps).reshape(B * S, D)
        routing = moe.route(
            h, lp["router"], lp.get("expert_bias"),
            experts_held=cfg.experts_held, k=cfg.experts_per_token,
            score=cfg.router_score, norm_topk=cfg.norm_topk,
            scale=cfg.routed_scale)
    with jax.named_scope("seg.moe_experts"):
        dt = cfg.dtype
        out = moe.held_experts(
            h, routing, lp["e_gate"].astype(dt), lp["e_up"].astype(dt),
            lp["e_down"].astype(dt))
        return x + out.reshape(B, S, D), routing.group_sizes


def _pattern_layer(cfg: TransformerConfig, kind: str, lp, x, positions):
    """One layer of ``kind`` (``layer_kind``) of a patterned stack ->
    (x, the tokens each held expert got; None for a dense layer)."""
    op, ffn = kind.split("_")
    if op == CONV:
        x = _conv_residual(cfg, lp, x)
    else:
        q, k, v = _project_qkv(cfg, lp, x, positions)
        x = _attn_out(cfg, lp, x, _attention_dense(q, k, v))
    if ffn == MOE:
        return _moe_residual(cfg, lp, x)
    return _mlp_residual(cfg, lp, x, 0), None


def _pattern_layers(cfg: TransformerConfig, layers, x, positions, constrain):
    """The stack of a patterned configuration: one ``lax.scan`` for each
    run of equal layers, over that run's slice of its kind's stack."""
    counts = _kind_counts(cfg)
    for kind, start, count in layer_runs(cfg):
        stack = layers[kind]
        if count != counts[kind]:
            stack = jax.tree.map(lambda a: a[start:start + count], stack)

        def body(x, lp, kind=kind):
            run = partial(_pattern_layer, cfg, kind, lp,
                          positions=positions)
            x, _load = jax.checkpoint(run)(x) if cfg.remat else run(x)
            return constrain(x, "batch", "sequence", "embed"), None

        x, _ = lax.scan(body, x, stack)
    return x


def moe_load(cfg: TransformerConfig, params: Dict[str, Any],
             tokens: jax.Array) -> Dict[str, jax.Array]:
    """Tokens routed to each held expert in each expert layer of a forward
    pass over ``tokens`` [B, S], from the layers' own routing:
    ``{kind: int32 [layers of that kind, experts held]}``."""
    B, S = tokens.shape
    load: Dict[str, list] = {}
    x = _embed(cfg, params, tokens)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    for kind, start, count in layer_runs(cfg):
        for at in range(start, start + count):
            lp = jax.tree.map(lambda a: a[at], params["layers"][kind])
            x, sizes = _pattern_layer(cfg, kind, lp, x, positions)
            if sizes is not None:
                load.setdefault(kind, []).append(sizes)
    return {kind: jnp.stack(sizes) for kind, sizes in load.items()}


def _refuse_pattern(cfg: TransformerConfig, body: str) -> None:
    """The cached serving bodies run one kind of layer: a conv layer's
    state has no place in the paged cache yet, and an expert layer that
    holds a share gives a partial result. A wrong answer is worse than
    none."""
    if cfg.patterned:
        raise NotImplementedError(
            f"{body} runs attention layers with one feed-forward kind; "
            f"this configuration has a layer pattern "
            f"({[k for k, _s, _n in layer_runs(cfg)]}), which only "
            f"forward/loss_fn run")


def forward(cfg: TransformerConfig, params: Dict[str, Any],
            tokens: jax.Array,
            mesh: Optional[Mesh] = None,
            rules: Optional[ShardingRules] = None) -> jax.Array:
    """GSPMD path: tokens [B, S] -> logits [B, S, V]. Layers via lax.scan."""
    r = rules or ShardingRules()

    def constrain(x, *logical):
        if mesh is None:
            return x
        return lax.with_sharding_constraint(
            x, NamedSharding(mesh, r.spec(*logical)))

    B, S = tokens.shape
    x = _embed(cfg, params, tokens)
    x = constrain(x, "batch", "sequence", "embed")
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    if cfg.patterned:
        x = _pattern_layers(cfg, params["layers"], x, positions, constrain)
        return constrain(_final_logits(cfg, params, x),
                         "batch", "sequence", "vocab")

    def body(carry, lp_with_idx):
        x = carry
        lp, idx = lp_with_idx

        def run(x):
            return _layer_fn(cfg, lp, x, positions, idx)

        x = jax.checkpoint(run)(x) if cfg.remat else run(x)
        x = constrain(x, "batch", "sequence", "embed")
        return x, None

    idxs = jnp.arange(cfg.n_layers)
    x, _ = lax.scan(body, x, (params["layers"], idxs))
    return constrain(_final_logits(cfg, params, x),
                     "batch", "sequence", "vocab")


@jax.named_scope("seg.head_loss")
def _lm_head(cfg, params, x):
    """f32 logits of final-normed hidden states."""
    if cfg.tie_embeddings:
        # One table, two uses: the gradient reaches it from both.
        return (x @ params["embed"].astype(cfg.dtype).T).astype(jnp.float32)
    return (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)


@jax.named_scope("seg.head_loss")
def _final_logits(cfg, params, x):
    return _lm_head(cfg, params,
                    rms_norm(x, params["final_norm"], cfg.norm_eps))


@jax.named_scope("seg.head_loss")
def _next_token_nll(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def loss_fn(cfg: TransformerConfig, params, tokens, targets,
            mesh=None, rules=None) -> jax.Array:
    return _next_token_nll(forward(cfg, params, tokens, mesh, rules),
                           targets)


# ---------------------------------------------------------------------------
# Manual SPMD training step: shard_map over (dp, pp, tp, sp, ep).
# ---------------------------------------------------------------------------

def _stage_params_spec(cfg: TransformerConfig) -> Dict[str, P]:
    """in_specs for the stacked layer tree inside shard_map: leading layer
    axis sharded over pp, wide weight axes over tp, experts over ep."""
    sp = {
        "attn_norm": P("pp", None),
        "wq": P("pp", None, "tp"), "wk": P("pp", None, "tp"),
        "wv": P("pp", None, "tp"), "wo": P("pp", "tp", None),
        "mlp_norm": P("pp", None),
        "w_gate": P("pp", None, "tp"), "w_up": P("pp", None, "tp"),
        "w_down": P("pp", "tp", None),
    }
    if cfg.num_experts:
        sp.update({
            "router": P("pp", None, None),
            "e_gate": P("pp", "ep", None, "tp"),
            "e_up": P("pp", "ep", None, "tp"),
            "e_down": P("pp", "ep", "tp", None),
        })
    return sp


def make_spmd_train_step(cfg: TransformerConfig, mesh: Mesh, params,
                         optimizer=None, n_microbatches: int = 2):
    """Build the manual multi-chip training step.

    Returns ``(step, pspec, ospec)`` where ``step(params, opt_state,
    tokens, targets) -> (params, opt_state, loss)`` is a jitted
    ``shard_map`` over the full mesh with explicit collectives on every
    axis, and pspec/ospec are the PartitionSpec trees for params and
    optimizer state (``params`` is only shape-inspected — pass real or
    ``jax.eval_shape`` abstract values).

    Requires cfg.n_layers % pp == 0, heads % tp == 0, batch % (dp*mb) == 0,
    seq % sp == 0, experts % ep == 0 (when MoE).
    """
    import optax

    _refuse_pattern(cfg, "make_spmd_train_step")
    if optimizer is None:
        optimizer = optax.adamw(3e-4)
    shape = mesh_shape(mesh)
    pp, tp, sp_n, ep_n = shape["pp"], shape["tp"], shape["sp"], shape["ep"]
    if cfg.n_layers % pp:
        raise ValueError(f"n_layers {cfg.n_layers} % pp {pp} != 0")
    if cfg.n_heads % tp or cfg.n_kv_heads % tp:
        raise ValueError("heads must divide tp")
    if cfg.num_experts and cfg.num_experts % ep_n:
        raise ValueError("experts must divide ep")
    layers_per_stage = cfg.n_layers // pp

    lp_spec = _stage_params_spec(cfg)
    pspec = {
        "embed": P(None, None),
        "layers": lp_spec,
        "final_norm": P(None),
        "lm_head": P(None, None),
    }
    data_spec = P(("dp", "fsdp"), "sp")

    sp_axis = "sp" if sp_n > 1 else None
    ep_axis = "ep" if ep_n > 1 else None
    tp_axis = "tp" if tp > 1 else None

    def stage_fn(stage_layers, act, stage_idx):
        """Run this pp-shard's layers_per_stage layers over activation
        bucket act = (x, positions)."""
        x, positions = act

        def body(carry, lp_i):
            lp, local_i = lp_i
            gidx = stage_idx * layers_per_stage + local_i

            def run(x):
                return _layer_fn(cfg, lp, x, positions, gidx,
                                 sp_axis=sp_axis, ep_axis=ep_axis,
                                 tp_axis=tp_axis)

            x = jax.checkpoint(run)(carry) if cfg.remat else run(carry)
            return x, None

        x, _ = lax.scan(
            body, x, (stage_layers, jnp.arange(layers_per_stage)))
        return x, positions

    def local_loss(params, tokens, targets):
        """Per-shard loss: tokens [B_local, S_local] (dp×sp sharded)."""
        B, S = tokens.shape
        stage = lax.axis_index("pp")
        x = _embed(cfg, params, tokens)
        s_idx = lax.axis_index("sp") if sp_n > 1 else 0
        positions = jnp.broadcast_to(
            jnp.arange(S) + s_idx * S, (B, S))

        if pp > 1:
            from ray_tpu.parallel.pipeline import pipeline_spmd
            mb = n_microbatches
            if B % mb:
                raise ValueError(f"local batch {B} % microbatches {mb}")
            xs = x.reshape(mb, B // mb, S, -1)
            pos_mb = jnp.broadcast_to(positions[: B // mb], xs.shape[:3])
            out, _ = pipeline_spmd(
                lambda lp, act: stage_fn(lp, act, lax.axis_index("pp")),
                params["layers"], (xs, pos_mb), axis_name="pp")
            x = out.reshape(B, S, -1)
        else:
            x, _ = stage_fn(params["layers"], (x, positions),
                            jnp.zeros((), jnp.int32))

        return _next_token_nll(_final_logits(cfg, params, x), targets)

    from ray_tpu.parallel.mesh import AXES

    n_total = math.prod(shape[a] for a in AXES)

    def _sync_grads(grads):
        """Per-leaf gradient sync. Inside shard_map, jax.grad returns on
        each shard d(sum of every shard's local_loss)/d(local leaf). Since
        local_loss is the local-token mean (distinct across dp/fsdp/sp,
        replicated as a function across tp/pp/ep), the global-mean gradient
        of a leaf sharded over axes S is psum over the complement of S,
        scaled by 1/N_devices — one rule covers replicated and sharded
        leaves alike."""
        flat_g, treedef = jax.tree.flatten(grads)
        flat_s = jax.tree.leaves(pspec, is_leaf=lambda x: isinstance(x, P))
        out = []
        for g, s in zip(flat_g, flat_s):
            sharded = set()
            for part in s:
                if part is None:
                    continue
                for ax in (part if isinstance(part, tuple) else (part,)):
                    sharded.add(ax)
            repl = tuple(a for a in AXES if a not in sharded)
            out.append(lax.psum(g, repl) / n_total)
        return jax.tree.unflatten(treedef, out)

    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(local_loss)(params, tokens, targets)
        grads = _sync_grads(grads)
        loss = lax.pmean(loss, ("dp", "fsdp", "sp"))
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    # Optimizer-state sharding: optax states embed whole param-shaped
    # subtrees (mu/nu — must carry the params' specs) plus scalar leaves
    # (counts — replicate). Substitute pspec wherever a subtree's treedef
    # matches the params' treedef; shape-matching would be unsound (wq/wo
    # share a global shape but transpose their tp axis).
    params_treedef = jax.tree.structure(params)

    def _is_param_tree(x):
        try:
            return jax.tree.structure(x) == params_treedef
        except Exception:
            return False

    opt_shapes = jax.eval_shape(optimizer.init, params)
    ospec = jax.tree.map(
        lambda sub: pspec if _is_param_tree(sub) else P(),
        opt_shapes, is_leaf=_is_param_tree)

    step_sm = jax.shard_map(
        step, mesh=mesh,
        in_specs=(pspec, ospec, data_spec, data_spec),
        out_specs=(pspec, ospec, P()),
        check_vma=False)
    return jax.jit(step_sm), pspec, ospec


def shard_params_for_step(params, mesh, pspec):
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, pspec)


# ---------------------------------------------------------------------------
# Inference path: paged KV cache + prefill / chunked prefill / decode.
#
# The training path above is cacheless (recomputes all K/V every call);
# serving needs the Orca/vLLM shape — K/V of every processed token persists
# in fixed-size blocks of preallocated HBM arrays, indexed per sequence
# through a block table, so the continuous-batching engine
# (ray_tpu/llm/) admits/evicts sequences by moving integers, never bytes.
# GQA indexes the cache at n_kv_heads width throughout (grouped queries —
# see ops/paged_attention.py); the n_heads-wide repeat never exists here.
#
# Tensor parallelism: every function below takes optional ``mesh``/
# ``rules``. With a mesh, the Megatron recipe from ``parallel/`` is
# grafted onto the cached path — wq/wk/wv column-sharded on tp (per-chip
# head shards), wo/w_down row-sharded (GSPMD inserts the psum), and the
# KV pool sharded along n_kv_heads (parallel.sharding.kv_cache_specs),
# so model + cache scale past one chip while block bookkeeping stays
# global integers. Constraints keep activations on the tp axis between
# the projections; without a mesh every constraint is a no-op.
# ---------------------------------------------------------------------------


def _infer_constrain(x, mesh, rules, *logical):
    """Sharding annotation for the inference path (no-op without mesh)."""
    from ray_tpu.parallel.sharding import constrain_logical

    return constrain_logical(x, mesh, rules, *logical)

def init_kv_cache(cfg: TransformerConfig, num_blocks: int, block_size: int,
                  dtype: Any = None) -> Dict[str, jax.Array]:
    """Preallocate the paged KV pool: ``[L, num_blocks, block_size,
    n_kv_heads, head_dim]`` for K and V. Block 0 is conventionally the
    NULL block (padding writes land there — see ray_tpu/llm/kv_cache.py);
    zeros-initialized so unwritten slots are finite and mask-safe."""
    dt = dtype or cfg.dtype
    shape = (cfg.n_layers, num_blocks, block_size,
             cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def prefill_with_cache(cfg: TransformerConfig, params, cache,
                       tokens: jax.Array, prompt_lens: jax.Array,
                       block_tables: jax.Array
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Process right-padded prompts, writing every position's K/V into
    the paged cache, and return the last-real-position logits.

    tokens [B, S] int32 (padded rows/tails may be anything);
    prompt_lens [B]; block_tables [B, M] with M*block_size >= S (padded
    entries point at the null block, so out-of-prompt writes are trash
    writes into block 0 — never another sequence's block).

    Returns (logits [B, vocab] f32 at position prompt_lens-1, new cache).
    Causality makes the padded tail invisible to every real position, so
    the result is bit-identical to an unpadded per-sequence run.
    """
    _refuse_pattern(cfg, "prefill_with_cache")
    B, S = tokens.shape
    block_size = cache["k"].shape[2]
    x = _embed(cfg, params, tokens)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    # Physical slot of every position: (block_tables[b, s//bs], s % bs).
    blk = jnp.take_along_axis(block_tables, positions // block_size,
                              axis=1)                       # [B, S]
    off = positions % block_size

    def body(carry, lp_idx):
        x, ck, cv = carry
        lp, idx = lp_idx
        q, k, v = _project_qkv(cfg, lp, x, positions)
        with jax.named_scope("seg.attn_core"):
            ck = ck.at[idx, blk, off].set(k)
            cv = cv.at[idx, blk, off].set(v)
            o = _attention_dense(q, k, v, causal=True, grad=False)
        x = _attn_out(cfg, lp, x, o)
        x = _mlp_residual(cfg, lp, x, idx)
        return (x, ck, cv), None

    idxs = jnp.arange(cfg.n_layers)
    (x, ck, cv), _ = lax.scan(
        body, (x, cache["k"], cache["v"]), (params["layers"], idxs))
    with jax.named_scope("seg.head_loss"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        last = jnp.take_along_axis(
            x, (prompt_lens - 1)[:, None, None].clip(0), axis=1)[:, 0]
    return _lm_head(cfg, params, last), {"k": ck, "v": cv}


def prefill_chunk(cfg: TransformerConfig, params, cache,
                  tokens: jax.Array, start_pos: jax.Array,
                  chunk_lens: jax.Array, block_tables: jax.Array,
                  mesh=None, rules=None
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Process one CHUNK of each prompt against the paged cache: tokens
    ``[B, C]`` are each sequence's prompt slice starting at absolute
    position ``start_pos[b]``, attending over everything already in the
    cache (prefix-cache hits, earlier chunks) plus the chunk itself.

    This one program is both halves of the prefill fast path:

    - **chunked prefill** — a long prompt runs as several calls with
      advancing ``start_pos``, so the decode batch's inter-token stall
      is bounded by one chunk, not one prompt;
    - **prefix-cache skip** — a prompt whose leading blocks were shared
      by ``PagedKVCache.allocate_prefix`` starts its FIRST chunk at the
      cached length and never recomputes the shared tokens.

    tokens [B, C] int32 (rows/tails may be anything past chunk_lens);
    start_pos [B]; chunk_lens [B] (valid tokens in this chunk);
    block_tables [B, M] covering position start_pos + C - 1 (padded
    entries point at the null block — out-of-range writes are trash
    writes into block 0, masked out of every softmax).

    Returns (logits [B, vocab] f32 at the chunk's LAST valid position —
    meaningful only for rows whose chunk completes the prompt — and the
    new cache).
    """
    x, ck, cv = _chunk_scan(cfg, params, cache, tokens, start_pos,
                            block_tables, mesh, rules)
    with jax.named_scope("seg.head_loss"):
        last = jnp.take_along_axis(
            x, (chunk_lens - 1)[:, None, None].clip(0), axis=1)[:, 0]
    return _lm_head(cfg, params, last), {"k": ck, "v": cv}


def _chunk_scan(cfg: TransformerConfig, params, cache, tokens, start_pos,
                block_tables, mesh, rules):
    """Shared multi-token body of ``prefill_chunk`` and ``verify_step``:
    run the chunk through every layer against the paged cache, writing
    each position's K/V before it is attended, and return the final-
    normed hidden states ``[B, C, D]`` plus the updated K/V pools."""
    _refuse_pattern(cfg, "_chunk_scan")
    C = tokens.shape[1]
    block_size = cache["k"].shape[2]
    M = block_tables.shape[1]
    x = _embed(cfg, params, tokens)
    positions = start_pos[:, None] + jnp.arange(C)[None, :]    # [B, C]
    blk = jnp.take_along_axis(
        block_tables, jnp.minimum(positions // block_size, M - 1),
        axis=1)                                                # [B, C]
    off = positions % block_size

    from ray_tpu.ops.paged_attention import paged_attention_prefill

    def body(carry, lp_idx):
        x, ck, cv = carry
        lp, idx = lp_idx
        q, k, v = _project_qkv(cfg, lp, x, positions)
        with jax.named_scope("seg.attn_core"):
            q = _infer_constrain(q, mesh, rules, None, None, "heads",
                                 "head_dim")
            k = _infer_constrain(k, mesh, rules, None, None, "kv_heads",
                                 "head_dim")
            v = _infer_constrain(v, mesh, rules, None, None, "kv_heads",
                                 "head_dim")
            # Write the chunk's K/V, then attend over [0, position] per
            # token — each new slot is part of its own context.
            ck = ck.at[idx, blk, off].set(k)
            cv = cv.at[idx, blk, off].set(v)
            o = paged_attention_prefill(q, ck[idx], cv[idx], block_tables,
                                        positions, mesh=mesh, rules=rules)
        x = _attn_out(cfg, lp, x, o)
        x = _mlp_residual(cfg, lp, x, idx)
        return (x, ck, cv), None

    idxs = jnp.arange(cfg.n_layers)
    (x, ck, cv), _ = lax.scan(
        body, (x, cache["k"], cache["v"]), (params["layers"], idxs))
    with jax.named_scope("seg.head_loss"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps), ck, cv


def verify_step(cfg: TransformerConfig, params, cache,
                tokens: jax.Array, start_pos: jax.Array,
                block_tables: jax.Array, mesh=None, rules=None
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Speculative-decode VERIFY: advance each sequence by ``C`` tokens
    in ONE program and return the logits at EVERY position — the
    chunked-prefill multi-token path generalized from last-position
    logits to all-position logits, so the flagship can score a draft
    model's k proposals (positions carry token i's context -> logits
    for token i+1) in a single batched step instead of k decode steps.

    tokens [B, C] int32 — row b holds the verified context's last
    accepted token followed by the draft's proposals, starting at
    absolute position ``start_pos[b]``; block_tables as in
    ``prefill_chunk`` (padded rows aim at the NULL block).

    Returns (logits [B, C, vocab] f32, new cache). K/V for ALL C
    positions is written — including positions whose draft token is
    later REJECTED. That is safe by the same invariant chunked prefill
    relies on: each layer writes a position's K/V before any later
    position attends, and the engine always overwrites a rejected
    position's slot (with the corrected token's K/V) before any
    subsequent step attends over it.
    """
    x, ck, cv = _chunk_scan(cfg, params, cache, tokens, start_pos,
                            block_tables, mesh, rules)
    return _lm_head(cfg, params, x), {"k": ck, "v": cv}


def decode_step(cfg: TransformerConfig, params, cache,
                tokens: jax.Array, positions: jax.Array,
                block_tables: jax.Array, mesh=None, rules=None
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One continuous-batching iteration: each sequence advances by one
    token against its paged context.

    tokens [B] int32 (the token AT ``positions``, usually last sampled);
    positions [B] int32 (0-based; context length becomes positions+1);
    block_tables [B, M] int32. Padded batch rows should carry position 0
    and a null block table — their writes land in block 0 and their
    logits are garbage the caller ignores.

    Returns (logits [B, vocab] f32, new cache).
    """
    _refuse_pattern(cfg, "decode_step")
    block_size = cache["k"].shape[2]
    x = _embed(cfg, params, tokens[:, None])         # [B, 1, D]
    pos2 = positions[:, None]                        # [B, 1]
    context_lens = positions + 1
    blk = jnp.take_along_axis(block_tables, pos2 // block_size,
                              axis=1)[:, 0]          # [B]
    off = positions % block_size

    from ray_tpu.ops.paged_attention import paged_attention_decode

    def body(carry, lp_idx):
        x, ck, cv = carry
        lp, idx = lp_idx
        q, k, v = _project_qkv(cfg, lp, x, pos2)
        with jax.named_scope("seg.attn_core"):
            q = _infer_constrain(q, mesh, rules, None, None, "heads",
                                 "head_dim")
            k = _infer_constrain(k, mesh, rules, None, None, "kv_heads",
                                 "head_dim")
            v = _infer_constrain(v, mesh, rules, None, None, "kv_heads",
                                 "head_dim")
            # Write THIS token's k/v, then attend over [0, positions] —
            # the new slot is part of its own context (self-attention).
            ck = ck.at[idx, blk, off].set(k[:, 0])
            cv = cv.at[idx, blk, off].set(v[:, 0])
            o = paged_attention_decode(
                q[:, 0], ck[idx], cv[idx], block_tables, context_lens,
                mesh=mesh, rules=rules)
        x = _attn_out(cfg, lp, x, o)
        x = _mlp_residual(cfg, lp, x, idx)
        return (x, ck, cv), None

    idxs = jnp.arange(cfg.n_layers)
    (x, ck, cv), _ = lax.scan(
        body, (x, cache["k"], cache["v"]), (params["layers"], idxs))
    return _final_logits(cfg, params, x[:, 0]), {"k": ck, "v": cv}
