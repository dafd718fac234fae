"""Flagship model: decoder-only transformer, TPU-first.

Design notes (vs the reference, which delegates all model math to torch —
SURVEY.md §2.6): everything here is built for the MXU and the Mesh:

- bfloat16 activations, f32 params/optimizer state; all FLOPs in batched
  einsums that tile onto the systolic array; static shapes throughout.
- Layers are **stacked** ([L, ...] leading axis) and run under ``lax.scan``
  → one compiled layer body regardless of depth, with optional
  ``jax.checkpoint`` rematerialisation for HBM. The masters are float32;
  ``_scan_layers`` casts the matmuls' operands to ``cfg.dtype`` once, a
  whole stack before its scan, and the scan runs over that copy: the
  backward pass reads the same copy (none is kept a layer), and those
  leaves' gradients leave the loop in ``cfg.dtype`` and become float32 at
  the cast's transpose, outside it. Which leaves: what ``_kind_leaves``
  says the layer reads in ``cfg.dtype`` (the projections, the SwiGLUs',
  the conv's two, the KDA, MLA and Mamba matrices, the experts' and the
  latent's). The others stay float32 in the scan because the layer reads
  them so, or rounds them itself: the router (a float32 product at
  ``highest``), the selection bias, a KDA or Mamba layer's ``dt_bias``
  and ``A_log``, the norms' weights and the taps.
- Two execution paths over one layer (``_layer``):
  1. ``forward`` / ``loss_fn``: GSPMD path — logical sharding constraints
     (ShardingRules) and jit; XLA inserts the dp/fsdp/tp collectives.
  2. ``make_spmd_train_step``: manual path — ``jax.shard_map`` over the
     full (dp, pp, tp, sp, ep) mesh with explicit collectives: Megatron
     column/row TP with psum, ring attention over sp, GPipe ppermute over
     pp, gradient psum-mean over dp. This is the multi-chip training step
     the driver dry-runs.

GQA attention with rotary embeddings, RMSNorm, SwiGLU MLP.

**Layer kinds.** A stack is layers of one or several kinds
(``layer_kind``): the sequence operator of a layer is causal attention, a
gated short convolution, Kimi Delta Attention (``kda``: a linear-attention
layer, its recurrence a chunked scan, ``ops/kda.py``), latent attention
(``mla``: keys and values expanded from one low-rank latent, a rotary part
of the keys shared by the heads, values narrower than keys; the queries
one matrix or, ``q_lora_rank``, a latent with a norm of their own; a gate
a head on the outputs or, ``mla_gate``, none) or a Mamba-2
mixer (``mamba``: a selective state-space layer, its recurrence the chunked
scan of ``ops/ssd.py``) (``layer_types``), its feed-forward the dense
SwiGLU (the ``num_dense_layers`` leading ones) or the routed experts as
published (``router_experts``; ``parallel/moe.py``: sigmoid or softmax
scores, top-k over scores plus a bias, among the groups kept where the
router limits its choice, renormalised gates, no token dropped, and only
the experts this chip holds computed; SwiGLU experts at the model's width,
or two matrices with a squared ReLU between, ``ffn_act``, at a latent width
of their own between a down and an up projection every token passes,
``moe_latent``), with a shared expert beside them where the model has one
(``shared_d_ff``). A layer may also be its operator or its feed-forward
alone, one norm and one residual add (``layer_ffns``; ``none`` in either
list). ``_kind_leaves``
describes a kind's parameters once; the tree, its specs and the manual
step's specs are made from that. Each run of equal layers in published
order (``layer_runs``), or of equal units of several kinds that repeat
(``layer_units``: five times expert layer then Mamba layer, say), is one
``lax.scan`` over its kinds' stacks. A
configuration with none of ``layer_types``, ``layer_ffns`` and
``router_experts`` is one
run of ``attention_dense`` and keeps the flat tree ``params["layers"]
[leaf]``; every other stacks per kind, ``params["layers"][kind][leaf]``
(``_stacks``). The cached serving bodies run the flat layout and refuse
the other.

**Multi-token prediction** (``mtp_depth`` 1; DeepSeek-V3, arXiv:2412.19437,
section 2.2). ``loss_fn`` adds a second loss: behind the stack one module
(``params["mtp"]``) joins the last hidden state, before the final norm,
with the embedding of each position's next token (a norm each, one
projection of both), runs one more causal layer of the last layer's kind
with weights, router and shared expert of its own, and predicts the token
after next through the model's own table and head (``_mtp_loss``, one
segment, ``seg.mtp``). ``loss_parts`` gives the two losses apart. Training
only: the cached bodies and the manual step refuse it.

**A looped stack** (``total_ut_steps`` T > 1; Ouro, arXiv:2510.25741).
The layers run T times a step over their own output with the same weights
(``_passes``), each pass ending in the final norm, whose output is the next
pass's input and what an exit gate (``params["exit_gate"]``, one float32
logit a position) and the head read; the loss weighs the passes'
next-token losses by the exit distribution the gates give, with an entropy
term (``_exit_loss``; the gate, the distribution and the weighing under
``seg.exit``). ``exit_weights`` gives that distribution. With sandwich
norms (``sandwich_norm``) a layer norms its attention's and its MLP's
outputs before their residual adds. Training only, as the module.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops.flash_attention import FLASH_LSE, FLASH_OUT
from ray_tpu.parallel.mesh import mesh_shape
from ray_tpu.parallel import moe
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
    dtype: Any = jnp.bfloat16
    remat: bool = False
    # The width of a head; None: d_model // n_heads.
    head_dim: Optional[int] = None
    norm_eps: float = 1e-6
    # RMSNorm over each head of q and of k (own weights), before rope.
    qk_norm: bool = False
    # The head is the embedding table, transposed; no ``lm_head`` leaf.
    tie_embeddings: bool = False
    # The layer kinds (training body only). ``layer_types``: the
    # sequence operator of each layer, "attention", "conv", "kda", "mla" or
    # "mamba", or "none" for a layer that is its feed-forward alone (None:
    # all attention); the causal depthwise kernels of a conv layer, of a
    # KDA layer's q, k and v and of a Mamba layer have ``conv_kernel`` taps.
    # ``layer_ffns``: the feed-forward of each layer, "dense", "moe", or
    # "none" for a layer that is its operator alone (None: the routed
    # experts after the ``num_dense_layers`` leading layers, where the
    # model has them). A layer is never neither.
    layer_types: Optional[Tuple[str, ...]] = None
    layer_ffns: Optional[Tuple[str, ...]] = None
    conv_kernel: int = 3
    # Rotary embedding on an attention layer's queries and keys.
    rope: bool = True
    # A Mamba-2 layer: ``mamba_heads`` heads of ``mamba_head_dim`` channels
    # (their product the layer's inner width), a state of ``mamba_state``
    # a channel, B and C shared by the heads of each of ``mamba_groups``
    # groups, the scan in chunks of ``mamba_chunk`` positions.
    mamba_heads: int = 0
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_groups: int = 1
    mamba_chunk: int = 128
    # A KDA layer: heads of ``head_dim`` for keys and values alike; its
    # log-decay is ``kda_gate_floor * sigmoid(.)``, so it lies in
    # (``kda_gate_floor``, 0) (``ops/kda.py`` is safe down to -5).
    kda_gate_floor: float = -5.0
    # An MLA layer: the latent's rank, a head's widths of the keys'
    # position-free and rotary parts and of the values (None: head_dim,
    # none, head_dim).
    kv_lora_rank: int = 512
    qk_nope_dim: Optional[int] = None
    qk_rope_dim: int = 0
    v_head_dim: Optional[int] = None
    # Its queries too from a latent of this rank with a norm of its own
    # (None: one matrix), and whether a sigmoid gate a head weighs the
    # heads' outputs before ``W_o``.
    q_lora_rank: Optional[int] = None
    mla_gate: bool = True
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
    # The choice limited to the experts of the ``router_groups_kept`` best
    # of ``router_groups`` groups of consecutive experts (1: no limit).
    router_groups: int = 1
    router_groups_kept: int = 1
    # One expert every token goes through, added to the routed result
    # (0: none); replicated over the chips that share the routed ones.
    shared_d_ff: int = 0
    # An expert, routed or shared: "swiglu" (gate, up, down), or "relu2",
    # two matrices with ``relu(.) ** 2`` between.
    ffn_act: str = "swiglu"
    # The width the routed experts work at where it is not the model's: a
    # down projection before them and an up projection after their
    # weighted sum, on every token (0: none, the model's width).
    moe_latent: int = 0
    # Multi-token prediction (training body only; DeepSeek-V3,
    # arXiv:2412.19437, section 2.2): ``mtp_depth`` modules behind the
    # stack (0: none; 1), each one more layer of the last layer's kind
    # behind a joint projection of the stack's last hidden state and the
    # next token's embedding, sharing the table and the head; its loss,
    # of the token after next, counts ``mtp_weight`` times.
    mtp_depth: int = 0
    mtp_weight: float = 0.3
    # A looped stack (training body only; Ouro, arXiv:2510.25741): the
    # layers run ``total_ut_steps`` times over their own output with the
    # same weights, each pass ending in the final norm; an exit gate after
    # every pass, and a loss over the passes weighed by the exit
    # distribution the gates give, plus ``exit_entropy_beta`` times its
    # negative entropy (1: one pass, no gate, the next-token loss).
    total_ut_steps: int = 1
    exit_entropy_beta: float = 0.1
    # An RMSNorm (weights of its own) on the attention's and the MLP's
    # outputs before their residual adds: sandwich norms.
    sandwich_norm: bool = False

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.qk_nope_dim is None:
            object.__setattr__(self, "qk_nope_dim", self.head_dim)
        if self.v_head_dim is None:
            object.__setattr__(self, "v_head_dim", self.head_dim)
        for name, known in (("layer_types", OPERATORS + (NONE,)),
                            ("layer_ffns", (DENSE, MOE, NONE))):
            listed = getattr(self, name)
            if listed is None:
                continue
            object.__setattr__(self, name, tuple(listed))
            if len(listed) != self.n_layers or set(listed) - set(known):
                raise ValueError(
                    f"{name} {tuple(listed)}: one of {known} "
                    f"for each of the {self.n_layers} layers")
        if self.mtp_depth not in (0, 1):
            raise ValueError(f"mtp_depth {self.mtp_depth}: no module or one")
        if self.total_ut_steps < 1 or (
                (self.total_ut_steps > 1 or self.sandwich_norm)
                and not (_flat(self) and not self.mtp_depth)):
            raise ValueError(
                f"total_ut_steps {self.total_ut_steps}, sandwich_norm "
                f"{self.sandwich_norm}: a looped stack and sandwich norms "
                f"are of a stack of attention layers with one dense "
                f"feed-forward and no multi-token-prediction module")
        kinds = {layer_kind(self, i) for i in range(self.n_layers)}
        ffns = {kind.split("_")[1] for kind in kinds}
        if f"{NONE}_{NONE}" in kinds or (MOE in ffns
                                         and not self.router_experts):
            raise ValueError(
                f"layer kinds {sorted(kinds)}: a layer is an operator, a "
                f"feed-forward or both, and \"moe\" needs router_experts")
        if any(kind.startswith(MAMBA) for kind in kinds) and (
                self.mamba_heads < 1
                or self.mamba_heads % self.mamba_groups):
            raise ValueError(
                f"a mamba layer needs mamba_heads ({self.mamba_heads}) in "
                f"whole groups ({self.mamba_groups})")
        if self.ffn_act not in ("swiglu", "relu2") or (
                self.ffn_act != "swiglu" and DENSE in ffns):
            raise ValueError(
                f"ffn_act {self.ffn_act!r}: \"swiglu\", or \"relu2\" for "
                f"the routed and the shared experts (a dense feed-forward "
                f"is SwiGLU)")
        if self.router_experts:
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
            groups, kept = self.router_groups, self.router_groups_kept
            if (self.router_experts % groups or not 1 <= kept <= groups
                    or groups > 1 and (
                        self.router_experts // groups < 2
                        or self.experts_per_token
                        > kept * (self.router_experts // groups))):
                raise ValueError(
                    f"router_groups {groups}, kept {kept}: groups of at "
                    f"least two experts that divide the router's "
                    f"{self.router_experts}, the kept ones holding the "
                    f"{self.experts_per_token} a token takes")


ATTENTION, CONV, KDA, MLA, MAMBA = OPERATORS = (
    "attention", "conv", "kda", "mla", "mamba")
DENSE, MOE, NONE = "dense", "moe", "none"
# Every kind of layer, ``<operator>_<feed-forward>``, either of which may
# be ``none``; the first is the flat layout's one kind. (A kind's place
# here is folded into ``init_params``' draws: new kinds go to the end.)
KINDS = tuple(f"{op}_{ffn}" for op in OPERATORS[:4] for ffn in (DENSE, MOE)) \
    + tuple(f"{MAMBA}_{ffn}" for ffn in (DENSE, MOE)) \
    + tuple(f"{op}_{NONE}" for op in OPERATORS) \
    + tuple(f"{NONE}_{ffn}" for ffn in (DENSE, MOE))


def layer_kind(cfg: TransformerConfig, i: int) -> str:
    """The kind of layer ``i``, ``<operator>_<feed-forward>``."""
    op = cfg.layer_types[i] if cfg.layer_types is not None else ATTENTION
    if cfg.layer_ffns is not None:
        return f"{op}_{cfg.layer_ffns[i]}"
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


def layer_units(cfg: TransformerConfig
                ) -> Tuple[Tuple[Tuple[str, ...], Tuple[int, ...], int], ...]:
    """The stack as scans: (kinds, starts, count) of each stretch of layers
    that is ``count`` times the unit ``kinds`` (distinct kinds; ``starts``
    counts within each kind's own stack), in published order. From each
    layer on, the unit that repeats over the most layers, at least twice,
    the shortest such; else the layer alone. A run of equal layers is a
    unit of one kind, so a pattern without a repeating unit of several
    kinds gives ``layer_runs``."""
    kinds = [layer_kind(cfg, i) for i in range(cfg.n_layers)]
    units, seen, i = [], {}, 0
    while i < len(kinds):
        best = (1, 1)                                 # (period, repeats)
        for period in range(1, (len(kinds) - i) // 2 + 1):
            unit = kinds[i:i + period]
            if len(set(unit)) < period:
                continue
            repeats = 1
            while kinds[i + repeats * period:
                        i + (repeats + 1) * period] == unit:
                repeats += 1
            if repeats > 1 and period * repeats > best[0] * best[1]:
                best = (period, repeats)
        period, repeats = best
        unit = tuple(kinds[i:i + period])
        units.append((unit, tuple(seen.get(k, 0) for k in unit), repeats))
        for k in unit:
            seen[k] = seen.get(k, 0) + repeats
        i += period * repeats
    return tuple(units)


def _kind_leaves(cfg: TransformerConfig, kind: str) -> Dict[str, tuple]:
    """name -> (shape, fan_in, PartitionSpec roles, the type the layer
    reads it in) of one layer of ``kind``; fan_in None: ones (a norm), 0:
    zeros (the bias). A matmul's operand in the activations' type is read
    in ``cfg.dtype`` (``mm``) and nowhere else, so a scan may run over
    that copy of it (``_scan_layers``); every other leaf is read as it is
    stored (``f32``): what the router's float32 product, a decay or a norm
    reads is never rounded on the way to the layer."""
    D, Hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_heads * Hd, cfg.n_kv_heads * Hd
    mm, f32 = jnp.dtype(cfg.dtype), jnp.dtype(jnp.float32)
    op, ffn = kind.split("_")
    if op == ATTENTION:
        leaves = {"attn_norm": ((D,), None, (None,), f32),
                  "wq": ((D, nq), D, ("fsdp", "tp"), mm),
                  "wk": ((D, nkv), D, ("fsdp", "tp"), mm),
                  "wv": ((D, nkv), D, ("fsdp", "tp"), mm),
                  "wo": ((nq, D), nq, ("tp", "fsdp"), mm)}
        if cfg.qk_norm:
            leaves.update(q_norm=((Hd,), None, (None,), f32),
                          k_norm=((Hd,), None, (None,), f32))
        if cfg.sandwich_norm:
            leaves["attn_post_norm"] = ((D,), None, (None,), f32)
    elif op == CONV:
        K = cfg.conv_kernel
        leaves = {"conv_norm": ((D,), None, (None,), f32),
                  "conv_in": ((D, 3 * D), D, ("fsdp", "tp"), mm),
                  "conv_taps": ((D, K), K, (None, None), f32),
                  "conv_out": ((D, D), D, ("tp", "fsdp"), mm)}
    elif op == KDA:
        H, K = cfg.n_heads, cfg.conv_kernel
        wide = ((D, nq), D, ("fsdp", "tp"), mm)
        taps = ((nq, K), K, ("tp", None), f32)
        leaves = {"kda_norm": ((D,), None, (None,), f32),
                  "kda_q": wide, "kda_k": wide, "kda_v": wide,
                  "kda_q_taps": taps, "kda_k_taps": taps, "kda_v_taps": taps,
                  # the decay: a = z W_a + dt_bias, one value a channel,
                  # and one rate a head (both drawn as zeros here: a seeded
                  # tree gives them their published distributions itself)
                  "kda_a": wide, "kda_dt_bias": ((nq,), 0, ("tp",), f32),
                  "kda_a_log": ((H,), 0, ("tp",), f32),
                  "kda_beta": ((D, H), D, ("fsdp", "tp"), mm),
                  "kda_gate": wide,
                  "kda_o_norm": ((Hd,), None, (None,), f32),
                  "kda_out": ((nq, D), nq, ("tp", "fsdp"), mm)}
    elif op == MAMBA:
        H, K = cfg.mamba_heads, cfg.conv_kernel
        inner = H * cfg.mamba_head_dim
        mixed = inner + 2 * cfg.mamba_groups * cfg.mamba_state
        leaves = {"mamba_norm": ((D,), None, (None,), f32),
                  # [z | x B C | dt]: columns of several meanings, so no
                  # tensor-parallel axis is given them yet
                  "mamba_in": ((D, inner + mixed + H), D, ("fsdp", None), mm),
                  "mamba_taps": ((mixed, K), K, (None, None), f32),
                  "mamba_conv_bias": ((mixed,), 0, (None,), f32),
                  # one rate, one step bias and one skip a head (zeros and
                  # ones here: a seeded tree draws the first two itself)
                  "mamba_a_log": ((H,), 0, (None,), f32),
                  "mamba_dt_bias": ((H,), 0, (None,), f32),
                  "mamba_d": ((H,), None, (None,), f32),
                  "mamba_gate_norm": ((inner,), None, (None,), f32),
                  "mamba_out": ((inner, D), inner, (None, "fsdp"), mm)}
    elif op == NONE:
        leaves = {}
    else:
        H, R = cfg.n_heads, cfg.kv_lora_rank
        qk, rot, dv = cfg.qk_nope_dim + cfg.qk_rope_dim, cfg.qk_rope_dim, \
            cfg.v_head_dim
        leaves = {"mla_norm": ((D,), None, (None,), f32)}
        if cfg.q_lora_rank is None:
            leaves["mla_q"] = ((D, H * qk), D, ("fsdp", "tp"), mm)
        else:
            Rq = cfg.q_lora_rank
            leaves.update(mla_q_a=((D, Rq), D, ("fsdp", None), mm),
                          mla_q_norm=((Rq,), None, (None,), f32),
                          mla_q_b=((Rq, H * qk), Rq, (None, "tp"), mm))
        leaves.update(
            # the latent and the keys' rotary part, one for all heads
            mla_kv_a=((D, R + rot), D, ("fsdp", None), mm),
            mla_kv_norm=((R,), None, (None,), f32),
            mla_kv_b=((R, H * (cfg.qk_nope_dim + dv)), R, (None, "tp"), mm))
        if cfg.mla_gate:
            leaves["mla_gate"] = ((D, H), D, ("fsdp", "tp"), mm)
        leaves["mla_out"] = ((H * dv, D), H * dv, ("tp", "fsdp"), mm)
    if ffn == NONE:
        return leaves
    leaves["mlp_norm"] = ((D,), None, (None,), f32)
    if ffn == DENSE:
        F = cfg.d_ff
        leaves.update(w_gate=((D, F), D, ("fsdp", "tp"), mm),
                      w_up=((D, F), D, ("fsdp", "tp"), mm),
                      w_down=((F, D), F, ("tp", "fsdp"), mm))
        if cfg.sandwich_norm:
            leaves["mlp_post_norm"] = ((D,), None, (None,), f32)
    else:
        F, E = cfg.moe_d_ff or cfg.d_ff, len(cfg.experts_held)
        W = cfg.moe_latent or D             # the width the experts work at
        gated = cfg.ffn_act == "swiglu"
        leaves["router"] = ((D, cfg.router_experts), D, (None, None), f32)
        if cfg.moe_latent:
            leaves.update(latent_down=((D, W), D, ("fsdp", None), mm),
                          latent_up=((W, D), W, (None, "fsdp"), mm))
        if gated:
            leaves["e_gate"] = ((E, W, F), W, ("expert", None, "tp"), mm)
        leaves.update(e_up=((E, W, F), W, ("expert", None, "tp"), mm),
                      e_down=((E, F, W), F, ("expert", "tp", None), mm))
        if cfg.expert_bias:
            leaves["expert_bias"] = ((cfg.router_experts,), 0, (None,), f32)
        if cfg.shared_d_ff:
            Fs = cfg.shared_d_ff
            if gated:
                leaves["s_gate"] = ((D, Fs), D, ("fsdp", "tp"), mm)
            leaves.update(s_up=((D, Fs), D, ("fsdp", "tp"), mm),
                          s_down=((Fs, D), Fs, ("tp", "fsdp"), mm))
    return leaves


def _kind_counts(cfg: TransformerConfig) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for kind, _start, count in layer_runs(cfg):
        counts[kind] = counts.get(kind, 0) + count
    return counts


def _flat(cfg: TransformerConfig) -> bool:
    """The layout: one kind, ``layers[leaf]``; else ``layers[kind][leaf]``."""
    return cfg.layer_types is None and cfg.layer_ffns is None \
        and not cfg.router_experts


def _stacks(cfg: TransformerConfig, layers) -> Dict[str, Any]:
    """{kind: that kind's stack} of a ``layers`` tree in either layout."""
    return {KINDS[0]: layers} if _flat(cfg) else layers


def _layers_tree(cfg: TransformerConfig, stack_of) -> Dict[str, Any]:
    """The ``layers`` tree in the configuration's layout, each kind's stack
    ``stack_of(kind, layers of that kind, _kind_leaves(cfg, kind))``."""
    stacks = {kind: stack_of(kind, n, _kind_leaves(cfg, kind))
              for kind, n in _kind_counts(cfg).items()}
    return stacks[KINDS[0]] if _flat(cfg) else stacks


def _dense_init(key, shape, fan_in):
    return (jax.random.normal(key, shape, jnp.float32)
            * (1.0 / math.sqrt(fan_in)))


def mtp_kind(cfg: TransformerConfig) -> str:
    """The kind of a multi-token-prediction module's layer: the stack's
    last layer's."""
    return layer_kind(cfg, cfg.n_layers - 1)


def _draws(key: jax.Array):
    """(16 keys of ``key``, the function that draws a kind's stack from
    them): what a seed means for ``init_params``."""
    # One distinct key per weight family: same-shaped families (wq/wk/wv,
    # w_gate/w_up, e_gate/e_up) must not share init, or attention/MLP
    # branches start out identical and training silently degrades.
    ks = jax.random.split(key, 16)

    def stack_of(kind, n, leaves):
        # A seed means for the flat layout what it has meant: the drawn
        # families of ``attention_dense`` take keys 2, 3, ... in the leaves'
        # order; a further kind folds its place among ``KINDS`` into them.
        # (a kind with more drawn families than ``ks`` has keys left
        # folds the family's number into the seed's key).
        family = itertools.count(2)
        stack = {}
        for name, (shape, fan_in, _roles, _read) in leaves.items():
            if fan_in:
                at = next(family)
                k = ks[at] if at < len(ks) else jax.random.fold_in(key, at)
                if kind != KINDS[0]:
                    k = jax.random.fold_in(k, KINDS.index(kind))
                stack[name] = jax.vmap(
                    lambda k: _dense_init(k, shape, fan_in))(
                        jax.random.split(k, n))
            else:
                fill = jnp.ones if fan_in is None else jnp.zeros
                stack[name] = fill((n,) + shape, jnp.float32)
        return stack

    return ks, stack_of


def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    """Stacked-layer param pytree, ``_stacks``' layout. Weights f32
    (master copy). With multi-token prediction, ``params["mtp"]``: the
    modules stacked, each its two input norms, its joint projection
    ``[2 * d_model, d_model]`` (rows: the hidden state's half, then the
    embedding's), its layer (``mtp_kind``) and its output norm. A looped
    stack's exit gate, ``params["exit_gate"]``: ``w`` [d_model, 1] and
    ``b`` [1] (zeros)."""
    ks, stack_of = _draws(key)
    params = {
        "embed": jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                   jnp.float32) * 0.02,
        "layers": _layers_tree(cfg, stack_of),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(
            ks[1], (cfg.d_model, cfg.vocab_size), cfg.d_model)
    if cfg.mtp_depth:
        n, D, kind = cfg.mtp_depth, cfg.d_model, mtp_kind(cfg)
        mks, module_stack = _draws(jax.random.fold_in(key, len(KINDS)))
        ones = jnp.ones((n, D), jnp.float32)
        params["mtp"] = {
            "h_norm": ones, "e_norm": ones, "out_norm": ones,
            "proj": jax.vmap(lambda k: _dense_init(k, (2 * D, D), 2 * D))(
                jax.random.split(mks[0], n)),
            "block": module_stack(kind, n, _kind_leaves(cfg, kind))}
    if cfg.total_ut_steps > 1:
        params["exit_gate"] = {
            "w": _dense_init(jax.random.fold_in(key, len(KINDS) + 1),
                             (cfg.d_model, 1), cfg.d_model),
            "b": jnp.zeros((1,), jnp.float32)}
    return params


def _stack_specs(leaves, lead, role) -> Dict[str, P]:
    return {name: P(lead, *(role[x] for x in roles))
            for name, (_shape, _fan_in, roles, _read) in leaves.items()}


def _layer_specs(cfg: TransformerConfig, lead, role) -> Dict[str, Any]:
    """PartitionSpecs of the ``layers`` tree: ``lead`` on the stacked
    axis, on each further axis the mesh axis ``role`` gives its role."""
    return _layers_tree(cfg, lambda _kind, _n, leaves: _stack_specs(
        leaves, lead, role))


def param_specs(cfg: TransformerConfig,
                rules: Optional[ShardingRules] = None) -> Dict[str, Any]:
    """PartitionSpec tree matching init_params (GSPMD path).

    Layer weights carry a leading stacked-layer axis: sharded on pp when a
    pipeline mesh is used (stages = contiguous layer blocks), else None.
    2D weights shard wide-axis on tp, narrow on fsdp (ZeRO-3).
    """
    r = rules or ShardingRules()
    # Of several runs, a kind's stack is no contiguous block of layers: no
    # stage axis.
    stage = r.stage if len(layer_runs(cfg)) == 1 else None
    role = {"tp": r.mlp, "fsdp": r.fsdp_shard, "expert": r.expert,
            None: None}
    specs = {
        "embed": P(r.vocab, None),
        "layers": _layer_specs(cfg, stage, role),
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(r.fsdp_shard, r.vocab)
    if cfg.mtp_depth:
        norm = P(None, None)
        specs["mtp"] = {
            "h_norm": norm, "e_norm": norm, "out_norm": norm,
            "proj": P(None, None, r.fsdp_shard),
            "block": _stack_specs(_kind_leaves(cfg, mtp_kind(cfg)), None,
                                  role)}
    if cfg.total_ut_steps > 1:
        specs["exit_gate"] = {"w": P(None, None), "b": P(None)}
    return specs


def _stage_params_spec(cfg: TransformerConfig) -> Dict[str, P]:
    """in_specs for the stacked layer tree inside ``make_spmd_train_step``'s
    shard_map: leading layer axis sharded over pp, wide weight axes over
    tp."""
    return _layer_specs(cfg, "pp", {"tp": "tp", "fsdp": None, None: None})


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
    """q [B,S,Hq,Dh], k [B,S,Hkv,Dh], v [B,S,Hkv,Dv] -> [B,S,Hq,Dv]
    (``Dv`` is ``Dh`` but for latent attention).

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
    Hkv, Dv = k.shape[2], v.shape[3]
    if use_flash(S, S, Dh, q.dtype, dv=Dv):
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
    return o.reshape(B, S, Hq, Dv)


@jax.named_scope("seg.embed")
def _embed(cfg, params, tokens):
    """The table's rows at ``tokens`` in ``cfg.dtype``, bit for bit
    ``params["embed"].astype(cfg.dtype)[tokens]``; the table's gradient is
    ``_table_rows``'s own."""
    return _table_rows(params["embed"], tokens, cfg.dtype,
                       cfg.tie_embeddings)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _table_rows(table, tokens, dtype, copied):
    """``table``'s rows at ``tokens`` in ``dtype``. Only the rows read are
    cast, and nothing else of the table is touched; but where a copy of the
    whole table in ``dtype`` is made anyway (``copied``: a tied head reads
    one, ``_lm_head``), the rows are that copy's, half as wide to fetch
    (read from float32 there, LFM2's step lost 1.6 % on the v5e: PERF.md
    section 6, PR 46). Its backward pass is ``_table_rows_grad``."""
    if copied:
        return table.astype(dtype)[tokens]
    return table[tokens].astype(dtype)


def _table_rows_kept(table, tokens, dtype, copied):
    # the table for its shape and type alone: nothing of it is read again
    return _table_rows(table, tokens, dtype, copied), (table, tokens)


@jax.named_scope("seg.embed")
def _table_rows_grad(_dtype, _copied, kept, g):
    """The table's cotangent: the rows' cotangents summed at their ids in
    float32, whatever type the rows were read in (JAX's own transpose of
    the look-up sums duplicates in that type and widens the whole table
    afterwards). A ``custom_vjp``'s backward pass inherits no scope from
    its forward, so it carries its segment itself."""
    table, tokens = kept
    V, D = table.shape
    g = g.astype(jnp.float32)
    # A power-of-two block of columns at a time, each block a table of its
    # own (2560 = 2048 + 512): on the v5e XLA's scatter-add of 4096 rows
    # costs 270 to 350 ns a row at 2048 and 4096 columns and 1600 to 7100
    # at 2560, 3584, 5120 and 7168, in bf16 as in f32 (PERF.md section 5,
    # PR 46); no power-of-two width read slow, and the blocks joined cost
    # less than the rows summed at a padded width.
    parts, at = [], 0
    while at < D:
        wide = 1 << ((D - at).bit_length() - 1)
        parts.append(jnp.zeros((V, wide), jnp.float32).at[tokens].add(
            g[..., at:at + wide]))
        at += wide
    total = jnp.concatenate(parts, axis=1)
    # One sum an instruction: XLA folds the add of two look-ups' sums (a
    # multi-token-prediction module's second) into one scatter-add of both
    # ids' rows, which two segments would share.
    total = lax.optimization_barrier(total)
    return total.astype(table.dtype), None


_table_rows.defvjp(_table_rows_kept, _table_rows_grad)


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
    if cfg.rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


@jax.named_scope("seg.attn_proj")
def _attn_out(cfg, lp, x, o, tp_axis=None):
    """Output projection of the attention heads o [B, S, Hq, Dh], its
    norm where the layer has sandwich norms, and the residual add onto
    x [B, S, D]."""
    B, S, _ = x.shape
    o = o.reshape(B, S, -1) @ lp["wo"].astype(cfg.dtype)
    if tp_axis is not None:
        o = lax.psum(o, tp_axis)  # row-parallel output proj
    if cfg.sandwich_norm:
        o = rms_norm(o, lp["attn_post_norm"], cfg.norm_eps)
    return x + o


@jax.named_scope("seg.attn_core")
def _attention_ring(sp_axis, q, k, v):
    """``_attention_dense``'s place in the manual step where the sequence
    is sharded over ``sp_axis``: causal ring attention, K/V repeated to the
    query heads."""
    Hq, Hkv = q.shape[2], k.shape[2]
    if Hq != Hkv:
        k = jnp.repeat(k, Hq // Hkv, axis=2)
        v = jnp.repeat(v, Hq // Hkv, axis=2)
    return ring_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), axis_name=sp_axis, causal=True,
    ).transpose(0, 2, 1, 3)


@jax.named_scope("seg.mlp")
def _mlp_residual(cfg, lp, x, tp_axis=None):
    """MLP norm, SwiGLU, its output's norm where the layer has sandwich
    norms, and the residual add, x [B, S, D]."""
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    out = _swiglu(cfg, lp, h, tp_axis)
    if cfg.sandwich_norm:
        out = rms_norm(out, lp["mlp_post_norm"], cfg.norm_eps)
    return x + out


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
    z = rms_norm(x, lp["conv_norm"], cfg.norm_eps)
    b, c, u = jnp.split(z @ lp["conv_in"].astype(dt), 3, axis=-1)
    y = _causal_taps(b * u, lp["conv_taps"])                # taps [D, K]
    return x + (c * y) @ lp["conv_out"].astype(dt)


def _causal_taps(x, taps):
    """A causal depthwise convolution: x [B, S, C], taps [C, K] (cast to
    x's type here) -> ``y_t = sum_j taps[:, j] * x_{t-(K-1)+j}``, zeros
    before the sequence, no bias. K shifted multiply-adds."""
    S, K = x.shape[1], taps.shape[1]
    v = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    taps = taps.astype(x.dtype)
    return sum(v[:, j:j + S] * taps[:, j] for j in range(K))   # tap K-1: now


def _l2_heads(x, eps=1e-6):
    """x [..., Dh] over the L2 norm of its last axis, in float32."""
    x32 = x.astype(jnp.float32)
    return (x32 * lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True)
                            + eps)).astype(x.dtype)


def _kda_residual(cfg, lp, x):
    """Kimi Delta Attention as a layer's sequence operator (Kimi Linear,
    arXiv:2510.26692, section 3), with its norm and residual add,
    x [B, S, D]. q, k and v go through causal taps and a SiLU each; q and k
    are L2-normalised a head (q scaled ``head_dim ** -0.5``); the log-decay
    is one value a channel, ``kda_gate_floor * sigmoid(exp(A_log) * (z W_a
    + dt_bias))``; beta a sigmoid a head; the heads' outputs are RMS-normed
    (one weight ``[head_dim]``), gated by ``sigmoid(z W_g)`` and projected.
    No rope: the decay carries position. Two segments: the recurrence
    itself is ``seg.kda_core``, and keeps for its backward pass its five
    inputs, its chunks' inverses and the state each chunk starts from (42
    MB a layer at 4096 tokens of 8 heads of 128, ``ops/kda.py``); it is not
    made a second time. Of what surrounds it the backward pass is left the
    six projections' outputs and makes the elementwise chains behind them
    again (some twenty ``[S, H * head_dim]`` arrays a layer otherwise)."""
    from ray_tpu.ops.kda import kda_chunk

    keep_products = partial(
        jax.checkpoint, policy=jax.checkpoint_policies.dots_saveable)
    with jax.named_scope("seg.kda_proj"):
        q, k, v, g, beta, gate = keep_products(
            partial(_kda_inputs, cfg))(lp, x)
    with jax.named_scope("seg.kda_core"):
        o = kda_chunk(q, k, v, g, beta)
    with jax.named_scope("seg.kda_proj"):
        return keep_products(partial(_kda_out, cfg))(lp, x, o, gate)


def _kda_inputs(cfg, lp, x):
    """x [B, S, D] -> q, k, v [B, S, H, Dh], the log-decay [B, S, H, Dh]
    and beta [B, S, H] in float32, and the output gate's logits
    [B, S, H * Dh]."""
    dt = cfg.dtype
    B, S, _ = x.shape
    H, Hd = cfg.n_heads, cfg.head_dim
    z = rms_norm(x, lp["kda_norm"], cfg.norm_eps)

    def mixed(name):
        y = _causal_taps(z @ lp[f"kda_{name}"].astype(dt),
                         lp[f"kda_{name}_taps"])
        return jax.nn.silu(y).reshape(B, S, H, Hd)

    q = _l2_heads(mixed("q")) * (Hd ** -0.5)
    k, v = _l2_heads(mixed("k")), mixed("v")
    # The decay's and beta's logits leave their products in float32: a
    # head's rate multiplies the decay's by up to 16 before the sigmoid,
    # and rounded to bfloat16 first they move its gradient by percents.
    f32 = partial(jnp.matmul, preferred_element_type=jnp.float32)
    a = f32(z, lp["kda_a"].astype(dt)) + lp["kda_dt_bias"]
    rate = jnp.exp(lp["kda_a_log"].astype(jnp.float32))[:, None]
    g = cfg.kda_gate_floor * jax.nn.sigmoid(rate * a.reshape(B, S, H, Hd))
    beta = jax.nn.sigmoid(f32(z, lp["kda_beta"].astype(dt)))
    return q, k, v, g, beta, z @ lp["kda_gate"].astype(dt)


def _kda_out(cfg, lp, x, o, gate):
    """The heads' outputs o [B, S, H, Dh], normed a head, under the gate
    (its logits [B, S, H * Dh]), through ``W_o``, and the residual add."""
    B, S, _ = x.shape
    o = rms_norm(o, lp["kda_o_norm"], cfg.norm_eps).reshape(B, S, -1)
    return x + (o * jax.nn.sigmoid(gate)) @ lp["kda_out"].astype(cfg.dtype)


def _mamba_residual(cfg, lp, x):
    """A Mamba-2 mixer as a layer's sequence operator (arXiv:2405.21060),
    with its norm and residual add, x [B, S, D]: ``[z | xBC | dt] = u
    W_in``; ``xBC`` through causal taps with a bias and a SiLU, then split
    into a head's inputs and a group's ``B`` and ``C``; ``dt = softplus(dt
    + dt_bias)`` and ``A = -exp(A_log)``, one a head; the selective scan
    with its skip ``D`` (``ops/ssd.py``); the result times ``SiLU(z)``
    RMS-normed over each group's channels (one weight a channel) and
    projected. No rope: the decay carries position. Two segments: the scan
    itself is ``seg.mamba_core``. Of what surrounds it the backward pass is
    left the products' outputs and makes the elementwise chains again."""
    from ray_tpu.ops.ssd import ssd_chunk

    keep_products = partial(
        jax.checkpoint, policy=jax.checkpoint_policies.dots_saveable)
    with jax.named_scope("seg.mamba_proj"):
        z, xs, b, c, dt = keep_products(partial(_mamba_inputs, cfg))(lp, x)
    with jax.named_scope("seg.mamba_core"):
        a = -jnp.exp(lp["mamba_a_log"].astype(jnp.float32))
        y = ssd_chunk(xs, dt, a, b, c, lp["mamba_d"], cfg.mamba_chunk)
    with jax.named_scope("seg.mamba_proj"):
        return keep_products(partial(_mamba_out, cfg))(lp, x, y, z)


def _mamba_inputs(cfg, lp, x):
    """x [B, S, D] -> the gate z [B, S, inner], the heads' inputs
    [B, S, H, P], B and C [B, S, G, N], and dt [B, S, H] in float32."""
    dt_ = cfg.dtype
    B, S, _ = x.shape
    H, P = cfg.mamba_heads, cfg.mamba_head_dim
    G, N = cfg.mamba_groups, cfg.mamba_state
    inner = H * P
    u = rms_norm(x, lp["mamba_norm"], cfg.norm_eps)
    w = lp["mamba_in"].astype(dt_)
    z, mixed = jnp.split(u @ w[:, :-H], [inner], axis=-1)
    mixed = jax.nn.silu(_causal_taps(mixed, lp["mamba_taps"])
                        + lp["mamba_conv_bias"].astype(dt_))
    xs, b, c = jnp.split(mixed, [inner, inner + G * N], axis=-1)
    # The step's logits leave their product in float32, as a KDA layer's
    # decay does: a head's rate multiplies them by up to 16.
    dt = jax.nn.softplus(
        jnp.matmul(u, w[:, -H:], preferred_element_type=jnp.float32)
        + lp["mamba_dt_bias"])
    return (z, xs.reshape(B, S, H, P), b.reshape(B, S, G, N),
            c.reshape(B, S, G, N), dt)


def _mamba_out(cfg, lp, x, y, z):
    """The scan's result y [B, S, H, P] under ``SiLU(z)``, normed over each
    group's channels, through ``W_out``, and the residual add."""
    B, S, _ = x.shape
    y = y.reshape(B, S, -1) * jax.nn.silu(z)
    y = rms_norm(y.reshape(B, S, cfg.mamba_groups, -1),
                 lp["mamba_gate_norm"].reshape(cfg.mamba_groups, -1),
                 cfg.norm_eps).reshape(B, S, -1)
    return x + y @ lp["mamba_out"].astype(cfg.dtype)


@jax.named_scope("seg.attn_proj")
def _project_mla(cfg, lp, x, positions):
    """Latent attention's projections in training form (DeepSeek-V2,
    arXiv:2405.04434, section 2.1; no absorbed weights, no cache),
    x [B, S, D] -> q [B,S,H,nope+rope], the keys' position-free part
    [B,S,H,nope], their rotary part [B,S,1,rope] that every head shares,
    v [B,S,H,Dv], and the heads' output gate [B,S,H] (None where the
    configuration has none). The queries are one product, or with a query
    latent (``q_lora_rank``) ``RMSNorm(z W_qa) W_qb``."""
    dt = cfg.dtype
    B, S, _ = x.shape
    H, nope, R = cfg.n_heads, cfg.qk_nope_dim, cfg.kv_lora_rank
    h = rms_norm(x, lp["mla_norm"], cfg.norm_eps)
    if cfg.q_lora_rank is None:
        q = h @ lp["mla_q"].astype(dt)
    else:
        q = rms_norm(h @ lp["mla_q_a"].astype(dt), lp["mla_q_norm"],
                     cfg.norm_eps) @ lp["mla_q_b"].astype(dt)
    q = q.reshape(B, S, H, -1)
    kv_a = h @ lp["mla_kv_a"].astype(dt)
    latent = rms_norm(kv_a[..., :R], lp["mla_kv_norm"], cfg.norm_eps)
    kv = (latent @ lp["mla_kv_b"].astype(dt)).reshape(B, S, H, -1)
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], positions, cfg.rope_theta)], -1)
    k_rope = rope(kv_a[..., None, R:], positions, cfg.rope_theta)
    gate = jax.nn.sigmoid(h @ lp["mla_gate"].astype(dt)) \
        if cfg.mla_gate else None
    return q, kv[..., :nope], k_rope, kv[..., nope:], gate


@jax.named_scope("seg.attn_core")
def _mla_keys(k_nope, k_rope):
    """A head's keys: its own position-free part beside the rotary part
    all heads share."""
    return jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3]
                                  + k_rope.shape[3:])], axis=-1)


@jax.named_scope("seg.attn_proj")
def _mla_out(cfg, lp, x, o, gate):
    """The heads' outputs o [B, S, H, Dv], each under its gate where it
    has one, through ``W_o``, and the residual add."""
    B, S, _ = x.shape
    if gate is not None:
        o = o * gate[..., None]
    return x + o.reshape(B, S, -1) @ lp["mla_out"].astype(cfg.dtype)


def _moe_residual(cfg, lp, x, alone=False):
    """The routed experts as a layer's feed-forward, with its norm and
    residual add, x [B, S, D]: this chip's experts' part of the layer
    (``parallel/moe.py``), and the tokens each held expert got; where the
    model has a shared expert, every token's pass through it besides, in
    the experts' own form (``ffn_act``). Where the routed experts work at
    a latent width (``moe_latent``), every token goes down to it before
    them and their weighted sum comes up from it after (``seg.moe_latent``:
    two plain products outside the row passes); the router and the shared
    expert read the model's width. Segments of their own, so not under
    ``seg.mlp``. ``alone``: the layer is this feed-forward and nothing else
    (a pattern of such layers has a norm a mixer, twice as many a block),
    and the two float32 copies of the residual stream that the norm and the
    router's product would leave the backward pass, ``[B * S, D]`` each, are
    made again there from the stream itself."""
    B, S, D = x.shape
    with jax.named_scope("seg.moe_route"):
        norm = jax.checkpoint(rms_norm, static_argnums=2,
                              prevent_cse=False) if alone else rms_norm
        h = norm(x, lp["mlp_norm"], cfg.norm_eps).reshape(B * S, D)
        routing = moe.route(
            h, lp["router"], lp.get("expert_bias"),
            experts_held=cfg.experts_held, k=cfg.experts_per_token,
            score=cfg.router_score, norm_topk=cfg.norm_topk,
            scale=cfg.routed_scale, n_group=cfg.router_groups,
            topk_group=cfg.router_groups_kept, keep_input=not alone)
    dt = cfg.dtype
    if cfg.shared_d_ff:
        with jax.named_scope("seg.moe_shared"):
            shared = _swiglu(cfg, {"w_gate": lp["s_gate"], "w_up": lp["s_up"],
                                   "w_down": lp["s_down"]}, h, None) \
                if cfg.ffn_act == "swiglu" else _relu2_expert(
                    h, lp["s_up"].astype(dt), lp["s_down"].astype(dt))
            x = x + shared.reshape(B, S, D)
    if cfg.moe_latent:
        with jax.named_scope("seg.moe_latent"):
            h = h @ lp["latent_down"].astype(dt)
    with jax.named_scope("seg.moe_experts"):
        out = moe.held_experts(
            h, routing,
            lp["e_gate"].astype(dt) if cfg.ffn_act == "swiglu" else None,
            lp["e_up"].astype(dt), lp["e_down"].astype(dt))
        if not cfg.moe_latent:
            return x + out.reshape(B, S, D), routing.group_sizes
    with jax.named_scope("seg.moe_latent"):
        out = out @ lp["latent_up"].astype(dt)
        return x + out.reshape(B, S, D), routing.group_sizes


@partial(jax.checkpoint, policy=jax.checkpoint_policies.dots_saveable)
def _relu2_expert(h, w_up, w_down):
    """``relu(h W_up) ** 2 W_down``; the backward pass is left the first
    product's output and squares it again."""
    return jnp.square(jax.nn.relu(h @ w_up)) @ w_down


def _layer(cfg: TransformerConfig, kind: str, lp, x, positions,
           attention, tp_axis):
    """One layer of ``kind`` (``layer_kind``) -> (x, the tokens each held
    expert got; None for a dense feed-forward). ``attention(q, k, v)`` is
    the attention to use. In the manual step the weights arriving here are
    the local TP shard (wide axis pre-sliced) and ``tp_axis`` the axis the
    row-parallel products sum over; in GSPMD mode it is None."""
    op, ffn = kind.split("_")
    if op == CONV:
        x = _conv_residual(cfg, lp, x)
    elif op == KDA:
        x = _kda_residual(cfg, lp, x)
    elif op == MAMBA:
        x = _mamba_residual(cfg, lp, x)
    elif op == MLA:
        q, k_nope, k_rope, v, gate = _project_mla(cfg, lp, x, positions)
        x = _mla_out(cfg, lp, x,
                     attention(q, _mla_keys(k_nope, k_rope), v), gate)
    elif op == ATTENTION:
        q, k, v = _project_qkv(cfg, lp, x, positions)
        x = _attn_out(cfg, lp, x, attention(q, k, v), tp_axis)
    if ffn == MOE:
        return _moe_residual(cfg, lp, x, alone=op == NONE)
    if ffn == NONE:
        return x, None
    return _mlp_residual(cfg, lp, x, tp_axis), None


# What a layer's checkpoint keeps beyond its input: the flash forward's two
# residuals, about a layer input's bytes, for a kernel not run again.
_KEEP_FLASH = jax.checkpoint_policies.save_only_these_names(FLASH_OUT,
                                                            FLASH_LSE)


def _scan_layers(cfg: TransformerConfig, kinds: Tuple[str, ...], stacks,
                 x, positions, attention, tp_axis, constrain, layers=None):
    """A run of equal units of layers, a unit one layer of each of
    ``kinds`` in turn (a run of equal layers: one kind): one ``lax.scan``
    of ``_layer`` over ``stacks``, a stack a kind, whose leaves lead with
    the layers (``layers``: the run's slice of each; None: all). The scan
    runs over each leaf in the type
    the layer reads it in (``_kind_leaves``): the float32 masters of the
    matmuls' operands are cast here, a whole stack at once and before it
    is cut into runs (one cast a stack under its own name in the compiled
    step, however many runs read it), and the layer's own casts of them do
    nothing. So the backward pass reads the same stack and keeps no copy
    of a layer's cast weights, and their gradients leave the loop as
    stacks of that type, widened by this cast's transpose. (Stacks handed
    in cast already, as a looped stack's are, pass the cast unchanged.)
    Each layer is checkpointed where the configuration asks (``remat``)
    and in a looped stack, whose passes would otherwise keep every layer
    application's activations for the backward pass. The checkpoint keeps
    the layer's input and, where attention ran the flash kernel, the
    kernel's output and log-sum-exp (``_KEEP_FLASH``): the backward pass
    makes the rest of the layer again (norms, projections, rope, the
    feed-forward) and hands the kept two to the backward kernels, and runs
    no flash forward a second time."""
    stacks = tuple({name: stack[name].astype(read)
                    for name, (_shape, _fan_in, _roles, read)
                    in _kind_leaves(cfg, kind).items()}
                   for kind, stack in zip(kinds, stacks))
    if layers is not None:
        stacks = tuple(jax.tree.map(lambda a: a[run], stack)
                       for run, stack in zip(layers, stacks))

    def body(x, lps):
        for kind, lp in zip(kinds, lps):
            run = partial(_layer, cfg, kind, lp, positions=positions,
                          attention=attention, tp_axis=tp_axis)
            x, _load = jax.checkpoint(run, policy=_KEEP_FLASH)(x) \
                if cfg.remat or cfg.total_ut_steps > 1 else run(x)
            x = constrain(x, "batch", "sequence", "embed")
        return x, None

    return lax.scan(body, x, stacks)[0]


def _layers(cfg: TransformerConfig, layers, x, positions, constrain):
    """The stack: each run of equal units in published order
    (``layer_units``) over that run's slice of its kinds' stacks."""
    counts, stacks = _kind_counts(cfg), _stacks(cfg, layers)
    for kinds, starts, count in layer_units(cfg):
        runs = None if all(count == counts[k] for k in kinds) else tuple(
            slice(start, start + count) for start in starts)
        x = _scan_layers(cfg, kinds, tuple(stacks[k] for k in kinds), x,
                         positions, _attention_dense, None, constrain, runs)
    return x


def moe_load(cfg: TransformerConfig, params: Dict[str, Any],
             tokens: jax.Array) -> Dict[str, jax.Array]:
    """Tokens routed to each held expert in each expert layer of a forward
    pass over ``tokens`` [B, S], from the layers' own routing:
    ``{kind: int32 [layers of that kind, experts held]}``, and under
    ``"mtp"`` a multi-token-prediction module's router (each position's
    next token is the row's own; the last position takes the first's)."""
    B, S = tokens.shape
    load: Dict[str, list] = {}
    x = _embed(cfg, params, tokens)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    stacks = _stacks(cfg, params["layers"])
    for kind, start, count in layer_runs(cfg):
        for at in range(start, start + count):
            lp = jax.tree.map(lambda a: a[at], stacks[kind])
            x, sizes = _layer(cfg, kind, lp, x, positions,
                              _attention_dense, None)
            if sizes is not None:
                load.setdefault(kind, []).append(sizes)
    if cfg.mtp_depth:
        x = _mtp_input(cfg, params, x, jnp.roll(tokens, -1, axis=1),
                       lambda x, *_logical: x)
        lp = jax.tree.map(lambda a: a[0], params["mtp"]["block"])
        _x, sizes = _layer(cfg, mtp_kind(cfg), lp, x, positions,
                           _attention_dense, None)
        if sizes is not None:
            load["mtp"] = [sizes]
    return {kind: jnp.stack(sizes) for kind, sizes in load.items()}


def _refuse_pattern(cfg: TransformerConfig, body: str) -> None:
    """The cached serving bodies run one kind of layer: the state of a
    conv, a KDA or a Mamba layer and an MLA layer's latent have no place in
    the paged cache yet, and an expert layer that holds a share gives a partial
    result. A looped stack needs a cache a layer a pass, which
    ``llm/kv_cache.py`` lacks; these bodies and the manual step run each
    layer once, and have met sandwich norms in no test. A wrong answer is
    worse than none."""
    if not _flat(cfg):
        raise NotImplementedError(
            f"{body} runs attention layers with one feed-forward kind; "
            f"this configuration has a layer pattern "
            f"({[k for k, _s, _n in layer_runs(cfg)]}), which only "
            f"forward/loss_fn run")
    if cfg.mtp_depth:
        raise NotImplementedError(
            f"{body} has no place for a multi-token-prediction module, "
            f"which only loss_fn runs")
    if cfg.total_ut_steps > 1 or cfg.sandwich_norm:
        raise NotImplementedError(
            f"{body} runs each layer once and without sandwich norms; this "
            f"configuration has {cfg.total_ut_steps} passes of its stack "
            f"and sandwich_norm {cfg.sandwich_norm}, which only "
            f"forward/loss_fn run")


def _hidden(cfg: TransformerConfig, params, tokens, mesh, rules):
    """GSPMD path: tokens [B, S] -> (the stack's last hidden state
    [B, S, D] before the final norm, its positions, the sharding
    constraint of this mesh). Layers via lax.scan."""
    x, positions, constrain = _stream(cfg, params, tokens, mesh, rules)
    x = _layers(cfg, params["layers"], x, positions, constrain)
    return x, positions, constrain


def _stream(cfg: TransformerConfig, params, tokens, mesh, rules):
    """GSPMD path: tokens [B, S] -> (their embeddings [B, S, D], their
    positions, the sharding constraint of this mesh)."""
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
    return x, positions, constrain


def _passes(cfg: TransformerConfig, params, x, positions, constrain, read):
    """A looped stack (``total_ut_steps`` T > 1) over the stream ``x``
    [B, S, D] (``_stream``): the layers T times over their own output,
    each pass ending in the final norm, whose output ``x_t`` is the next
    pass's input and what ``read(x_t)`` reads -> (``x_T``, ``read``'s
    results stacked over the passes). One ``lax.scan`` over the passes
    around the layers' own: the stacks are cast to the type the layers
    read them in once, before the first pass, and each leaf's gradient
    sums its T uses in that type; each layer application is checkpointed
    (``_scan_layers``), so the backward pass keeps a layer's input a pass
    and its flash kernel's output and log-sum-exp, and makes the rest of
    the layer again."""
    layers = {name: params["layers"][name].astype(read_as)   # flat layout
              for name, (_shape, _fan_in, _roles, read_as)
              in _kind_leaves(cfg, KINDS[0]).items()}

    def one_pass(x, _):
        x = _layers(cfg, layers, x, positions, constrain)
        with jax.named_scope("seg.head_loss"):
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, read(x)

    return lax.scan(one_pass, x, None, length=cfg.total_ut_steps)


@jax.named_scope("seg.exit")
def _exit_logits(params, x):
    """The exit gate's logit of each position, float32 [B, S]:
    ``x w + b``, ``x`` read in float32."""
    gate = params["exit_gate"]
    return jnp.matmul(x.astype(jnp.float32), gate["w"],
                      precision=lax.Precision.HIGHEST)[..., 0] + gate["b"]


def _exit_log_probs(logits):
    """The exit distribution's logarithm over T passes from the gates'
    logits [T, ...]: with ``lambda_t = sigmoid(logit_t)``, ``p_t =
    lambda_t prod_{j<t} (1 - lambda_j)`` for t < T and ``p_T = prod_{j<T}
    (1 - lambda_j)`` (the last pass's gate weighs nothing); in log-sigmoids,
    so no factor underflows."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-logits[:-1]), axis=0)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay])
    leave = jnp.concatenate([jax.nn.log_sigmoid(logits[:-1]),
                             jnp.zeros_like(stay[:1])])
    return leave + before


def exit_weights(cfg: TransformerConfig, params: Dict[str, Any],
                 tokens: jax.Array) -> jax.Array:
    """The exit distribution of a looped stack over ``tokens`` [B, S],
    averaged over the positions: float32 [total_ut_steps], summing to 1."""
    _x, logits = _passes(cfg, params, *_stream(cfg, params, tokens, None,
                                               None),
                         partial(_exit_logits, params))
    return jnp.mean(jnp.exp(_exit_log_probs(logits)), axis=(1, 2))


def forward(cfg: TransformerConfig, params: Dict[str, Any],
            tokens: jax.Array,
            mesh: Optional[Mesh] = None,
            rules: Optional[ShardingRules] = None) -> jax.Array:
    """GSPMD path: tokens [B, S] -> logits [B, S, V]; of a looped stack,
    its last pass's."""
    if cfg.total_ut_steps > 1:
        x, positions, constrain = _stream(cfg, params, tokens, mesh, rules)
        x, _ = _passes(cfg, params, x, positions, constrain, lambda x: None)
        logits = _lm_head(cfg, params, x)
    else:
        x, _positions, constrain = _hidden(cfg, params, tokens, mesh, rules)
        logits = _final_logits(cfg, params, x)
    return constrain(logits, "batch", "sequence", "vocab")


def _head_weight(cfg, params):
    """The head's weight [D, V] in ``cfg.dtype``: ``lm_head``, or the
    embedding table's copy transposed."""
    if cfg.tie_embeddings:
        # One table, two uses: the gradient reaches it from both.
        return params["embed"].astype(cfg.dtype).T
    return params["lm_head"].astype(cfg.dtype)


@jax.named_scope("seg.head_loss")
def _lm_head(cfg, params, x):
    """f32 logits of final-normed hidden states."""
    return (x @ _head_weight(cfg, params)).astype(jnp.float32)


@jax.named_scope("seg.head_loss")
def _final_logits(cfg, params, x):
    return _lm_head(cfg, params,
                    rms_norm(x, params["final_norm"], cfg.norm_eps))


def _token_nll(logits, targets):
    """The next-token loss a position of given logits, by autodiff's own
    rules: the plain form the tests hold ``_head_nll`` to."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


@jax.named_scope("seg.head_loss")
def _head_nll(cfg, params, x, targets, constrain):
    """The next-token loss of each position, float32 [B, S], of final-normed
    hidden states ``x`` and their ``targets``: ``_token_nll(_lm_head(...))``
    in one ``jax.custom_vjp`` (``_logits_nll``) that keeps the logits in
    ``cfg.dtype`` and writes nothing of float32 over the vocabulary."""
    return _logits_nll(x, _head_weight(cfg, params), targets, constrain)


@jax.named_scope("seg.head_loss")
def _final_nll(cfg, params, x, targets, constrain):
    """The training loss's next-token part: the mean over positions of the
    loss of the stack's output ``x`` under the final norm."""
    return jnp.mean(_head_nll(
        cfg, params, rms_norm(x, params["final_norm"], cfg.norm_eps),
        targets, constrain))


def _is_target(logits, targets):
    """[..., V] booleans, true at each row's target: a comparison with the
    columns' index, so no value is fetched or put by index."""
    return lax.broadcasted_iota(jnp.int32, logits.shape,
                                logits.ndim - 1) == targets[..., None]


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _logits_nll(x, w, targets, constrain):
    """``lse(x w) − (x w)[target]`` a position, float32, from the logits
    ``x w`` in their own type; its backward pass is ``_logits_nll_grad``."""
    return _logits_nll_kept(x, w, targets, constrain)[0]


def _logits_nll_kept(x, w, targets, constrain):
    logits = constrain(x @ w, "batch", "sequence", "vocab")
    wide = logits.astype(jnp.float32)
    top = jnp.max(wide, axis=-1, keepdims=True)
    lse = top[..., 0] + jnp.log(jnp.sum(jnp.exp(wide - top), axis=-1))
    picked = jnp.sum(jnp.where(_is_target(logits, targets), wide, 0.0),
                     axis=-1)
    return lse - picked, (x, w, targets, logits, lse)


@jax.named_scope("seg.head_loss")
def _logits_nll_grad(constrain, kept, dnll):
    """The cotangents of ``x`` and ``w``: the logits' cotangent ``(softmax −
    onehot) dnll`` made in one elementwise pass from the logits and the
    log-sum-exp and cast to the logits' type, as JAX's transpose of the cast
    casts it, then the two products (the transpose of a log-softmax and a
    pick would scatter into float32 zeros over the vocabulary and read them
    twice). It writes its segment itself, as ``_table_rows_grad`` does, so
    the join's segment does not rest on the names JAX gives a transpose."""
    x, w, targets, logits, lse = kept
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    g = jnp.where(_is_target(logits, targets), p - 1.0, p) * dnll[..., None]
    g = constrain(g.astype(logits.dtype), "batch", "sequence", "vocab")
    dx = g @ w.T
    dw = jnp.einsum("...d,...v->dv", x, g)
    return dx, dw, None


_logits_nll.defvjp(_logits_nll_kept, _logits_nll_grad)


def _mtp_input(cfg: TransformerConfig, params, h, next_tokens, constrain):
    """What a multi-token-prediction module's layer reads, [B, S, D]: the
    stack's last hidden state ``h`` (before the final norm) and the
    embedding of each position's next token, each under a norm of its own,
    joined by one projection."""
    m = params["mtp"]
    e = _embed(cfg, params, next_tokens)
    joined = jnp.concatenate([rms_norm(h, m["h_norm"][0], cfg.norm_eps),
                              rms_norm(e, m["e_norm"][0], cfg.norm_eps)], -1)
    return constrain(joined @ m["proj"][0].astype(cfg.dtype),
                     "batch", "sequence", "embed")


@jax.named_scope("seg.mtp")
def _mtp_loss(cfg: TransformerConfig, params, h, targets, positions,
              constrain):
    """The module's loss (DeepSeek-V3, arXiv:2412.19437, section 2.2, depth
    1): position i joins ``h_i`` with the embedding of ``targets_i`` and
    predicts ``targets_{i+1}`` through the model's own table and head, so
    their gradients hold both uses. Between them one causal layer of
    ``mtp_kind`` with weights of its own, a stack of one through
    ``_scan_layers``; it runs over all S positions (the flash tiles stay
    whole), and the last, which has no token after next, is left out of
    the mean: being causal it moves no other. One segment for all of it:
    its norms, the join, its layer, its head pass and its loss."""
    x = _mtp_input(cfg, params, h, targets, constrain)
    g = _scan_layers(cfg, (mtp_kind(cfg),), (params["mtp"]["block"],), x,
                     positions, _attention_dense, None, constrain)
    out_norm = params["mtp"]["out_norm"][0]
    nll = _head_nll(cfg, params, rms_norm(g, out_norm, cfg.norm_eps),
                    jnp.roll(targets, -1, axis=1), constrain)
    return jnp.mean(nll[:, :-1])


def loss_parts(cfg: TransformerConfig, params, tokens, targets,
               mesh=None, rules=None) -> Tuple[jax.Array, Any]:
    """(the next-token loss, the multi-token-prediction module's loss of
    the token after next; None without a module), apart. Of a looped
    stack: (its exit objective, ``_exit_loss``; None)."""
    if cfg.total_ut_steps > 1:
        return _exit_loss(cfg, params, tokens, targets, mesh, rules), None
    x, positions, constrain = _hidden(cfg, params, tokens, mesh, rules)
    main = _final_nll(cfg, params, x, targets, constrain)
    if not cfg.mtp_depth:
        return main, None
    return main, _mtp_loss(cfg, params, x, targets, positions, constrain)


def _exit_loss(cfg: TransformerConfig, params, tokens, targets, mesh,
               rules):
    """A looped stack's objective (Ouro's Stage I, arXiv:2510.25741): the
    mean over positions of ``sum_t p_t l_t + exit_entropy_beta * sum_t p_t
    log p_t``, ``l_t`` the next-token loss of pass t's head and ``p`` the
    exit distribution of the gates (``_exit_log_probs``); no gradient is
    stopped. Each pass's head and loss (``_head_nll``) is checkpointed, so
    of the passes' ``[B, S, V]`` logits one is alive at a time."""
    x, positions, constrain = _stream(cfg, params, tokens, mesh, rules)

    def head_loss(params, x):
        return _head_nll(cfg, params, x, targets, constrain)

    _x, (logits, nll) = _passes(
        cfg, params, x, positions, constrain,
        lambda x: (_exit_logits(params, x),
                   jax.checkpoint(head_loss)(params, x)))
    return _exit_objective(cfg, logits, nll)


@jax.named_scope("seg.exit")
def _exit_objective(cfg: TransformerConfig, logits, nll):
    """The gates' logits and the passes' next-token losses, float32
    [T, B, S] each -> the exit objective of ``_exit_loss``."""
    log_p = _exit_log_probs(logits)
    return jnp.mean(jnp.sum(
        jnp.exp(log_p) * (nll + cfg.exit_entropy_beta * log_p), axis=0))


def loss_fn(cfg: TransformerConfig, params, tokens, targets,
            mesh=None, rules=None) -> jax.Array:
    """The training loss: the next-token loss, and ``mtp_weight`` times
    the module's where the configuration has one; a looped stack's exit
    objective."""
    main, extra = loss_parts(cfg, params, tokens, targets, mesh, rules)
    return main if extra is None else main + cfg.mtp_weight * extra


# ---------------------------------------------------------------------------
# Manual SPMD training step: shard_map over (dp, pp, tp, sp, ep).
# ---------------------------------------------------------------------------

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
    seq % sp == 0.
    """
    import optax

    _refuse_pattern(cfg, "make_spmd_train_step")
    if optimizer is None:
        optimizer = optax.adamw(3e-4)
    shape = mesh_shape(mesh)
    pp, tp, sp_n = shape["pp"], shape["tp"], shape["sp"]
    if cfg.n_layers % pp:
        raise ValueError(f"n_layers {cfg.n_layers} % pp {pp} != 0")
    if cfg.n_heads % tp or cfg.n_kv_heads % tp:
        raise ValueError("heads must divide tp")

    pspec = {
        "embed": P(None, None),
        "layers": _stage_params_spec(cfg),
        "final_norm": P(None),
        "lm_head": P(None, None),
    }
    data_spec = P(("dp", "fsdp"), "sp")

    attention = (partial(_attention_ring, "sp") if sp_n > 1
                 else _attention_dense)
    tp_axis = "tp" if tp > 1 else None

    def stage_fn(stage_layers, act):
        """Run this pp-shard's n_layers // pp layers over activation
        bucket act = (x, positions)."""
        x, positions = act
        x = _scan_layers(cfg, KINDS[:1], (stage_layers,), x, positions,
                         attention, tp_axis, lambda x, *_logical: x)
        return x, positions

    def local_loss(params, tokens, targets):
        """Per-shard loss: tokens [B_local, S_local] (dp×sp sharded)."""
        B, S = tokens.shape
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
                stage_fn, params["layers"], (xs, pos_mb), axis_name="pp")
            x = out.reshape(B, S, -1)
        else:
            x, _ = stage_fn(params["layers"], (x, positions))

        return _final_nll(cfg, params, x, targets, lambda y, *_logical: y)

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


def _cached_layers(cfg: TransformerConfig, params, cache, x, positions,
                   attend, mesh, rules):
    """The cached stack: x [B, S, D] at ``positions`` [B, S] through every
    layer against the paged pools -> (x, the new cache).
    ``attend(idx, q, k, v, ck, cv) -> (o, ck, cv)`` writes layer ``idx``'s
    K/V into the pools and attends over them; the layer index is threaded
    for the pools alone. Projections and feed-forward are the training
    layer's own."""

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
            o, ck, cv = attend(idx, q, k, v, ck, cv)
        x = _attn_out(cfg, lp, x, o)
        x = _mlp_residual(cfg, lp, x)
        return (x, ck, cv), None

    idxs = jnp.arange(cfg.n_layers)
    (x, ck, cv), _ = lax.scan(
        body, (x, cache["k"], cache["v"]), (params["layers"], idxs))
    return x, {"k": ck, "v": cv}


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
    x, cache = _chunk_scan(cfg, params, cache, tokens, start_pos,
                           block_tables, mesh, rules)
    with jax.named_scope("seg.head_loss"):
        last = jnp.take_along_axis(
            x, (chunk_lens - 1)[:, None, None].clip(0), axis=1)[:, 0]
    return _lm_head(cfg, params, last), cache


def _chunk_scan(cfg: TransformerConfig, params, cache, tokens, start_pos,
                block_tables, mesh, rules):
    """Shared multi-token body of ``prefill_chunk`` and ``verify_step``:
    run the chunk through every layer against the paged cache, writing
    each position's K/V before it is attended, and return the final-
    normed hidden states ``[B, C, D]`` plus the updated cache."""
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

    def attend(idx, q, k, v, ck, cv):
        # Write the chunk's K/V, then attend over [0, position] per
        # token — each new slot is part of its own context.
        ck = ck.at[idx, blk, off].set(k)
        cv = cv.at[idx, blk, off].set(v)
        o = paged_attention_prefill(q, ck[idx], cv[idx], block_tables,
                                    positions, mesh=mesh, rules=rules)
        return o, ck, cv

    x, cache = _cached_layers(cfg, params, cache, x, positions, attend,
                              mesh, rules)
    with jax.named_scope("seg.head_loss"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps), cache


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
    x, cache = _chunk_scan(cfg, params, cache, tokens, start_pos,
                           block_tables, mesh, rules)
    return _lm_head(cfg, params, x), cache


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

    def attend(idx, q, k, v, ck, cv):
        # Write THIS token's k/v, then attend over [0, positions] —
        # the new slot is part of its own context (self-attention).
        ck = ck.at[idx, blk, off].set(k[:, 0])
        cv = cv.at[idx, blk, off].set(v[:, 0])
        o = paged_attention_decode(
            q[:, 0], ck[idx], cv[idx], block_tables, context_lens,
            mesh=mesh, rules=rules)
        return o, ck, cv

    x, cache = _cached_layers(cfg, params, cache, x, pos2, attend, mesh,
                              rules)
    return _final_logits(cfg, params, x[:, 0]), cache
