"""Draft-model helpers for speculative decoding (reference role: the
draft/target pairing in speculative-decoding serving stacks — a small
cheap model proposes k tokens, the flagship verifies them in one
multi-token step; see ``llm/engine.py``'s spec-decode path).

Two pieces:

- ``draft_config``: derive a shrunk ``TransformerConfig`` from the
  flagship's (same vocab — proposals must be scoreable by the flagship
  — fewer layers, narrower residual stream). Any field can be pinned
  via overrides; divisibility (d_model % n_heads, n_heads % n_kv_heads)
  is the caller's contract, as with any TransformerConfig.
- ``shift_params``: a SYNTHETIC deterministic parameterization whose
  greedy next token is exactly ``(t + shift) % vocab_size`` for last
  token ``t``, on ANY config with ``d_model >= vocab_size``. Zero
  attention/MLP weights make every layer an identity residual update
  (zero q/k/v -> uniform softmax over zero values -> zero output; zero
  MLP -> zero), a one-hot embedding carries the token through the
  residual stream, and a shift-permutation lm_head reads it back out.
  Because the rule depends only on the last token — not on width or
  depth — a shift-params draft and a shift-params flagship agree
  token-for-token by construction: the deterministic ~1.0-acceptance
  workload the spec-decode bench and tests measure against (honestly
  disclosed as synthetic; real model pairs land wherever their
  distributional agreement puts them).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.transformer import TransformerConfig, init_params

__all__ = ["draft_config", "shift_params"]


def draft_config(base: TransformerConfig, **overrides
                 ) -> TransformerConfig:
    """A small draft config derived from the flagship's: same vocab and
    context window, half the depth/width by default (floored so tiny
    test configs stay valid). Overrides win field-by-field."""
    small: Dict[str, Any] = dict(
        n_layers=max(1, base.n_layers // 2),
        d_model=max(32, base.d_model // 2),
        n_heads=max(1, base.n_heads // 2),
        n_kv_heads=max(1, base.n_kv_heads // 2),
        d_ff=max(32, base.d_ff // 2),
        head_dim=None,      # its own d_model // n_heads, not the base's
    )
    small.update(overrides)
    return dataclasses.replace(base, **small)


def shift_params(cfg: TransformerConfig, shift: int = 1) -> Dict[str, Any]:
    """Parameters realizing greedy next == ``(last_token + shift) %
    vocab`` exactly (see module docstring). Requires ``d_model >=
    vocab_size`` so the one-hot embedding fits the residual stream."""
    if cfg.d_model < cfg.vocab_size:
        raise ValueError(
            f"shift_params needs d_model ({cfg.d_model}) >= vocab_size "
            f"({cfg.vocab_size}) for the one-hot embedding")
    params = init_params(cfg, jax.random.PRNGKey(0))
    # Zero every layer weight, keep every norm gain at one: each layer
    # becomes x -> x (attention output and MLP both exactly zero).
    layers = {}
    for name, arr in params["layers"].items():
        if name.endswith("norm"):
            layers[name] = jnp.ones_like(arr)
        else:
            layers[name] = jnp.zeros_like(arr)
    params["layers"] = layers
    # One-hot embed: token t -> e_t in the first vocab dims. final_norm
    # of ones rescales positively per row, preserving the argmax.
    embed = jnp.zeros((cfg.vocab_size, cfg.d_model), cfg.dtype)
    embed = embed.at[jnp.arange(cfg.vocab_size),
                     jnp.arange(cfg.vocab_size)].set(1.0)
    params["embed"] = embed
    params["final_norm"] = jnp.ones_like(params["final_norm"])
    # Shift-permutation readout: logits[v] = x[(v - shift) % vocab], so
    # the single positive residual dim t votes for (t + shift) % vocab.
    head = jnp.zeros((cfg.d_model, cfg.vocab_size), cfg.dtype)
    head = head.at[jnp.arange(cfg.vocab_size),
                   (jnp.arange(cfg.vocab_size) + shift)
                   % cfg.vocab_size].set(1.0)
    params["lm_head"] = head
    return params
