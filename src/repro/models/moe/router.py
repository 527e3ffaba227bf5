"""Router stage: expert scoring, top-k selection, capacity sizing.

Every dispatch implementation starts here -- ``route`` is the single source
of truth for scores, the NAEE dynamic-skipping baseline, and the
load-balancing auxiliary loss, so the implementations stay numerically
interchangeable.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def route(params: Dict, cfg: ModelConfig, x2d, top_k: int, k_budget=None):
    """x2d [T, D] -> (weights [T,k] f32, idx [T,k] i32, aux_loss scalar).

    ``k_budget`` (optional, [T] i32) caps the number of *active* experts per
    token below the static ``top_k``: routed slots at positions >= the token's
    budget get weight exactly 0.0 *before* the top-k renormalization, so a
    token budgeted ``kb`` experts inside a graph traced for ``top_k >= kb``
    produces bitwise the same weights as a graph traced for ``top_k == kb``
    (the zero-weight surplus slots absorb exactly in every combine).  This is
    the contract that lets one bucketed-k serving graph carry heterogeneous
    per-request LExI plans (DESIGN.md §10).
    """
    logits = x2d.astype(jnp.float32) @ params["router"]          # [T, E]
    if cfg.router_type == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(scores, top_k)                  # [T, k]
    if k_budget is not None:
        slot = jnp.arange(top_k, dtype=jnp.int32)[None, :]       # [1, k]
        weights = jnp.where(slot < k_budget[:, None], weights, 0.0)
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    if cfg.dynamic_skip_tau > 0.0 and top_k >= 2:
        # NAEE dynamic skipping baseline: drop low-confidence extra experts
        thresh = cfg.dynamic_skip_tau * weights[:, :1]
        keep = jnp.concatenate(
            [jnp.ones_like(weights[:, :1], bool), weights[:, 1:] >= thresh], 1)
        weights = weights * keep

    # Switch-transformer load-balancing auxiliary loss (used in training).
    e = cfg.num_experts
    me = jnp.mean(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=(0, 1))
    ce = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    aux = e * jnp.sum(me * ce)
    return weights, idx, aux


def routed_experts(params: Dict, cfg: ModelConfig, x2d, top_k: int, live,
                   k_budget=None):
    """Distinct experts the live tokens route to -> i32 scalar.

    ``live`` [T] bool marks the tokens that count (a decode step's
    occupied slots); ``k_budget`` [T] i32, when given, counts only each
    token's first ``k_budget`` routed slots, the ones ``route`` leaves a
    weight.  The ids are ``route``'s own: the same scoring and top-k on
    the same input, which XLA's common-subexpression pass merges with the
    MoE layer's call.  The count is a one-hot against the expert axis,
    masked and any-reduced over tokens and slots: no scatter.
    """
    _, idx, _ = route(params, cfg, x2d, top_k)                   # [T, k]
    use = jnp.broadcast_to(live[:, None], idx.shape)
    if k_budget is not None:
        use = use & (jnp.arange(top_k, dtype=jnp.int32)[None, :]
                     < k_budget[:, None])
    hit = (idx[..., None] == jnp.arange(cfg.num_experts, dtype=idx.dtype)
           ) & use[..., None]                                    # [T, k, E]
    return jnp.sum(jnp.any(hit, axis=(0, 1)), dtype=jnp.int32)


def route_lookahead(params: Dict, cfg: ModelConfig, x2d, top_k: int):
    """Predict this layer's top-k expert ids from the *previous* layer's
    pre-FFN hidden state -> pred_idx [T, k] i32.

    The exact router input (this layer's post-attention normed hidden) is
    not available until the previous layer's FFN and this layer's
    attention have run -- which is precisely the dependency the lookahead
    wants to break.  So the hint scores the previous layer's pre-FFN
    hidden through *this* layer's router instead: residual streams change
    slowly across adjacent layers, so the top-k sets usually agree, and
    the prediction depends only on the scan carry -- the staged weight
    gathers it drives are schedulable before this layer's attention
    (DESIGN.md §7).  Only the id *selection* is replicated from ``route``
    (same scoring function, same ``top_k`` tie-breaking); weights, NAEE
    skipping and the aux loss stay with ``route`` on the true input --
    consumers hit-select staged loads against the true ids, so a miss
    costs a fallback load, never an output change.
    """
    logits = x2d.astype(jnp.float32) @ params["router"]          # [T, E]
    if cfg.router_type == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(scores, top_k)
    return idx


def capacity(t: int, top_k: int, num_experts: int, factor: float) -> int:
    """Per-expert buffer rows for the capacity-based dispatch family."""
    c = int(math.ceil(t * top_k / num_experts * factor))
    return max(4, ((c + 3) // 4) * 4)  # pad to a multiple of 4 lanes
