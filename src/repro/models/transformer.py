"""Decoder-only LM assembly: embeddings, layer stack, head, losses, steps.

Supports the plain LM, the VLM variant (precomputed patch embeddings
concatenated ahead of the token embeddings -- frontend stub per assignment),
and exposes train / prefill / decode entry points used by the launcher,
serving engine and dry-run.

Decode steps with ``opts.router_lookahead`` carry each layer's pre-FFN
hidden one layer forward through the stack scan: layer i's expert ids are
predicted from layer i-1's carry *before* layer i's attention, so staged
expert-weight loads no longer serialize behind the router (hit-selected
against the true ids -- numerically exact; models/blocks.py, DESIGN.md §7).
``opts.expert_dtype`` selects int8/int4 expert-tile storage with in-kernel
dequant on the gmm/decode MoE paths.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks as blocks_mod
from repro.models.common import (
    apply_norm,
    dense_init,
    embed_init,
    init_norm,
    param_dtype,
    split_keys,
)
from repro.models.opts import DEFAULT_OPTS, ModelOpts


# --------------------------------------------------------------------------- #
# Init
# --------------------------------------------------------------------------- #


def init_lm(key, cfg: ModelConfig) -> Dict:
    ks = split_keys(key, 4)
    dt = param_dtype(cfg)
    p: Dict = {
        "embed": embed_init(ks[0], (cfg.padded_vocab, cfg.d_model), dt),
        "stack": blocks_mod.init_stack(ks[1], cfg),
        "final_norm": init_norm(ks[2], cfg),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[3], (cfg.d_model, cfg.padded_vocab), dt)
    if cfg.prefix_embed_len:
        p["prefix_proj"] = dense_init(ks[3], (cfg.d_model, cfg.d_model), dt)
    return p


# --------------------------------------------------------------------------- #
# Forward pieces
# --------------------------------------------------------------------------- #


def embed_tokens(params, cfg: ModelConfig, tokens):
    return jnp.take(params["embed"], tokens, axis=0)


def lm_logits(params, cfg: ModelConfig, x):
    x = apply_norm(params["final_norm"], cfg, x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head).astype(jnp.float32)


def forward(
    params: Dict,
    cfg: ModelConfig,
    tokens,
    positions,
    *,
    mode: str = "train",
    caches=None,
    prefix_embeds=None,
    mesh=None,
    opts: ModelOpts = DEFAULT_OPTS,
    block_tables=None,
    kernel_blocks=None,
    k_budgets=None,
    count_routed: bool = False,
):
    """tokens [B,S]; positions [B,S] (train/prefill/chunk) or [B] (decode).

    Returns (hidden [B,S,D], new_caches, aux_loss), and with
    ``count_routed`` the ``[n_moe]`` distinct-experts count of
    ``blocks.apply_stack``.  ``k_budgets``
    [B, n_moe] i32 caps per-row active experts below the pattern's static
    per-layer top-k (per-request LExI plans; DESIGN.md §10).
    """
    x = embed_tokens(params, cfg, tokens)
    if prefix_embeds is not None:
        pre = prefix_embeds.astype(x.dtype) @ params["prefix_proj"]
        x = jnp.concatenate([pre, x], axis=1)
    return blocks_mod.apply_stack(
        params["stack"], cfg, x, positions, mode=mode, caches=caches,
        mesh=mesh, opts=opts, block_tables=block_tables,
        kernel_blocks=kernel_blocks, k_budgets=k_budgets,
        count_routed=count_routed)


# --------------------------------------------------------------------------- #
# Training loss
# --------------------------------------------------------------------------- #


def softmax_xent(logits, targets, mask):
    """logits [B,S,V] f32, targets [B,S] i32, mask [B,S] {0,1}."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(nll) / denom


def lm_loss(
    params: Dict,
    cfg: ModelConfig,
    batch: Dict,
    *,
    mesh=None,
    opts: ModelOpts = DEFAULT_OPTS,
    aux_coef: float = 0.01,
):
    """batch: tokens [B,S], targets [B,S], mask [B,S], opt. prefix_embeds."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    pre = batch.get("prefix_embeds")
    plen = pre.shape[1] if pre is not None else 0
    positions = jnp.broadcast_to(jnp.arange(s + plen)[None], (b, s + plen))
    hidden, _, aux = forward(params, cfg, tokens, positions, mode="train",
                             prefix_embeds=pre, mesh=mesh, opts=opts)
    hidden = hidden[:, plen:]                         # loss on token part only
    logits = lm_logits(params, cfg, hidden)
    xent = softmax_xent(logits, batch["targets"], batch["mask"].astype(jnp.float32))
    loss = xent + aux_coef * aux
    return loss, {"xent": xent, "aux": aux}


# --------------------------------------------------------------------------- #
# Inference steps
# --------------------------------------------------------------------------- #


def init_caches(cfg: ModelConfig, batch: int, max_len: int, *,
                layout: str = "contiguous", page_size: int = 16,
                num_pages: int = 0):
    return blocks_mod.init_stack_cache(cfg, batch, max_len, layout=layout,
                                       page_size=page_size,
                                       num_pages=num_pages)


def prefill(
    params: Dict,
    cfg: ModelConfig,
    tokens,
    caches,
    *,
    positions=None,
    prefix_embeds=None,
    mesh=None,
    opts: ModelOpts = DEFAULT_OPTS,
):
    """Populate caches with a full prompt.  Returns (last_logits [B,V], caches).

    ``positions`` may carry -1 for pad tokens: they are masked out of
    attention (the position-based bias treats pos<0 as invalid) and their
    cache writes land on an already-masked trash slot.
    """
    b, s = tokens.shape
    plen = prefix_embeds.shape[1] if prefix_embeds is not None else 0
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s + plen)[None], (b, s + plen))
    hidden, caches, _ = forward(params, cfg, tokens, positions, mode="prefill",
                                caches=caches, prefix_embeds=prefix_embeds,
                                mesh=mesh, opts=opts)
    logits = lm_logits(params, cfg, hidden[:, -1:])[:, 0]
    return logits, caches


def chunk_prefill(
    params: Dict,
    cfg: ModelConfig,
    tokens,        # [B, C] one fixed-width chunk per slot
    caches,
    *,
    positions,     # [B, C] absolute positions; -1 = pad / idle row
    last_index=None,   # [B] in-chunk index of each row's final prompt token
    block_tables=None,
    mesh=None,
    opts: ModelOpts = DEFAULT_OPTS,
    k_budgets=None,
):
    """One chunked-prefill step over all slots.  Returns (logits [B,V], caches).

    Every prompt runs through the same ``[B, C]`` graph regardless of its
    length: the chunk's K/V are committed to the cache, then the chunk
    queries attend against the whole cache (prior chunks included).  The
    returned logits are taken at ``last_index`` per row (clipped, so rows
    that have not finished their prompt return ignorable values).
    """
    hidden, caches, _ = forward(params, cfg, tokens, positions, mode="chunk",
                                caches=caches, mesh=mesh, opts=opts,
                                block_tables=block_tables,
                                k_budgets=k_budgets)
    if last_index is None:
        sel = hidden[:, -1]
    else:
        idx = jnp.clip(last_index, 0, hidden.shape[1] - 1)
        sel = jnp.take_along_axis(hidden, idx[:, None, None], axis=1)[:, 0]
    logits = lm_logits(params, cfg, sel[:, None])[:, 0]
    return logits, caches


def decode_step(
    params: Dict,
    cfg: ModelConfig,
    tokens,        # [B] current token ids
    pos,           # [B] absolute positions of those tokens
    caches,
    *,
    mesh=None,
    opts: ModelOpts = DEFAULT_OPTS,
    block_tables=None,
    kernel_blocks=None,
    k_budgets=None,
    count_routed: bool = False,
):
    """One decode step.  Returns (logits [B,V] f32, updated caches), and
    with ``count_routed`` a third value: ``[n_moe]`` i32, the distinct
    experts each MoE layer routed the live slots (``pos >= 0``) to.

    ``kernel_blocks`` statically bounds the paged-kernel table walk to the
    live-page bucket (ignored by the gather path)."""
    hidden, caches, _, *routed = forward(
        params, cfg, tokens[:, None], pos, mode="decode", caches=caches,
        mesh=mesh, opts=opts, block_tables=block_tables,
        kernel_blocks=kernel_blocks, k_budgets=k_budgets,
        count_routed=count_routed)
    logits = lm_logits(params, cfg, hidden)[:, 0]
    return (logits, caches, *routed)
