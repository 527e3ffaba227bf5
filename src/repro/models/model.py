"""Unified model API: every arch behind the same five functions.

    init_params(key, cfg)                  -> params pytree
    loss_fn(params, cfg, batch, ...)       -> (loss, metrics)   [train]
    prefill_fn(params, cfg, batch, caches) -> (logits, caches)
    decode_fn(params, cfg, tokens, pos, caches) -> (logits, caches)
        (count_routed=True adds the per-MoE-layer distinct-experts count)
    init_caches(cfg, batch, max_len)       -> cache pytree

The dry-run, trainer, server and benchmarks all go through this module so an
``--arch`` flag is the only thing that changes between architectures.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec as encdec_mod
from repro.models import transformer as tf_mod
from repro.models.opts import DEFAULT_OPTS, ModelOpts


def init_params(key, cfg: ModelConfig) -> Dict:
    if cfg.is_encoder_decoder:
        return encdec_mod.init_encdec(key, cfg)
    return tf_mod.init_lm(key, cfg)


def abstract_params(cfg: ModelConfig):
    """Parameter ShapeDtypeStructs without allocating (dry-run path)."""
    return jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def loss_fn(params, cfg: ModelConfig, batch, *, mesh=None,
            opts: ModelOpts = DEFAULT_OPTS):
    if cfg.is_encoder_decoder:
        return encdec_mod.encdec_loss(params, cfg, batch, mesh=mesh, opts=opts)
    return tf_mod.lm_loss(params, cfg, batch, mesh=mesh, opts=opts)


def init_caches(cfg: ModelConfig, batch: int, max_len: int, *,
                layout: str = "contiguous", page_size: int = 16,
                num_pages: int = 0):
    """Cache pytree.  ``layout="paged"`` builds block-table page pools of
    ``num_pages`` x ``page_size`` positions per attention layer (serving);
    the default contiguous layout is the per-slot-row equivalence oracle."""
    if cfg.is_encoder_decoder:
        if layout != "contiguous":
            raise NotImplementedError("paged KV is decoder-only LM for now")
        return encdec_mod.init_encdec_caches(cfg, batch, max_len)
    return tf_mod.init_caches(cfg, batch, max_len, layout=layout,
                              page_size=page_size, num_pages=num_pages)


def abstract_caches(cfg: ModelConfig, batch: int, max_len: int, **kw):
    return jax.eval_shape(lambda: init_caches(cfg, batch, max_len, **kw))


def prefill_fn(params, cfg: ModelConfig, batch, caches, *, mesh=None,
               opts: ModelOpts = DEFAULT_OPTS):
    """batch: {"tokens": [B,S]} plus optional frames / prefix_embeds."""
    if cfg.is_encoder_decoder:
        return encdec_mod.encdec_prefill(params, cfg, batch["frames"],
                                         batch["tokens"], caches,
                                         mesh=mesh, opts=opts)
    return tf_mod.prefill(params, cfg, batch["tokens"], caches,
                          positions=batch.get("positions"),
                          prefix_embeds=batch.get("prefix_embeds"),
                          mesh=mesh, opts=opts)


def decode_fn(params, cfg: ModelConfig, tokens, pos, caches, *, mesh=None,
              opts: ModelOpts = DEFAULT_OPTS, block_tables=None,
              kernel_blocks=None, k_budgets=None, count_routed: bool = False):
    if cfg.is_encoder_decoder:
        out = encdec_mod.encdec_decode_step(params, cfg, tokens, pos, caches,
                                            mesh=mesh, opts=opts)
        # the decoder stack has no MoE layers to count
        return out + ((jnp.zeros((0,), jnp.int32),) if count_routed else ())
    return tf_mod.decode_step(params, cfg, tokens, pos, caches,
                              mesh=mesh, opts=opts, block_tables=block_tables,
                              kernel_blocks=kernel_blocks,
                              k_budgets=k_budgets, count_routed=count_routed)


def chunk_prefill_fn(params, cfg: ModelConfig, tokens, positions, caches, *,
                     last_index=None, block_tables=None, mesh=None,
                     opts: ModelOpts = DEFAULT_OPTS, k_budgets=None):
    """One fixed-width chunked-prefill step (decoder-only LMs)."""
    if cfg.is_encoder_decoder:
        raise NotImplementedError("chunked prefill is decoder-only LM for now")
    return tf_mod.chunk_prefill(params, cfg, tokens, caches,
                                positions=positions, last_index=last_index,
                                block_tables=block_tables, mesh=mesh,
                                opts=opts, k_budgets=k_budgets)


# --------------------------------------------------------------------------- #
# Synthetic batch builders (shapes only -- see launch/dryrun for specs)
# --------------------------------------------------------------------------- #


def make_train_batch(cfg: ModelConfig, key, batch: int, seq: int) -> Dict:
    """Concrete random batch for smoke tests / examples."""
    ks = jax.random.split(key, 3)
    out = {
        "tokens": jax.random.randint(ks[0], (batch, seq), 0, cfg.vocab_size),
        "targets": jax.random.randint(ks[1], (batch, seq), 0, cfg.vocab_size),
        "mask": jnp.ones((batch, seq), jnp.int32),
    }
    if cfg.is_encoder_decoder:
        out["frames"] = jax.random.normal(
            ks[2], (batch, cfg.encoder_seq_len, cfg.d_model), jnp.float32)
    elif cfg.prefix_embed_len:
        out["prefix_embeds"] = jax.random.normal(
            ks[2], (batch, cfg.prefix_embed_len, cfg.d_model), jnp.float32)
    return out
