"""Layer blocks + grouped scan execution.

The layer stack is compiled as ``lax.scan`` over *groups* of consecutive
identical layers (same ``BlockSpec``), so HLO size and compile time are
O(#groups) instead of O(#layers).  A LExI plan that assigns distinct top-k
values across depth simply produces more (smaller) groups -- per-layer k stays
a *static* quantity, which is what lets XLA specialize dispatch shapes.

Zamba2-style ``shared_attn`` blocks share one parameter set (stored once under
``params["shared_attn"]``) but keep per-occurrence KV caches.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import BlockSpec, ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import apply_norm, init_norm, split_keys
from repro.models.mlp import init_mlp, mlp
from repro.models.opts import DEFAULT_OPTS, ModelOpts


# --------------------------------------------------------------------------- #
# Grouping
# --------------------------------------------------------------------------- #


@dataclass(frozen=True)
class Group:
    spec: BlockSpec
    count: int
    start: int   # first layer index


def group_pattern(pattern: Tuple[BlockSpec, ...]) -> List[Group]:
    groups: List[Group] = []
    i = 0
    while i < len(pattern):
        j = i
        while j < len(pattern) and pattern[j] == pattern[i]:
            j += 1
        groups.append(Group(pattern[i], j - i, i))
        i = j
    return groups


# --------------------------------------------------------------------------- #
# Per-layer init / apply
# --------------------------------------------------------------------------- #


def init_block(key, cfg: ModelConfig, spec: BlockSpec) -> Dict:
    ks = split_keys(key, 4)
    if spec.kind == "mamba":
        return {
            "norm1": init_norm(ks[0], cfg),
            "mixer": ssm_mod.init_mamba(ks[1], cfg),
        }
    p = {
        "norm1": init_norm(ks[0], cfg),
        "attn": attn_mod.init_attention(ks[1], cfg),
        "norm2": init_norm(ks[2], cfg),
    }
    if spec.kind == "attn_moe":
        p["moe"] = moe_mod.init_moe(ks[3], cfg)
    else:  # attn_mlp / shared_attn
        p["mlp"] = init_mlp(ks[3], cfg)
    return p


def apply_block(
    params: Dict,
    cfg: ModelConfig,
    spec: BlockSpec,
    x,
    positions,
    *,
    mode: str,
    cache: Optional[Dict],
    mesh=None,
    opts: ModelOpts = DEFAULT_OPTS,
    block_tables=None,
    kernel_blocks: Optional[int] = None,
    lookahead_h2=None,
    k_budget=None,
):
    """Returns (x, new_cache, aux_loss, h2).

    ``h2`` is this block's pre-FFN normed hidden (None for mamba blocks).
    ``apply_stack`` carries it one layer forward when router lookahead is
    on, and ``lookahead_h2`` is that carry: the *previous* layer's h2, from
    which this block predicts its top-k expert ids before its own
    attention output exists (DESIGN.md §7).

    ``k_budget`` [B] i32 caps active experts per batch row below the
    spec's static ``moe_top_k`` via exact zero-weighting in ``route``
    (per-request LExI plans; DESIGN.md §10).
    """
    if mesh is not None and opts.act_constraint:
        # optionally pin activations to batch-over-data at block boundaries
        # (a sharding-layout lever studied in EXPERIMENTS.md §Perf; default
        # off -- measured worse than GSPMD's own propagation)
        from jax.sharding import NamedSharding
        from repro.sharding.rules import batch_spec
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, batch_spec(x.shape, mesh)))
    aux = jnp.zeros((), jnp.float32)
    if spec.kind == "mamba":
        if mode == "chunk":
            raise NotImplementedError(
                "chunked prefill needs conv/state carry across chunks; "
                "mamba blocks use whole-prompt prefill (serving/runner.py)")
        h, new_cache = ssm_mod.mamba_forward(
            params["mixer"], cfg, apply_norm(params["norm1"], cfg, x),
            mode=mode, cache=cache)
        return x + h, new_cache, aux, None

    # Router lookahead: the prediction depends only on the scan carry (the
    # previous layer's pre-FFN hidden), so issuing it *before* this
    # layer's attention makes the staged expert-weight gathers schedulable
    # under the attention compute -- the whole point of the lookahead.
    pred_idx = None
    if lookahead_h2 is not None and spec.kind == "attn_moe":
        d = lookahead_h2.shape[-1]
        pred_idx = moe_mod.route_lookahead(
            params["moe"], cfg, lookahead_h2.reshape(-1, d), spec.moe_top_k)

    attn_kw = {"block_tables": block_tables,
               "use_paged_kernel": opts.use_paged_kernel,
               "kernel_blocks": kernel_blocks}
    if cfg.attention == "mla":
        attn_kw["absorb"] = opts.mla_absorb
    else:
        attn_kw["use_flash"] = opts.use_flash
        attn_kw["compute_dtype"] = opts.attn_compute_dtype
        attn_kw["use_flash_decode"] = opts.use_flash_decode
        if opts.decode_kv_seq_shard and mode == "decode" and mesh is not None:
            attn_kw["seq_shard_mesh"] = mesh
    h, new_cache = attn_mod.attention(
        params["attn"], cfg, apply_norm(params["norm1"], cfg, x), positions,
        mode=mode, cache=cache, **attn_kw)
    x = x + h

    h2 = apply_norm(params["norm2"], cfg, x)
    if spec.kind == "attn_moe":
        impl = opts.moe_impl or cfg.moe_impl
        if mode == "decode" and impl == "ep_a2a":
            impl = "ep_psum"  # a2a dispatch is wrong shape regime for decode
        kb_tok = None
        if k_budget is not None:
            b, s, _ = h2.shape
            kb_tok = jnp.broadcast_to(
                k_budget.astype(jnp.int32)[:, None], (b, s)).reshape(-1)
        y, aux = moe_mod.moe(params["moe"], cfg, h2, spec.moe_top_k,
                             impl=impl, mesh=mesh,
                             use_kernel=opts.use_moe_kernel,
                             a2a_chunks=opts.a2a_chunks,
                             decode_kernel=(opts.use_moe_decode_kernel
                                            and mode == "decode"),
                             expert_dtype=opts.expert_dtype,
                             pred_idx=pred_idx, k_budget=kb_tok)
        x = x + y
    else:
        x = x + mlp(params["mlp"], h2)
    return x, new_cache, aux, h2


# --------------------------------------------------------------------------- #
# Grouped (scanned) stack init / apply
# --------------------------------------------------------------------------- #


def init_stack(key, cfg: ModelConfig) -> Dict:
    """Params for the whole layer stack: {"groups": [...], "shared_attn": ...}."""
    pattern = cfg.pattern()
    groups = group_pattern(pattern)
    out: Dict = {"groups": []}
    keys = split_keys(key, len(groups) + 1)
    if any(g.spec.kind == "shared_attn" for g in groups):
        out["shared_attn"] = init_block(keys[-1], cfg, BlockSpec("shared_attn"))
    for g, k in zip(groups, keys):
        if g.spec.kind == "shared_attn":
            out["groups"].append({})  # weights live in out["shared_attn"]
        elif g.count == 1:
            out["groups"].append(init_block(k, cfg, g.spec))
        else:
            lk = jnp.stack(split_keys(k, g.count))
            out["groups"].append(jax.vmap(lambda kk: init_block(kk, cfg, g.spec))(lk))
    return out


def init_stack_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                     layout: str = "contiguous", page_size: int = 16,
                     num_pages: int = 0):
    """Cache pytree aligned with groups (None entries in train mode).

    ``layout="paged"`` builds per-layer page pools instead of per-slot rows
    (attention blocks only -- mamba state has no position dim to page).
    """
    caches = []
    for g in group_pattern(cfg.pattern()):
        if g.spec.kind == "mamba":
            one = ssm_mod.init_mamba_cache(cfg, batch)
        elif layout == "paged":
            one = attn_mod.init_paged_cache(cfg, num_pages, page_size)
        else:
            one = attn_mod.init_cache(cfg, batch, max_len)
        if g.count == 1:
            caches.append(one)
        else:
            caches.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (g.count, *x.shape)), one))
    return caches


def apply_stack(
    params: Dict,
    cfg: ModelConfig,
    x,
    positions,
    *,
    mode: str,
    caches=None,
    mesh=None,
    opts: ModelOpts = DEFAULT_OPTS,
    block_tables=None,
    kernel_blocks: Optional[int] = None,
    k_budgets=None,
    count_routed: bool = False,
):
    """Run all layer groups.  Returns (x, new_caches, total_aux), and with
    ``count_routed`` a fourth value: ``[n_moe]`` i32, the distinct experts
    each MoE layer routed the live rows (``positions >= 0``) to, within
    each row's budget (``moe.routed_experts``).

    ``k_budgets`` [B, n_moe] i32 gives each batch row a per-MoE-layer
    active-expert cap below the pattern's static per-layer top-k
    (per-request LExI plans, DESIGN.md §10).  Only single-layer groups can
    carry budgets or count routed experts -- serving uses per-layer split
    patterns (``BlockSpec.split_id``), which guarantee that.
    """
    groups = group_pattern(cfg.pattern())
    total_aux = jnp.zeros((), jnp.float32)
    new_caches = []
    routed = []
    use_cache = caches is not None
    lookahead = opts.router_lookahead and mode == "decode"
    moe_layer_i = 0  # running index into k_budgets' layer axis
    # Router lookahead carry: layer i-1's pre-FFN hidden, from which layer
    # i predicts its expert ids before its own attention runs.  Zeros feed
    # the first layer -- its staged loads just miss, which never changes
    # outputs (hit-select against the true ids).
    h2_prev = jnp.zeros_like(x) if lookahead else None

    for gi, g in enumerate(groups):
        gparams = params["groups"][gi]
        gcache = caches[gi] if use_cache else None
        if g.spec.kind == "shared_attn":
            gparams = params["shared_attn"]
        gl = lookahead and g.spec.kind != "mamba"
        g_budget = None
        if g.spec.kind == "attn_moe" and g.count != 1:
            for what, on in (("k_budgets", k_budgets is not None),
                             ("count_routed", count_routed)):
                if on:
                    raise ValueError(
                        f"{what} requires single-layer MoE groups; use a "
                        "per-layer split pattern (BlockSpec.split_id)")
        if k_budgets is not None and g.spec.kind == "attn_moe":
            g_budget = k_budgets[:, moe_layer_i]
        if g.spec.kind == "attn_moe":
            moe_layer_i += g.count

        def one_layer(p_layer, xx, c_layer, h2_in=None, spec=g.spec,
                      kb=g_budget):
            fn = partial(apply_block, cfg=cfg, spec=spec, positions=positions,
                         mode=mode, mesh=mesh, opts=opts,
                         block_tables=block_tables,
                         kernel_blocks=kernel_blocks)
            if opts.remat != "none" and mode == "train":
                fn = _remat(fn, opts)
            return fn(p_layer, x=xx, cache=c_layer, lookahead_h2=h2_in,
                      k_budget=kb)

        if g.count == 1:
            x, nc, aux, h2 = one_layer(gparams, x, gcache,
                                       h2_prev if gl else None)
            if gl:
                h2_prev = h2
            if count_routed and g.spec.kind == "attn_moe":
                routed.append(_routed_count(gparams["moe"], cfg, g.spec,
                                            h2, positions, g_budget))
            new_caches.append(nc)
            total_aux = total_aux + aux
        elif use_cache:
            if gl:
                def body_cl(carry, layer_in, fn=one_layer):
                    p_layer, c_layer = layer_in
                    xx, h2p = carry
                    xx, c_out, aux, h2 = fn(p_layer, xx, c_layer, h2p)
                    return (xx, h2), (c_out, aux)

                (x, h2_prev), (c_stack, auxs) = jax.lax.scan(
                    body_cl, (x, h2_prev), (gparams, gcache),
                    unroll=True if opts.scan_unroll else 1)
            else:
                def body_c(carry, layer_in, fn=one_layer):
                    p_layer, c_layer = layer_in
                    xx, c_out, aux, _ = fn(p_layer, carry, c_layer)
                    return xx, (c_out, aux)

                x, (c_stack, auxs) = jax.lax.scan(
                    body_c, x, (gparams, gcache),
                    unroll=True if opts.scan_unroll else 1)
            new_caches.append(c_stack)
            total_aux = total_aux + jnp.sum(auxs)
        elif (opts.remat_chunk > 1 and mode == "train"
              and g.count > opts.remat_chunk and opts.remat != "none"):
            # two-level chunked remat: checkpoint at chunk boundaries only.
            # Stashes g.count/G layer-boundary activations instead of
            # g.count, at zero extra recompute vs per-layer full remat
            # (EXPERIMENTS.md §Perf cell A).
            G = opts.remat_chunk
            n_main = (g.count // G) * G

            def chunk_body(carry, pchunk, spec=g.spec):
                def inner(c2, p_layer):
                    xx, _, aux, _ = apply_block(p_layer, cfg, spec, c2,
                                                positions, mode=mode,
                                                cache=None, mesh=mesh,
                                                opts=opts)
                    return xx, aux
                xx, auxs = jax.lax.scan(inner, carry, pchunk)
                return xx, jnp.sum(auxs)

            main = jax.tree.map(
                lambda a: a[:n_main].reshape(n_main // G, G, *a.shape[1:]),
                gparams)
            x, auxs = jax.lax.scan(jax.checkpoint(chunk_body), x, main,
                                   unroll=True if opts.scan_unroll else 1)
            total_aux = total_aux + jnp.sum(auxs)
            if n_main < g.count:  # remainder layers: per-layer remat
                rest = jax.tree.map(lambda a: a[n_main:], gparams)

                def body_r(carry, p_layer, fn=one_layer):
                    xx, _, aux, _ = fn(p_layer, carry, None)
                    return xx, aux

                x, auxs = jax.lax.scan(body_r, x, rest,
                                       unroll=True if opts.scan_unroll else 1)
                total_aux = total_aux + jnp.sum(auxs)
            new_caches.append(None)
        else:
            def body_nc(carry, p_layer, fn=one_layer):
                xx, _, aux, _ = fn(p_layer, carry, None)
                return xx, aux

            x, auxs = jax.lax.scan(body_nc, x, gparams,
                                   unroll=True if opts.scan_unroll else 1)
            new_caches.append(None)
            total_aux = total_aux + jnp.sum(auxs)

    out = (x, (new_caches if use_cache else None), total_aux)
    if count_routed:
        out += (jnp.stack(routed) if routed
                else jnp.zeros((0,), jnp.int32),)
    return out


def _routed_count(params, cfg: ModelConfig, spec: BlockSpec, h2, positions,
                  k_budget):
    """Distinct experts one MoE layer routed the live rows to (i32)."""
    b, s, d = h2.shape
    live = jnp.broadcast_to((positions >= 0).reshape(b, -1), (b, s))
    kb = (None if k_budget is None else jnp.broadcast_to(
        k_budget.astype(jnp.int32)[:, None], (b, s)).reshape(-1))
    return moe_mod.routed_experts(params, cfg, h2.reshape(b * s, d),
                                  spec.moe_top_k, live.reshape(-1), kb)


def ungroup_stack(stack_params: Dict, pattern: Tuple[BlockSpec, ...]):
    """Stacked group params -> per-layer param list ('SHARED' markers for
    shared_attn occurrences)."""
    groups = group_pattern(pattern)
    layers: List = [None] * len(pattern)
    for gi, g in enumerate(groups):
        gp = stack_params["groups"][gi]
        if g.spec.kind == "shared_attn":
            for i in range(g.count):
                layers[g.start + i] = "SHARED"
        elif g.count == 1:
            layers[g.start] = gp
        else:
            for i in range(g.count):
                layers[g.start + i] = jax.tree.map(lambda x, i=i: x[i], gp)
    return layers


def regroup_stack(stack_params: Dict, old_pattern: Tuple[BlockSpec, ...],
                  new_pattern: Tuple[BlockSpec, ...]) -> Dict:
    """Restructure stacked params for a new grouping (e.g. a LExI plan that
    splits a uniform MoE stack into runs of distinct per-layer k).

    Layer *kinds* must match position-wise -- only static attributes like
    ``moe_top_k`` (which do not touch parameter shapes) may differ.
    """
    if len(old_pattern) != len(new_pattern):
        raise ValueError("pattern length mismatch")
    for a, b in zip(old_pattern, new_pattern):
        if a.kind != b.kind:
            raise ValueError(f"kind mismatch: {a.kind} vs {b.kind}")
    layers = ungroup_stack(stack_params, old_pattern)
    out: Dict = {"groups": []}
    if "shared_attn" in stack_params:
        out["shared_attn"] = stack_params["shared_attn"]
    for g in group_pattern(new_pattern):
        if g.spec.kind == "shared_attn":
            out["groups"].append({})
        elif g.count == 1:
            out["groups"].append(layers[g.start])
        else:
            chunk = layers[g.start : g.start + g.count]
            out["groups"].append(jax.tree.map(lambda *xs: jnp.stack(xs), *chunk))
    return out


def _remat(fn, opts: ModelOpts):
    if opts.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy, static_argnums=())
    return jax.checkpoint(fn)
