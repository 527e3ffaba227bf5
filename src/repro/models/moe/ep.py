"""Expert-parallel impls (shard_map): ``ep_a2a`` (train/prefill) and
``ep_psum`` (decode).

``ep_a2a``: tokens sharded over (pod, data, model), experts sharded over
``model``.  Scatter into per-expert capacity buffers, ``all_to_all`` over
the model axis, grouped expert FFN, a2a back, weighted combine.  Collective
bytes scale with sum_j k_j -- a LExI plan buys communication, not just FLOPs.

``ep_psum``: activations replicated over ``model``, each device computes
only its local experts' contribution, partial outputs are ``psum``-reduced.
The right pattern when T (= decode batch) is small.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.moe.compute import add_shared, expert_ffn
from repro.models.moe.dispatch import _gather_combine, _scatter, _slot_positions
from repro.models.moe.router import capacity, route


def moe_ep_a2a_local(params, cfg: ModelConfig, x_local, top_k: int, *,
                     model_axis: str, model_size: int, all_axes,
                     use_kernel: bool = False, a2a_chunks: int = 1):
    """shard_map body.  x_local [T_loc, D]; expert params sliced [E_loc,...]."""
    e = cfg.num_experts
    e_loc = e // model_size
    t_loc, d = x_local.shape
    cap = capacity(t_loc, top_k, e, cfg.moe_capacity_factor)

    weights, idx, aux = route(params, cfg, x_local, top_k)
    pos, keep = _slot_positions(idx, e, cap)
    buf = _scatter(x_local, idx, pos, keep, e, cap)               # [E,C,D]
    buf = buf.reshape(model_size, e_loc, cap, d)

    def run_chunk(b):
        # b [ms, E_loc, C', D] -> recv indexed by source shard on axis 0
        recv = jax.lax.all_to_all(b, model_axis, split_axis=0, concat_axis=0)
        xe = recv.transpose(1, 0, 2, 3).reshape(e_loc, model_size * b.shape[2], d)
        ye = expert_ffn(params["w1"], params["w2"], xe, use_kernel)
        ye = ye.reshape(e_loc, model_size, b.shape[2], d).transpose(1, 0, 2, 3)
        return jax.lax.all_to_all(ye, model_axis, split_axis=0, concat_axis=0)

    if a2a_chunks > 1 and cap % a2a_chunks == 0:
        # split the capacity dim so XLA can overlap a2a with expert GEMMs
        parts = jnp.split(buf, a2a_chunks, axis=2)
        back = jnp.concatenate([run_chunk(b) for b in parts], axis=2)
    else:
        back = run_chunk(buf)

    ye_local = back.reshape(e, cap, d)
    y = _gather_combine(ye_local, weights, idx, pos, keep, cap).astype(x_local.dtype)
    y = add_shared(params, cfg, x_local, y)
    return y, jax.lax.pmean(aux, all_axes)


def moe_ep_psum_local(params, cfg: ModelConfig, x_rep, top_k: int, *,
                      model_axis: str, model_size: int, token_axes,
                      use_kernel: bool = False):
    """shard_map body for decode: ``x_rep`` [T, D] replicated over model axis;
    expert params sliced [E_loc, ...].  Local contributions + psum."""
    e = cfg.num_experts
    e_loc = e // model_size
    midx = jax.lax.axis_index(model_axis)
    t, d = x_rep.shape

    weights, idx, aux = route(params, cfg, x_rep, top_k)
    lo = midx * e_loc
    local = (idx >= lo) & (idx < lo + e_loc)                      # [T, k]
    idx_loc = jnp.where(local, idx - lo, e_loc)                   # non-local -> trash
    w_loc = jnp.where(local, weights, 0.0)

    # worst case: all T*k slots land on one local expert -> cap = T*k is always
    # safe; keep it tighter with the same global-capacity heuristic.
    cap = capacity(t, top_k, e_loc, cfg.moe_capacity_factor)
    pos, keep = _slot_positions(idx_loc, e_loc + 1, cap)
    keep = keep & local
    xe = _scatter(x_rep, idx_loc, pos, keep, e_loc + 1, cap)[:e_loc]
    ye = expert_ffn(params["w1"], params["w2"], xe, use_kernel)
    ye_pad = jnp.concatenate([ye, jnp.zeros((1, cap, d), ye.dtype)], axis=0)
    y = _gather_combine(ye_pad, w_loc, idx_loc, pos, keep, cap)
    y = jax.lax.psum(y, model_axis).astype(x_rep.dtype)
    y = add_shared(params, cfg, x_rep, y)
    # aux is invariant over the model axis (same routing on every model
    # shard): reduce over the token axes only
    if token_axes:
        aux = jax.lax.pmean(aux, token_axes)
    return y, aux


def _ep_param_specs(params, model_axis: str):
    specs = {
        "router": P(None, None),
        "w1": P(model_axis, None, None),
        "w2": P(model_axis, None, None),
    }
    if "shared" in params:
        specs["shared"] = {"w1": P(None, None), "w2": P(None, None)}
    return specs


def moe_ep_a2a(params: Dict, cfg: ModelConfig, x2d, top_k: int, *, mesh,
               use_kernel: bool = False, a2a_chunks: int = 1):
    """shard_map wrapper for ``moe_ep_a2a_local`` over a (…, model) mesh."""
    all_axes = tuple(mesh.axis_names)
    model_axis = "model"
    model_size = mesh.shape[model_axis]
    token_axes = tuple(a for a in all_axes if a != model_axis)
    body = partial(moe_ep_a2a_local, cfg=cfg, top_k=top_k,
                   model_axis=model_axis, model_size=model_size,
                   all_axes=all_axes, use_kernel=use_kernel,
                   a2a_chunks=a2a_chunks)
    return jax.shard_map(
        lambda p, xx: body(p, x_local=xx),
        mesh=mesh,
        in_specs=(_ep_param_specs(params, model_axis),
                  P((*token_axes, model_axis), None)),
        out_specs=(P((*token_axes, model_axis), None), P()),
    )(params, x2d)


def moe_ep_psum(params: Dict, cfg: ModelConfig, x2d, top_k: int, *, mesh,
                use_kernel: bool = False):
    """shard_map wrapper for ``moe_ep_psum_local`` over a (…, model) mesh."""
    all_axes = tuple(mesh.axis_names)
    model_axis = "model"
    model_size = mesh.shape[model_axis]
    token_axes = tuple(a for a in all_axes if a != model_axis)
    body = partial(moe_ep_psum_local, cfg=cfg, top_k=top_k,
                   model_axis=model_axis, model_size=model_size,
                   token_axes=token_axes, use_kernel=use_kernel)
    return jax.shard_map(
        lambda p, xx: body(p, x_rep=xx),
        mesh=mesh,
        in_specs=(_ep_param_specs(params, model_axis), P(token_axes, None)),
        out_specs=(P(token_axes, None), P()),
    )(params, x2d)
