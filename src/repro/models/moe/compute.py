"""Compute stage: expert SwiGLU FFN over each dispatch layout.

``expert_ffn`` consumes the capacity-buffer layout ``[E, C, D]``;
``grouped_ffn`` consumes the sorted dropless layout ``[M, D]`` described by
a ``SortPlan``.  Both have a pure-jnp path (CPU / profiling / autodiff
through XLA) and a Pallas kernel path selected by ``use_kernel``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.mlp import mlp
from repro.models.moe.dispatch import SortPlan


def expert_ffn(w1, w2, xe, use_kernel: bool = False):
    """xe [E, C, D] -> [E, C, D] (SwiGLU per expert, capacity layout)."""
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.moe_ffn(xe, w1, w2)
    h = jnp.einsum("ecd,edf->ecf", xe, w1)
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", h, w2)


def grouped_ffn(w1, w2, xs, plan: SortPlan, use_kernel: bool = False):
    """xs [M, D] sorted-by-expert -> [M, D] (padding rows stay zero).

    Kernel path: the plan-aware ragged grouped-matmul Pallas kernel walks
    row tiles via the prefetched ``tile_expert`` map and skips empty tiles.
    jnp path: the same tile decomposition as a batched matmul with per-tile
    gathered weights -- O(M*D*F) like the kernel (``lax.ragged_dot`` would
    be the obvious spelling but lowers to an O(M*E*D*F) masked dot on CPU).
    Padding rows are zero and SwiGLU(0)*0 @ w2 == 0, so no masking is
    needed in either path.

    The jnp path rounds where the kernels do (``kernels/moe_gmm.py``
    ``swiglu_tile``): dots accumulate in f32, SwiGLU runs in f32, ``h``
    is cast to the weights' dtype before the down-projection, and the f32
    result goes to the combine unrounded -- so on the chip it is the
    kernels' oracle to f32 summation order, not to bf16 rounding.
    """
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.moe_gmm(xs, w1, w2, plan.tile_expert, plan.tile_valid,
                            block_m=plan.block_m)
    m, d = xs.shape
    xt = xs.reshape(-1, plan.block_m, d)              # [n_tiles, bm, D]
    h = jnp.einsum("tbd,tdf->tbf", xt, w1[plan.tile_expert],
                   preferred_element_type=jnp.float32)
    gate, up = jnp.split(h, 2, axis=-1)
    h = (jax.nn.silu(gate) * up).astype(w2.dtype)
    yt = jnp.einsum("tbf,tfd->tbd", h, w2[plan.tile_expert],
                    preferred_element_type=jnp.float32)
    return yt.reshape(m, d)


def routed_ffn(w1, w2, x2d, idx, weights, use_kernel: bool = False,
               pred_idx=None):
    """x2d [T, D] + routing (idx, weights) [T, k] -> combined [T, D].

    The routed per-token layout: no token movement at all -- each token's k
    expert ids drive the weight access directly, and the router-weighted
    combine is fused with the expert SwiGLU (f32 accumulation, like
    ``sort_combine``).  Kernel path: the fused decode kernel DMAs each
    routed expert's weight tiles via scalar prefetch (jnp gather fallback
    off-TPU).  jnp path: the same gather-and-contract spelled inline.
    ``pred_idx`` [T, k] (router lookahead) stages the gather paths' weight
    loads on ids predicted one layer ahead -- numerically a no-op.
    """
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.moe_decode(x2d, w1, w2, idx, weights, pred_idx)
    from repro.kernels.moe_decode import moe_decode_routed_jnp
    return moe_decode_routed_jnp(x2d, w1, w2, idx, weights, pred_idx)


def quant_leaves(params: Dict, expert_dtype: str):
    """(w1q, w2q, s1, s2) from a quantized MoE layer dict, with a clear
    error when the params were never quantized (the opts/engine contract
    is quantize-at-load; hitting raw weights here is a wiring bug)."""
    if "w1_scale" not in params:
        raise ValueError(
            f"expert_dtype={expert_dtype!r} needs quantized params: run "
            "models.moe.quantize_expert_params (Engine(expert_dtype=...) "
            "does this at load)")
    return (params["w1"], params["w2"], params["w1_scale"],
            params["w2_scale"])


def routed_ffn_quant(params: Dict, x2d, idx, weights,
                     use_kernel: bool = False, *, expert_dtype: str,
                     pred_idx=None):
    """``routed_ffn`` over int8-stored expert tiles (in-kernel dequant on
    the kernel path, dequant-after-gather on the jnp path)."""
    w1q, w2q, s1, s2 = quant_leaves(params, expert_dtype)
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.moe_decode_quant(x2d, w1q, w2q, s1, s2, idx, weights,
                                     pred_idx, dtype=expert_dtype)
    from repro.kernels.moe_decode import moe_decode_routed_quant_jnp
    return moe_decode_routed_quant_jnp(x2d, w1q, w2q, s1, s2, idx, weights,
                                       dtype=expert_dtype,
                                       pred_idx=pred_idx)


def grouped_ffn_quant(params: Dict, xs, plan: SortPlan,
                      use_kernel: bool = False, *, expert_dtype: str):
    """``grouped_ffn`` over int8-stored expert tiles.

    Kernel path: the quantized ragged kernel dequantizes tiles in VMEM
    (scale rows ride the same ``tile_expert`` prefetch).  jnp path: the
    per-tile weight gather moves int8 (int4: packed) copies and the scale
    multiplies sit where the kernel puts them -- s1 after the w1 dot, s2
    folded into h before the w2 dot.
    """
    w1q, w2q, s1, s2 = quant_leaves(params, expert_dtype)
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.moe_gmm_quant(xs, w1q, w2q, s1, s2, plan.tile_expert,
                                  plan.tile_valid, dtype=expert_dtype,
                                  block_m=plan.block_m)
    m, d = xs.shape
    f = w2q.shape[1]
    w1g = w1q[plan.tile_expert]                   # [n_tiles, D(p), 2F] int8
    w2g = w2q[plan.tile_expert]                   # [n_tiles, F, D(p)] int8
    s1g = s1[plan.tile_expert]                    # [n_tiles, 2, F] f32
    s2g = s2[plan.tile_expert]                    # [n_tiles, F] f32
    if expert_dtype == "int4":
        from repro.models.moe.params import unpack_int4
        w1g = unpack_int4(w1g, axis=1)
        w2g = unpack_int4(w2g, axis=2)
    xt = xs.reshape(-1, plan.block_m, d).astype(jnp.float32)
    h = jnp.einsum("tbd,tdf->tbf", xt, w1g.astype(jnp.float32))
    h = h.reshape(h.shape[0], plan.block_m, 2, f) * s1g[:, None]
    h = jax.nn.silu(h[:, :, 0, :]) * h[:, :, 1, :] * s2g[:, None]
    yt = jnp.einsum("tbf,tfd->tbd", h, w2g.astype(jnp.float32))
    return yt.reshape(m, d).astype(xs.dtype)


def add_shared(params: Dict, cfg: ModelConfig, x2d, y):
    """Always-on shared experts (Qwen/DeepSeek) on top of the routed output."""
    if cfg.num_shared_experts:
        y = y + mlp(params["shared"], x2d)
    return y
