"""jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels execute with ``interpret=True`` (or, for the
two decode kernels, a jnp path with identical semantics) -- the kernel body
runs in Python for correctness validation; on TPU the same code lowers to
Mosaic.  Any other backend is refused rather than silently interpreted.
Model code calls these wrappers, never pallas_call directly.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_ffn import moe_ffn_pallas


def _interpret() -> bool:
    """True on the CPU backend (interpret / jnp branch), False on TPU."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels run on TPU (Mosaic) or CPU "
                           f"(interpret); backend {backend!r} is neither")
    return backend == "cpu"


@partial(jax.jit, static_argnames=("block_c", "block_f"))
def moe_ffn(xe, w1, w2, *, block_c: int = 128, block_f: int = 256):
    """Grouped expert SwiGLU FFN: xe [E,C,D], w1 [E,D,2F], w2 [E,F,D]."""
    return moe_ffn_pallas(xe, w1, w2, block_c=block_c, block_f=block_f,
                          interpret=_interpret())


@partial(jax.jit, static_argnames=("block_m", "block_f"))
def moe_gmm(xs, w1, w2, tile_expert, tile_valid, *, block_m: int,
            block_f: int = 256):
    """Ragged grouped SwiGLU over a tile-aligned sorted buffer.

    xs [M, D] (M = n_tiles*block_m), w1 [E, D, 2F], w2 [E, F, D],
    tile_expert/tile_valid [n_tiles] i32 -> [M, D].
    """
    from repro.kernels.moe_gmm import moe_gmm_pallas
    return moe_gmm_pallas(xs, w1, w2, tile_expert, tile_valid,
                          block_m=block_m, block_f=block_f,
                          interpret=_interpret())


@jax.jit
def moe_decode(x, w1, w2, idx, weights, pred_idx=None):
    """Fused routed-expert decode MoE: x [B, D], w1 [E, D, 2F], w2 [E, F, D],
    idx [B, k] i32, weights [B, k] -> [B, D].

    On TPU this is the Mosaic kernel DMA'ing each routed expert's weight
    tiles via scalar-prefetched ids (no sort plan, no packed buffer).
    Off-TPU it runs the jnp gather path with *identical semantics* instead
    of the interpreted kernel: interpret-mode grid iteration pays Python
    per (token, slot, f-step) cell, while the gather is one fused XLA op.
    The kernel body itself is validated in interpret mode by
    tests/test_moe_decode.py.

    ``pred_idx`` (router lookahead) stages the fallback's gathers on ids
    predicted one layer ahead, hit-selected against the true ids -- a
    numeric no-op that reorders dependencies.  The kernel path ignores it:
    its DMA is driven by the true scalar-prefetched ids.
    """
    from repro.kernels.moe_decode import moe_decode_pallas, \
        moe_decode_routed_jnp
    if _interpret():
        return moe_decode_routed_jnp(x, w1, w2, idx, weights, pred_idx)
    return moe_decode_pallas(x, w1, w2, idx, weights, interpret=False)


@partial(jax.jit, static_argnames=("dtype", "block_f"))
def moe_decode_quant(x, w1q, w2q, s1, s2, idx, weights, pred_idx=None, *,
                     dtype: str, block_f: int = 256):
    """Quantized fused routed-expert decode MoE (in-kernel dequant).

    x [B, D]; w1q/w2q int8 tiles (int4: packed along D); s1 [E, 2, F] /
    s2 [E, F] f32 scale rows -> [B, D].  Backend selection mirrors
    ``moe_decode``: the Mosaic kernel dequantizes tiles in VMEM on TPU;
    off-TPU the dequant-after-gather jnp path runs the same math (and it
    is the only consumer of ``pred_idx``).
    """
    from repro.kernels.moe_decode import moe_decode_quant_pallas, \
        moe_decode_routed_quant_jnp
    if _interpret():
        return moe_decode_routed_quant_jnp(x, w1q, w2q, s1, s2, idx,
                                           weights, dtype=dtype,
                                           pred_idx=pred_idx)
    return moe_decode_quant_pallas(x, w1q, w2q, s1, s2, idx, weights,
                                   dtype=dtype, block_f=block_f,
                                   interpret=False)


@partial(jax.jit, static_argnames=("dtype", "block_m", "block_f"))
def moe_gmm_quant(xs, w1q, w2q, s1, s2, tile_expert, tile_valid, *,
                  dtype: str, block_m: int, block_f: int = 256):
    """Quantized ragged grouped SwiGLU over a tile-aligned sorted buffer.

    Same tile walk as ``moe_gmm`` with int8-stored expert tiles and their
    scale rows DMA'd by the same prefetched ``tile_expert`` map.
    """
    from repro.kernels.moe_gmm import moe_gmm_quant_pallas
    return moe_gmm_quant_pallas(xs, w1q, w2q, s1, s2, tile_expert,
                                tile_valid, dtype=dtype, block_m=block_m,
                                block_f=block_f, interpret=_interpret())


@partial(jax.jit, static_argnames=("window", "block_q", "block_k"))
def flash_attention_bhsd(q, k, v, *, window=None, block_q: int = 512,
                         block_k: int = 512):
    """Causal flash attention in [B, H, S, hd] layout."""
    return flash_attention_pallas(q, k, v, window=window, block_q=block_q,
                                  block_k=block_k, interpret=_interpret())


@partial(jax.jit, static_argnames=("window", "block_k"))
def flash_decode(q, k, v, pos, cur_pos, *, window=None, block_k: int = 512):
    """One-token decode attention over a position-masked KV cache."""
    from repro.kernels.flash_decode import flash_decode_pallas
    return flash_decode_pallas(q, k, v, pos, cur_pos, window=window,
                               block_k=block_k, interpret=_interpret())


@partial(jax.jit, static_argnames=("window",))
def flash_decode_paged(q, kp, vp, posp, block_tables, cur_pos, *, window=None):
    """Block-table-native paged decode attention (GQA).

    On TPU this is the Mosaic kernel walking the table with per-page DMA.
    Off-TPU it runs the jnp reference with *identical semantics* instead of
    the interpreted kernel: interpret-mode grid iteration scales with the
    pool size and would be orders of magnitude slower than XLA here, while
    the reference still only gathers the pages it is told to walk (pass a
    truncated live view of the table to keep traffic O(live tokens)).  The
    kernel body itself is validated in interpret mode by
    tests/test_paged_attention.py.
    """
    from repro.kernels.flash_decode_paged import flash_decode_paged_pallas
    if _interpret():
        from repro.kernels import ref
        return ref.flash_decode_paged_ref(q, kp, vp, posp, block_tables,
                                          cur_pos, window=window)
    return flash_decode_paged_pallas(q, kp, vp, posp, block_tables, cur_pos,
                                     window=window, interpret=False)


@partial(jax.jit, static_argnames=("scale",))
def flash_decode_paged_mla(q_lat, q_rope, ckvp, kropep, posp, block_tables,
                           cur_pos, *, scale: float):
    """Weight-absorbed MLA paged decode over the latent pool pair.

    Returns the latent attention output [B, H, r] in f32; the caller folds
    W_kv_b(v) in afterwards.  Backend selection as in flash_decode_paged.
    """
    from repro.kernels.flash_decode_paged import flash_decode_paged_mla_pallas
    if _interpret():
        from repro.kernels import ref
        return ref.flash_decode_paged_mla_ref(q_lat, q_rope, ckvp, kropep,
                                              posp, block_tables, cur_pos,
                                              scale=scale)
    return flash_decode_paged_mla_pallas(q_lat, q_rope, ckvp, kropep, posp,
                                         block_tables, cur_pos, scale=scale,
                                         interpret=False)


def flash_attention(q, k, v, *, window=None):
    """Model-layout adapter: q [B,S,Hq,hd], k/v [B,S,Hkv,hd] -> [B,S,Hq,hd]."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_bhsd(qt, kt, vt, window=window)
    return out.transpose(0, 2, 1, 3)
