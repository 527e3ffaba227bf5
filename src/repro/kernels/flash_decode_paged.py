"""Pallas TPU kernel: block-table-native paged flash-decode.

PR-2's paged KVCache made decode *allocation* O(live tokens), but every
attention call still gathered ``pages[block_table]`` into a contiguous
``[B, n_blk * P]`` view first -- O(table width) HBM traffic per step, i.e.
"memory saved, inference not faster" (the trap PAPER.md §5 ascribes to
naive pruning).  This kernel attends the pages *in place*:

  * the block table rides in through ``PrefetchScalarGridSpec`` so its
    entries are available to the BlockSpec index maps before the kernel
    body runs -- page ``table[b, j]`` of the K/V pool is DMA'd per KV tile,
    exactly the scalar-prefetch scheme ``kernels/moe_gmm.py`` uses for
    expert weights;
  * ``posp`` (per-page stored positions) masks invalid tail slots
    in-kernel: a slot participates iff ``0 <= posp <= cur_pos`` (and within
    the sliding window, if any), so ring-wrapped sliding-window layouts and
    half-filled tail pages need no special cases -- identical semantics to
    the gather path's ``_mask_bias``;
  * pages unmapped in the table point at the reserved trash page 0 (whose
    ``posp`` stays -1); the kernel additionally skips their compute via
    ``pl.when(table[b, j] != TRASH_PAGE)``;
  * the online-softmax accumulator (m, l, acc) lives in VMEM scratch and
    runs over a sequence's pages in block order (the KV grid dim iterates
    sequentially on TPU), flushing the output tile once at the last page.

GQA is handled by head-group packing (q reshaped ``[B, Hkv, g, hd]``, one
grid row per kv head); MLA by a second kernel over the latent pool pair
``ckvp/kropep`` that computes the weight-absorbed scores
``q_lat . ckv + q_rope . krope`` and accumulates ``probs @ ckv`` -- the
output stays in latent space ``[B, H, r]`` and the caller applies
``W_kv_b(v)`` outside.

The caller may pass a *truncated* table view ``table[:, :n_live]`` to walk
only the pages any live sequence can attend (serving/kv_cache.py
``live_blocks`` computes the bucketed bound) -- correct because positions
occupy a prefix of the ring until it wraps, at which point the bound is the
full table.  That is where the decode win comes from: per-step traffic
scales with the live context, not ``max_len``.

All-masked queries (idle batch slots): the recovery property of online
softmax keeps live tiles exact even if earlier tiles were fully masked
(``alpha = exp(-inf - m_real) = 0`` discards the placeholder sums); a query
with *no* valid slot anywhere produces unspecified-but-finite output, which
the engine never reads (idle slots sample into the void).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
TRASH_PAGE = 0   # mirrors models/attention.py: reserved always-masked page


# --------------------------------------------------------------------------- #
# GQA
# --------------------------------------------------------------------------- #


def _gqa_kernel(bt_ref, cur_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                m_ref, l_ref, acc_ref, *, scale: float, window):
    """One (sequence, page) cell over every KV head of the page.

    q_ref   [1, g, Hkv, hd]   this sequence's queries, group-major
    k_ref   [1, P, Hkv, hd]   page ``table[b, j]`` (all heads: the block's
    v_ref   [1, P, Hkv, hd]   last two dims are whole, as TPU tiling needs)
    pos_ref [1, P, 1, 1]      stored positions of the page's slots
    m/l     [g, Hkv, 1] f32   online-softmax running max / denominator
    acc     [g, Hkv, hd] f32  running numerator

    Scores are elementwise products reduced over ``hd`` (lanes), so every
    intermediate keeps Hkv in the sublane position: no per-head slices,
    no transposes, and one DMA per page instead of one per (page, head).
    """
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    g = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(bt_ref[b, j] != TRASH_PAGE)
    def _accumulate():
        k = k_ref[0].astype(jnp.float32)                # [P, Hkv, hd]
        v = v_ref[0].astype(jnp.float32)
        pos = pos_ref[0]                                # [P, 1, 1] i32
        cur = cur_ref[b]
        valid = (pos >= 0) & (pos <= cur)
        if window is not None:
            valid &= pos > cur - window
        for gi in range(g):
            q = q_ref[0, gi].astype(jnp.float32) * scale        # [Hkv, hd]
            s = jnp.sum(k * q[None], axis=-1, keepdims=True)    # [P, Hkv, 1]
            s = jnp.where(valid, s, NEG_INF)
            m_old = m_ref[gi]                                   # [Hkv, 1]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=0))
            p = jnp.exp(s - m_new[None])                        # [P, Hkv, 1]
            alpha = jnp.exp(m_old - m_new)
            l_ref[gi] = l_ref[gi] * alpha + jnp.sum(p, axis=0)
            acc_ref[gi] = acc_ref[gi] * alpha + jnp.sum(p * v, axis=0)
            m_ref[gi] = m_new

    @pl.when(j == nj - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_decode_paged_pallas(q, kp, vp, posp, block_tables, cur_pos, *,
                              window=None, interpret: bool = False):
    """q [B,Hq,hd]; kp/vp [N,P,Hkv,hd]; posp [N,P] i32;
    block_tables [B,n_blk] i32; cur_pos [B] i32 -> [B,Hq,hd].

    ``block_tables`` may be a truncated view covering only live pages; every
    entry must be a valid pool index (unmapped entries are TRASH_PAGE).
    """
    b, hq, hd = q.shape
    n, p, hkv = kp.shape[0], kp.shape[1], kp.shape[2]
    g = hq // hkv
    n_blk = block_tables.shape[1]
    scale = 1.0 / (hd ** 0.5)

    # head h*g + gi of q serves kv head h; lay the group axis out first so
    # a block's last two dims are the whole (Hkv, hd) of a page
    qg = q.reshape(b, hkv, g, hd).transpose(0, 2, 1, 3)
    pos4 = posp.reshape(n, p, 1, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_blk),
        in_specs=[
            pl.BlockSpec((1, g, hkv, hd), lambda b_, j_, bt, cur: (b_, 0, 0, 0)),
            pl.BlockSpec((1, p, hkv, hd),
                         lambda b_, j_, bt, cur: (bt[b_, j_], 0, 0, 0)),
            pl.BlockSpec((1, p, hkv, hd),
                         lambda b_, j_, bt, cur: (bt[b_, j_], 0, 0, 0)),
            pl.BlockSpec((1, p, 1, 1),
                         lambda b_, j_, bt, cur: (bt[b_, j_], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, hkv, hd),
                               lambda b_, j_, bt, cur: (b_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, hkv, 1), jnp.float32),
            pltpu.VMEM((g, hkv, 1), jnp.float32),
            pltpu.VMEM((g, hkv, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_gqa_kernel, scale=scale, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, hkv, hd), q.dtype),
        interpret=interpret,
        name="flash_decode_paged",
    )(block_tables.astype(jnp.int32), cur_pos.astype(jnp.int32), qg, kp, vp,
      pos4)
    return out.transpose(0, 2, 1, 3).reshape(b, hq, hd)


# --------------------------------------------------------------------------- #
# MLA (weight-absorbed latent attention)
# --------------------------------------------------------------------------- #


def _mla_kernel(bt_ref, cur_ref, ql_ref, qr_ref, ckv_ref, kr_ref, pos_ref,
                o_ref, m_ref, l_ref, acc_ref, *, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(bt_ref[b, j] != TRASH_PAGE)
    def _accumulate():
        ql = ql_ref[0].astype(jnp.float32) * scale      # [H, r]
        qr = qr_ref[0].astype(jnp.float32) * scale      # [H, dr]
        ckv = ckv_ref[0].astype(jnp.float32)            # [P, r]
        kr = kr_ref[0].astype(jnp.float32)              # [P, dr]
        pos = pos_ref[0]                                # [1, P]
        cur = cur_ref[b]

        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(ql, ckv, dims)
             + jax.lax.dot_general(qr, kr, dims))       # [H, P]
        valid = (pos >= 0) & (pos <= cur)
        s = jnp.where(valid, s, NEG_INF)

        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_old - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, ckv)
        m_ref[...] = m_new

    @pl.when(j == nj - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_decode_paged_mla_pallas(q_lat, q_rope, ckvp, kropep, posp,
                                  block_tables, cur_pos, *, scale: float,
                                  interpret: bool = False):
    """q_lat [B,H,r] (q_nope absorbed through W_kv_b(k)); q_rope [B,H,dr];
    ckvp [N,P,r]; kropep [N,P,dr]; posp [N,P]; block_tables [B,n_blk];
    cur_pos [B] -> latent output [B,H,r] (caller applies W_kv_b(v)).

    ``scale`` is the model's score scale 1/sqrt(dn + dr) -- it cannot be
    derived from the latent shapes, so it is passed explicitly.
    """
    b, h, r = q_lat.shape
    dr = q_rope.shape[-1]
    n, p = ckvp.shape[0], ckvp.shape[1]
    n_blk = block_tables.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_blk),
        in_specs=[
            pl.BlockSpec((1, h, r), lambda b_, j_, bt, cur: (b_, 0, 0)),
            pl.BlockSpec((1, h, dr), lambda b_, j_, bt, cur: (b_, 0, 0)),
            pl.BlockSpec((1, p, r), lambda b_, j_, bt, cur: (bt[b_, j_], 0, 0)),
            pl.BlockSpec((1, p, dr),
                         lambda b_, j_, bt, cur: (bt[b_, j_], 0, 0)),
            pl.BlockSpec((1, 1, p), lambda b_, j_, bt, cur: (bt[b_, j_], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, r), lambda b_, j_, bt, cur: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, r), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), jnp.float32),
        interpret=interpret,
        name="flash_decode_paged_mla",
    )(block_tables.astype(jnp.int32), cur_pos.astype(jnp.int32), q_lat,
      q_rope, ckvp, kropep, posp.reshape(n, 1, p))
