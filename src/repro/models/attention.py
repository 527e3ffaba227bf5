"""Attention layers: GQA (w/ qk-norm, sliding window) and MLA, with KV caches.

Cache convention (per layer)
----------------------------
Contiguous (slot-per-row) layout:

GQA: ``{"k": [B, S_buf, Hkv, hd], "v": [B, S_buf, Hkv, hd], "pos": [B, S_buf]}``
MLA: ``{"ckv": [B, S_buf, r_kv], "krope": [B, S_buf, dr], "pos": [B, S_buf]}``

Paged (block-table) layout -- a shared pool of fixed-size position pages,
indexed per sequence through a block table (DESIGN.md §3):

GQA: ``{"kp": [N, P, Hkv, hd], "vp": [N, P, Hkv, hd], "posp": [N, P]}``
MLA: ``{"ckvp": [N, P, r_kv], "kropep": [N, P, dr], "posp": [N, P]}``

with N pages of P positions each.  A ``block_tables [B, n_blk]`` array maps
logical block j of sequence b to a physical page; page 0 is a reserved trash
page (``posp`` stays -1) that unmapped table entries point at, so gather-based
reads need no validity sideband.  Writes with invalid positions (< 0) are
routed out of bounds and dropped (``mode="drop"``), which is what lets one
batched graph serve a mix of active / idle / prefilling slots.  Paged decode
has two read paths (DESIGN.md §4): the gather oracle (pool -> contiguous
view -> SDPA) and, under ``use_paged_kernel``, the block-table-native
flash-decode kernel that attends the pages in place, optionally walking only
the first ``kernel_blocks`` table columns (the live-page bound).

``pos`` stores the absolute position held in each slot (-1 = empty).  For
sliding-window attention the buffer is a ring of size ``min(max_len, window)``
-- slot = position % S_buf -- which is what makes the 500k-token decode cell
O(window) instead of O(seq).  Masks are always derived from ``pos``, so ring
wrap-around needs no special cases (and carries over unchanged to the paged
layout, where the ring is simply striped across a sequence's pages).

MLA decode implements both the straightforward ("materialized") path and the
weight-absorbed path (fold W_kv_b into the query / output projections) so
decode FLOPs scale with the latent rank instead of H*(dn+dv).  The two are
numerically equivalent (tested) -- absorption is the production default.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import (
    activation_dtype,
    apply_rope,
    dense_init,
    param_dtype,
    rms_norm_headwise,
    split_keys,
)

NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# Init
# --------------------------------------------------------------------------- #


def init_attention(key, cfg: ModelConfig) -> Dict:
    dt = param_dtype(cfg)
    d = cfg.d_model
    if cfg.attention == "mla":
        ks = split_keys(key, 6)
        hd_q = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        p: Dict = {}
        if cfg.q_lora_rank:
            p["wq_a"] = dense_init(ks[0], (d, cfg.q_lora_rank), dt)
            p["q_norm"] = {"scale": jnp.ones((cfg.q_lora_rank,), dt)}
            p["wq_b"] = dense_init(ks[1], (cfg.q_lora_rank, cfg.num_heads * hd_q), dt,
                                   in_axis_size=cfg.q_lora_rank)
        else:
            p["wq"] = dense_init(ks[0], (d, cfg.num_heads * hd_q), dt)
        p["wkv_a"] = dense_init(ks[2], (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), dt)
        p["kv_norm"] = {"scale": jnp.ones((cfg.kv_lora_rank,), dt)}
        p["wkv_b"] = dense_init(
            ks[3],
            (cfg.kv_lora_rank, cfg.num_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            dt, in_axis_size=cfg.kv_lora_rank)
        p["wo"] = dense_init(ks[4], (cfg.num_heads * cfg.v_head_dim, d), dt,
                             in_axis_size=cfg.num_heads * cfg.v_head_dim)
        return p

    hd = cfg.head_dim_
    ks = split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, cfg.num_heads * hd), dt),
        "wk": dense_init(ks[1], (d, cfg.num_kv_heads * hd), dt),
        "wv": dense_init(ks[2], (d, cfg.num_kv_heads * hd), dt),
        "wo": dense_init(ks[3], (cfg.num_heads * hd, d), dt,
                         in_axis_size=cfg.num_heads * hd),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((hd,), dt)}
        p["k_norm"] = {"scale": jnp.ones((hd,), dt)}
    return p


def init_cross_attention(key, cfg: ModelConfig) -> Dict:
    """Encoder-decoder cross attention (whisper)."""
    return init_attention(key, cfg)


# --------------------------------------------------------------------------- #
# Cache
# --------------------------------------------------------------------------- #


def cache_buf_len(cfg: ModelConfig, max_len: int) -> int:
    if cfg.sliding_window:
        return min(max_len, cfg.sliding_window)
    return max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    """Abstract/concrete single-layer cache (used via eval_shape in dry-run)."""
    dt = activation_dtype(cfg)
    s = cache_buf_len(cfg, max_len)
    if cfg.attention == "mla":
        return {
            "ckv": jnp.zeros((batch, s, cfg.kv_lora_rank), dt),
            "krope": jnp.zeros((batch, s, cfg.qk_rope_head_dim), dt),
            "pos": jnp.full((batch, s), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, s, cfg.num_kv_heads, cfg.head_dim_), dt),
        "v": jnp.zeros((batch, s, cfg.num_kv_heads, cfg.head_dim_), dt),
        "pos": jnp.full((batch, s), -1, jnp.int32),
    }


def _write_seq(buf, values, positions):
    """Scatter a [B, S, ...] sequence into a ring buffer at positions % S_buf.

    Keeps only the last S_buf tokens when S > S_buf (ring semantics).
    Positions < 0 (pad / idle rows) are routed out of bounds and dropped.
    """
    s_buf = buf.shape[1]
    s = values.shape[1]
    if s > s_buf:
        values = values[:, -s_buf:]
        positions = positions[:, -s_buf:]
    valid = positions >= 0
    slots = jnp.where(valid, positions % s_buf, s_buf)  # [B, S]; OOB -> drop
    bidx = jnp.arange(buf.shape[0])[:, None]
    return buf.at[bidx, slots].set(values.astype(buf.dtype), mode="drop")


def _write_step(buf, value, position):
    """Scatter one token per sample: value [B, ...], position [B].

    Positions < 0 (idle slots) are dropped, so one fixed-width decode graph
    serves a partially occupied batch without cross-slot clobbering.
    """
    s_buf = buf.shape[1]
    valid = position >= 0
    slots = jnp.where(valid, position % s_buf, s_buf)   # [B]; OOB -> drop
    bidx = jnp.arange(buf.shape[0])
    return buf.at[bidx, slots].set(value.astype(buf.dtype), mode="drop")


# --------------------------------------------------------------------------- #
# Paged (block-table) cache
# --------------------------------------------------------------------------- #

TRASH_PAGE = 0  # reserved page unmapped block-table entries point at


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int) -> Dict:
    """Single-layer paged pool: ``num_pages`` pages of ``page_size`` slots."""
    dt = activation_dtype(cfg)
    n, p = num_pages, page_size
    if cfg.attention == "mla":
        return {
            "ckvp": jnp.zeros((n, p, cfg.kv_lora_rank), dt),
            "kropep": jnp.zeros((n, p, cfg.qk_rope_head_dim), dt),
            "posp": jnp.full((n, p), -1, jnp.int32),
        }
    return {
        "kp": jnp.zeros((n, p, cfg.num_kv_heads, cfg.head_dim_), dt),
        "vp": jnp.zeros((n, p, cfg.num_kv_heads, cfg.head_dim_), dt),
        "posp": jnp.full((n, p), -1, jnp.int32),
    }


def is_paged(cache: Optional[Dict]) -> bool:
    return cache is not None and "posp" in cache


def _paged_write(pages, values, positions, block_tables):
    """Scatter [B, S, ...] values into a page pool through the block table.

    ``positions`` < 0 are routed out of bounds and dropped; ring semantics
    (slot = pos % S_buf) fall out of S_buf = n_blk * page_size.
    """
    p = pages.shape[1]
    s_buf = block_tables.shape[1] * p
    valid = positions >= 0
    slot = jnp.where(valid, positions, 0) % s_buf       # [B, S]
    page = jnp.take_along_axis(block_tables, slot // p, axis=1)
    page = jnp.where(valid, page, pages.shape[0])       # OOB -> drop
    return pages.at[page, slot % p].set(values.astype(pages.dtype),
                                        mode="drop")


def _paged_read(pages, block_tables):
    """Gather a sequence view [B, n_blk * P, ...] from the pool (static

    shapes: the gather width is the block-table width, not the live length).
    Unmapped entries point at the trash page, whose ``posp`` is -1, so the
    position-derived mask hides them with no extra sideband.
    """
    g = jnp.take(pages, block_tables, axis=0)           # [B, n_blk, P, ...]
    return g.reshape(g.shape[0], g.shape[1] * g.shape[2], *g.shape[3:])


# --------------------------------------------------------------------------- #
# Masking + core attention math
# --------------------------------------------------------------------------- #


def _mask_bias(q_pos, kv_pos, window: Optional[int], causal: bool):
    """Additive bias [B, 1, Sq, Sk] from absolute positions."""
    q = q_pos[:, None, :, None].astype(jnp.int32)       # [B,1,Sq,1]
    k = kv_pos[:, None, None, :].astype(jnp.int32)      # [B,1,1,Sk]
    valid = k >= 0
    if causal:
        valid &= k <= q
    if window is not None:
        valid &= k > q - window
    return jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa(q, k, v, bias, scale: float, compute_dtype: str = "f32",
          precision=None):
    """Grouped-query attention: q [B,Sq,Hq,d], k/v [B,Sk,Hkv,d(v)].

    ``compute_dtype="bf16_accum32"`` keeps K/V operands in their storage
    dtype with f32 accumulation (preferred_element_type) -- on TPU this is
    MXU-native and halves the HBM bytes of reading a bf16 KV cache (§Perf).
    ``precision`` applies to the f32 dots: at TPU's default a dot rounds
    its f32 operands (the probabilities) to bf16; ``HIGHEST`` does not.
    """
    b, sq, hq, dq = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    # standard GQA head mapping: q head h uses kv head h // g (kv-major)
    qg = q.reshape(b, sq, hkv, g, dq)
    if compute_dtype == "bf16_accum32":
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                            preferred_element_type=jnp.float32) * scale
        scores = scores + bias[:, None]
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
    else:
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                            k.astype(jnp.float32),
                            precision=precision) * scale
        scores = scores + bias[:, None]                 # [B,Hkv,g,Sq,Sk]
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32),
                         precision=precision)
    return out.reshape(b, sq, hq, v.shape[-1]).astype(q.dtype)


# --------------------------------------------------------------------------- #
# Sequence-sharded decode attention (context parallelism for the KV cache)
# --------------------------------------------------------------------------- #


def _decode_attend_seqshard(cfg: ModelConfig, q, k_new, v_new, pos_b, cache,
                            mesh, compute_dtype: str = "f32"):
    """Decode attention with the KV cache sharded over the *sequence* dim of
    the ``model`` axis (flash-decoding-style context parallelism).

    Each model shard holds S_buf/m positions, appends the new token iff its
    ring slot lands in-range, computes partial (max, sumexp, weighted-V), and
    the shards combine with a log-sum-exp reduction:

        m* = pmax(m);  l* = psum(l * e^{m-m*});  o = psum(o_p * e^{m-m*}) / l*

    This is what makes 32k-context decode *fit*: without it the cache
    replicates over the model axis whenever kv_heads % model != 0
    (EXPERIMENTS.md §Perf, cell B).  Masking needs no special cases because
    it is derived from the stored absolute positions.
    """
    from jax.sharding import PartitionSpec as P
    from repro.sharding.rules import batch_spec, data_axes, data_axes_size

    axes = tuple(mesh.axis_names)
    msize = mesh.shape["model"]
    daxes = data_axes(mesh)
    b = q.shape[0]
    bdim = (daxes if len(daxes) > 1 else daxes[0]) \
        if b % max(data_axes_size(mesh), 1) == 0 else None
    hd = cfg.head_dim_
    scale = 1.0 / (hd ** 0.5)
    window = cfg.sliding_window

    def body(q_l, kn, vn, pb, k_l, v_l, pos_l):
        s_loc = k_l.shape[1]
        midx = jax.lax.axis_index("model")
        s_buf = s_loc * msize
        slot = pb % s_buf                                  # [B]
        loc = slot - midx * s_loc
        ok = (loc >= 0) & (loc < s_loc)
        locc = jnp.clip(loc, 0, s_loc - 1)
        bidx = jnp.arange(k_l.shape[0])
        k_l = k_l.at[bidx, locc].set(
            jnp.where(ok[:, None, None], kn.astype(k_l.dtype), k_l[bidx, locc]))
        v_l = v_l.at[bidx, locc].set(
            jnp.where(ok[:, None, None], vn.astype(v_l.dtype), v_l[bidx, locc]))
        pos_l = pos_l.at[bidx, locc].set(jnp.where(ok, pb, pos_l[bidx, locc]))

        bias = _mask_bias(pb[:, None], pos_l, window, True)   # [B,1,1,S_loc]
        bl, _, hq, dq = q_l.shape
        hkv = k_l.shape[2]
        g = hq // hkv
        qg = q_l.reshape(bl, 1, hkv, g, dq)    # q head h -> kv head h // g
        if compute_dtype == "bf16_accum32":
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_l,
                           preferred_element_type=jnp.float32) * scale
        else:
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                           k_l.astype(jnp.float32)) * scale
        s = s + bias[:, None]                              # [B,hkv,g,1,S_loc]
        m = jnp.max(s, axis=-1, keepdims=True)             # local max
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        if compute_dtype == "bf16_accum32":
            o = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v_l.dtype), v_l,
                           preferred_element_type=jnp.float32)
        else:
            o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_l.astype(jnp.float32))

        m_g = jax.lax.pmax(m, "model")
        corr = jnp.exp(m - m_g)                            # [B,hkv,g,1,1]
        l_g = jax.lax.psum(l * corr, "model")
        o_g = jax.lax.psum(o * corr, "model")              # [B,hkv,g,1,d]
        out = o_g / jnp.maximum(l_g, 1e-30)
        out = out.transpose(0, 3, 1, 2, 4).reshape(bl, 1, hq, v_l.shape[-1])
        return out.astype(q_l.dtype), k_l, v_l, pos_l

    qspec = P(bdim, None, None, None)
    cspec = P(bdim, "model", None, None)
    pspec = P(bdim, "model")
    bspec3 = P(bdim, None, None)
    bspec1 = P(bdim)
    out, k2, v2, p2 = jax.shard_map(
        body, mesh=mesh,
        in_specs=(qspec, bspec3, bspec3, bspec1, cspec, cspec, pspec),
        out_specs=(qspec, cspec, cspec, pspec),
    )(q, k_new, v_new, pos_b, cache["k"], cache["v"], cache["pos"])
    return out, {"k": k2, "v": v2, "pos": p2}


# --------------------------------------------------------------------------- #
# GQA forward
# --------------------------------------------------------------------------- #


def gqa_attention(
    params: Dict,
    cfg: ModelConfig,
    x,
    positions,
    *,
    mode: str = "train",
    cache: Optional[Dict] = None,
    causal: bool = True,
    kv_override: Optional[Tuple] = None,
    use_flash: bool = False,
    rope: bool = True,
    compute_dtype: str = "f32",
    seq_shard_mesh=None,
    use_flash_decode: bool = False,
    block_tables=None,
    use_paged_kernel: bool = False,
    kernel_blocks: Optional[int] = None,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """x [B,S,D]; positions [B,S] (train/prefill/chunk) or [B] (decode).

    Returns (output [B,S,D], updated cache or None).
    ``kv_override = (k, v, kv_positions)`` implements cross-attention
    (which is rope-free: pass ``rope=False``).

    ``mode="chunk"`` is chunked prefill: write this chunk's K/V into the
    cache, then attend the chunk queries against the *whole* cache (prior
    chunks included) -- decode generalized to S query tokens.  Requires
    ``positions [B, S]`` with -1 marking pad / idle rows.  With a paged
    cache, ``block_tables [B, n_blk]`` routes both writes and the gathered
    read.

    ``use_paged_kernel`` makes paged decode attend the pages in-kernel
    (block-table-native flash-decode) instead of gathering the pool into a
    contiguous view first; ``kernel_blocks`` optionally bounds the walk to
    the first N table columns (the live-page bucket -- see
    serving/kv_cache.py ``live_blocks``).  Writes always go through the
    full table.
    """
    if kv_override is not None:
        rope = False
    b, s, _ = x.shape
    hd = cfg.head_dim_
    q = (x @ params["wq"]).reshape(b, s, cfg.num_heads, hd)

    if kv_override is None:
        k = (x @ params["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
        v = (x @ params["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    else:
        k, v, kv_positions = kv_override

    if cfg.qk_norm:
        q = rms_norm_headwise(q, params["q_norm"]["scale"])
        if kv_override is None:
            k = rms_norm_headwise(k, params["k_norm"]["scale"])

    if mode == "decode":
        pos_b = positions  # [B]
        if rope:
            q = apply_rope(q, pos_b[:, None], cfg.rope_theta)
        if kv_override is None and seq_shard_mesh is not None:
            if is_paged(cache):
                raise NotImplementedError(
                    "decode_kv_seq_shard requires the contiguous cache layout")
            # context-parallel decode: KV cache seq-sharded over `model`
            if rope:
                k = apply_rope(k, pos_b[:, None], cfg.rope_theta)
            out, new_cache = _decode_attend_seqshard(
                cfg, q, k[:, 0], v[:, 0], pos_b, cache, seq_shard_mesh,
                compute_dtype)
            out = out.reshape(b, s, cfg.num_heads * hd) @ params["wo"]
            return out, new_cache
        out = None
        if kv_override is None:
            if rope:
                k = apply_rope(k, pos_b[:, None], cfg.rope_theta)
            cache = dict(cache)
            if is_paged(cache):
                pos_s = pos_b[:, None]
                cache["kp"] = _paged_write(cache["kp"], k, pos_s, block_tables)
                cache["vp"] = _paged_write(cache["vp"], v, pos_s, block_tables)
                cache["posp"] = _paged_write(cache["posp"], pos_s, pos_s,
                                             block_tables)
                if use_paged_kernel:
                    # block-table-native: attend the pages in-kernel, walking
                    # only the live-page prefix when the caller bounded it
                    from repro.kernels import ops as kops
                    bt = (block_tables if kernel_blocks is None
                          else block_tables[:, :kernel_blocks])
                    out = kops.flash_decode_paged(
                        q[:, 0], cache["kp"], cache["vp"], cache["posp"],
                        bt, pos_b, window=cfg.sliding_window)[:, None]
                else:
                    k_all = _paged_read(cache["kp"], block_tables)
                    v_all = _paged_read(cache["vp"], block_tables)
                    kv_pos = _paged_read(cache["posp"], block_tables)
            else:
                cache["k"] = _write_step(cache["k"], k[:, 0], pos_b)
                cache["v"] = _write_step(cache["v"], v[:, 0], pos_b)
                cache["pos"] = _write_step(cache["pos"], pos_b, pos_b)
                k_all, v_all, kv_pos = cache["k"], cache["v"], cache["pos"]
        else:
            k_all, v_all, kv_pos = k, v, kv_positions
        if out is None:
            if use_flash_decode and kv_override is None:
                from repro.kernels import ops as kops
                out = kops.flash_decode(q[:, 0], k_all, v_all, kv_pos, pos_b,
                                        window=cfg.sliding_window)[:, None]
            else:
                bias = _mask_bias(pos_b[:, None], kv_pos, cfg.sliding_window,
                                  causal)
                # the paged kernel's oracle: it does exact f32 math on the
                # VPU, so the dots here must not round probs to bf16
                out = _sdpa(q, k_all, v_all, bias, 1.0 / (hd ** 0.5),
                            compute_dtype,
                            precision=jax.lax.Precision.HIGHEST)
        new_cache = cache
    elif mode == "chunk":
        # chunked prefill: attend against the PRE-write cache plus the
        # in-chunk keys (concatenated), then commit the chunk.  Writing
        # first would be wrong under a sliding-window ring: the chunk's
        # writes evict positions still inside the window of the chunk's own
        # earlier queries.  Attend-then-write also matches whole-prefill
        # numerics exactly (fresh K/V, not cache-dtype round-trips).
        if rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        cache = dict(cache)
        if is_paged(cache):
            k_old = _paged_read(cache["kp"], block_tables)
            v_old = _paged_read(cache["vp"], block_tables)
            pos_old = _paged_read(cache["posp"], block_tables)
        else:
            k_old, v_old, pos_old = cache["k"], cache["v"], cache["pos"]
        k_all = jnp.concatenate([k_old, k.astype(k_old.dtype)], axis=1)
        v_all = jnp.concatenate([v_old, v.astype(v_old.dtype)], axis=1)
        kv_pos = jnp.concatenate([pos_old, positions], axis=1)
        bias = _mask_bias(positions, kv_pos, cfg.sliding_window, causal)
        out = _sdpa(q, k_all, v_all, bias, 1.0 / (hd ** 0.5), compute_dtype)
        if is_paged(cache):
            cache["kp"] = _paged_write(cache["kp"], k, positions, block_tables)
            cache["vp"] = _paged_write(cache["vp"], v, positions, block_tables)
            cache["posp"] = _paged_write(cache["posp"], positions, positions,
                                         block_tables)
        else:
            cache["k"] = _write_seq(cache["k"], k, positions)
            cache["v"] = _write_seq(cache["v"], v, positions)
            cache["pos"] = _write_seq(cache["pos"], positions, positions)
        new_cache = cache
    else:
        if rope:
            q = apply_rope(q, positions, cfg.rope_theta)
        if kv_override is None:
            if rope:
                k = apply_rope(k, positions, cfg.rope_theta)
            kv_pos = positions
        else:
            kv_pos = kv_positions
        if use_flash and kv_override is None and causal:
            from repro.kernels import ops as kops
            out = kops.flash_attention(q, k, v, window=cfg.sliding_window)
        else:
            bias = _mask_bias(positions, kv_pos, cfg.sliding_window, causal)
            out = _sdpa(q, k, v, bias, 1.0 / (hd ** 0.5), compute_dtype)
        new_cache = None
        if mode == "prefill" and kv_override is None:
            cache = dict(cache)
            cache["k"] = _write_seq(cache["k"], k, positions)
            cache["v"] = _write_seq(cache["v"], v, positions)
            cache["pos"] = _write_seq(cache["pos"], positions, positions)
            new_cache = cache

    out = out.reshape(b, s, cfg.num_heads * hd) @ params["wo"]
    return out, new_cache


# --------------------------------------------------------------------------- #
# MLA forward
# --------------------------------------------------------------------------- #


def _mla_q(params, cfg: ModelConfig, x):
    from repro.models.common import apply_norm
    b, s, _ = x.shape
    hd_q = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        cq = x @ params["wq_a"]
        cq = apply_norm(params["q_norm"], cfg.with_(norm_type="rmsnorm"), cq)
        q = (cq @ params["wq_b"]).reshape(b, s, cfg.num_heads, hd_q)
    else:
        q = (x @ params["wq"]).reshape(b, s, cfg.num_heads, hd_q)
    return jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)   # q_nope, q_rope


def _mla_latents(params, cfg: ModelConfig, x, positions):
    from repro.models.common import apply_norm
    kv_a = x @ params["wkv_a"]
    ckv, krope = jnp.split(kv_a, [cfg.kv_lora_rank], axis=-1)
    ckv = apply_norm(params["kv_norm"], cfg.with_(norm_type="rmsnorm"), ckv)
    krope = apply_rope(krope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return ckv, krope


def _wkv_b_split(params, cfg: ModelConfig):
    wkv_b = params["wkv_b"].reshape(
        cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
    return (wkv_b[..., : cfg.qk_nope_head_dim],      # [r, H, dn]
            wkv_b[..., cfg.qk_nope_head_dim:])       # [r, H, dv]


def mla_attention(
    params: Dict,
    cfg: ModelConfig,
    x,
    positions,
    *,
    mode: str = "train",
    cache: Optional[Dict] = None,
    absorb: bool = True,
    block_tables=None,
    use_paged_kernel: bool = False,
    kernel_blocks: Optional[int] = None,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3 style).

    ``use_paged_kernel`` (paged cache, decode, absorbed path only) attends
    the latent pool pair ``ckvp/kropep`` in-kernel through the block table
    instead of gathering; other modes, and the materialized (non-absorbed)
    path, keep the gather oracle.
    """
    b, s, _ = x.shape
    scale = 1.0 / ((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** 0.5)

    if mode in ("decode", "chunk"):
        # decode is the S=1 special case of chunked prefill: same cache
        # write + attend-against-everything math, the einsums keep S symbolic
        q_pos = positions[:, None] if mode == "decode" else positions  # [B,S]
        q_nope, q_rope = _mla_q(params, cfg, x)        # [B,S,H,dn],[B,S,H,dr]
        q_rope = apply_rope(q_rope, q_pos, cfg.rope_theta)
        ckv_t, krope_t = _mla_latents(params, cfg, x, q_pos)
        cache = dict(cache)
        if is_paged(cache):
            cache["ckvp"] = _paged_write(cache["ckvp"], ckv_t, q_pos,
                                         block_tables)
            cache["kropep"] = _paged_write(cache["kropep"], krope_t, q_pos,
                                           block_tables)
            cache["posp"] = _paged_write(cache["posp"], q_pos, q_pos,
                                         block_tables)
            if use_paged_kernel and absorb and mode == "decode":
                from repro.kernels import ops as kops
                wk_b, wv_b = _wkv_b_split(params, cfg)
                q_lat = jnp.einsum("bshn,rhn->bshr",
                                   q_nope.astype(jnp.float32),
                                   wk_b.astype(jnp.float32))
                bt = (block_tables if kernel_blocks is None
                      else block_tables[:, :kernel_blocks])
                o_lat = kops.flash_decode_paged_mla(
                    q_lat[:, 0], q_rope[:, 0].astype(jnp.float32),
                    cache["ckvp"], cache["kropep"], cache["posp"], bt,
                    positions, scale=scale)                # [B, H, r] f32
                out = jnp.einsum("bhr,rhv->bhv", o_lat,
                                 wv_b.astype(jnp.float32))[:, None]
                out = out.astype(x.dtype).reshape(
                    b, s, cfg.num_heads * cfg.v_head_dim)
                return out @ params["wo"], cache
            ckv = _paged_read(cache["ckvp"], block_tables)
            krope = _paged_read(cache["kropep"], block_tables)
            kv_pos = _paged_read(cache["posp"], block_tables)
        else:
            cache["ckv"] = _write_seq(cache["ckv"], ckv_t, q_pos)
            cache["krope"] = _write_seq(cache["krope"], krope_t, q_pos)
            cache["pos"] = _write_seq(cache["pos"], q_pos, q_pos)
            ckv, krope, kv_pos = cache["ckv"], cache["krope"], cache["pos"]
        bias = _mask_bias(q_pos, kv_pos, None, True)   # [B,1,Sq,Sk]

        wk_b, wv_b = _wkv_b_split(params, cfg)
        if absorb:
            # fold W_kv_b(k) into q:    q_lat [B,1,H,r]
            q_lat = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32),
                               wk_b.astype(jnp.float32))
            s_nope = jnp.einsum("bshr,bkr->bhsk", q_lat, ckv.astype(jnp.float32))
            s_rope = jnp.einsum("bshd,bkd->bhsk", q_rope.astype(jnp.float32),
                                krope.astype(jnp.float32))
            scores = (s_nope + s_rope) * scale + bias
            probs = jax.nn.softmax(scores, axis=-1)
            o_lat = jnp.einsum("bhsk,bkr->bshr", probs, ckv.astype(jnp.float32))
            out = jnp.einsum("bshr,rhv->bshv", o_lat, wv_b.astype(jnp.float32))
        else:
            kn = jnp.einsum("bkr,rhn->bkhn", ckv.astype(jnp.float32),
                            wk_b.astype(jnp.float32))
            vv = jnp.einsum("bkr,rhv->bkhv", ckv.astype(jnp.float32),
                            wv_b.astype(jnp.float32))
            s_nope = jnp.einsum("bshn,bkhn->bhsk", q_nope.astype(jnp.float32), kn)
            s_rope = jnp.einsum("bshd,bkd->bhsk", q_rope.astype(jnp.float32),
                                krope.astype(jnp.float32))
            scores = (s_nope + s_rope) * scale + bias
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bhsk,bkhv->bshv", probs, vv)
        out = out.astype(x.dtype).reshape(b, s, cfg.num_heads * cfg.v_head_dim)
        return out @ params["wo"], cache

    # train / prefill: materialize k, v per token (cheaper at large Sq=Sk)
    q_nope, q_rope = _mla_q(params, cfg, x)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv, krope = _mla_latents(params, cfg, x, positions)
    wk_b, wv_b = _wkv_b_split(params, cfg)
    kn = jnp.einsum("bkr,rhn->bkhn", ckv, wk_b)
    vv = jnp.einsum("bkr,rhv->bkhv", ckv, wv_b)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([kn, jnp.broadcast_to(
        krope[:, :, None, :], (*krope.shape[:2], cfg.num_heads, krope.shape[-1])
    ).astype(kn.dtype)], axis=-1)
    bias = _mask_bias(positions, positions, None, True)
    out = _sdpa(q, k, vv.astype(q.dtype), bias, scale)
    out = out.reshape(b, s, cfg.num_heads * cfg.v_head_dim)
    new_cache = None
    if mode == "prefill":
        cache = dict(cache)
        cache["ckv"] = _write_seq(cache["ckv"], ckv, positions)
        cache["krope"] = _write_seq(cache["krope"], krope, positions)
        cache["pos"] = _write_seq(cache["pos"], positions, positions)
        new_cache = cache
    return out @ params["wo"], new_cache


def attention(params, cfg: ModelConfig, x, positions, **kw):
    if cfg.attention == "mla":
        kw.pop("use_flash", None)
        kw.pop("kv_override", None)
        kw.pop("causal", None)
        return mla_attention(params, cfg, x, positions, **kw)
    return gqa_attention(params, cfg, x, positions, **kw)
