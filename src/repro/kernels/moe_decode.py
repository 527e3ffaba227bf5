"""Pallas TPU kernel: fused routed-expert SwiGLU for decode-shaped MoE batches.

The serving decode step routes ``B ~ 8`` single tokens per step.  The
sort-based ``gmm`` dispatch built for prefill-scale ``T`` (argsort the
token copies, scatter them into a packed ``[M, D]`` buffer whose expert
groups are padded to the row tile) is the wrong shape regime there: with
``T*k`` copies spread over up to ``E`` experts, almost every row tile is
padding, and the argsort/scatter/unsort machinery costs more than the
expert math it organizes.  This kernel drops the dispatch stage entirely:

  * the router's top-k expert ids ``idx [B, k]`` ride in through
    ``PrefetchScalarGridSpec`` (the scheme ``kernels/moe_gmm.py`` and
    ``kernels/flash_decode_paged.py`` use), so BlockSpec index maps DMA
    exactly the *routed* experts' weight tiles -- expert ``idx[b, j]``'s
    ``w1``/``w2`` slices per ``(token, slot, f-step)`` grid cell.  No sort
    plan, no ``[M, D]`` packed buffer, no tiles that exist only to pad an
    expert group;
  * top-k selection itself happens one level up (``models/moe/router.py``):
    scalar-prefetched ids must exist *before* the kernel body runs, and
    ``route()`` stays the single source of truth for scores, renorm and the
    NAEE skipping baseline, so every impl stays numerically interchangeable;
  * the per-token combine weight is applied to each partial product inside
    the kernel and accumulated in f32 VMEM scratch across the ``k`` slots
    and f-steps -- router-weighted combine fused with compute, flushed once
    at the last grid cell;
  * ``k`` is a **static** specialization (the grid is ``(B, k, F/bf)``): a
    LExI plan's per-layer expert counts change the number of grid cells --
    i.e. the issued FLOPs -- directly, which is what converts a plan into
    decode wall-clock rather than dispatch-overhead noise.

Work is O(B * k * D * F) with no padding term; the gmm path's is
O((B*k + E*(bm-1)) * D * F) plus the sort machinery.  The crossover back to
``gmm`` comes at prefill-scale ``T``, where per-expert row tiles amortize
weight DMA over many tokens (``models/moe/registry.py`` holds the
auto-switch threshold; DESIGN.md §5 has the contract).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.moe_gmm import block_f_for, dequant_swiglu_tile, \
    gate_up_specs, quant_scale_specs, quant_scale_views, swiglu_tile


def _kernel(idx_ref, w_ref, x_ref, gw_ref, uw_ref, w2_ref, o_ref, acc_ref,
            *, n_k_slots: int, n_f_steps: int):
    """One (token, k-slot, f-step) grid cell.

    idx_ref                  scalar-prefetch ref (consumed by the index maps)
    w_ref   [B, k] f32 SMEM  router combine weights
    x_ref   [B, D]           every token's activations (one resident block)
    gw_ref  [1, D, bf]       gate columns of expert idx[b, j]'s fused w1
    uw_ref  [1, D, bf]       up columns of the same expert
    w2_ref  [1, bf, D]       down-projection slice of expert idx[b, j]
    o_ref   [B, D]           output (written once, at the last cell)
    acc_ref [B, D] f32       VMEM accumulator across tokens, slots, f-steps

    The TPU tiles the last two dims of a block by (8, 128), so a one-row
    ``[1, D]`` block of ``x`` cannot be DMA'd; the whole ``[B, D]`` batch
    stays resident instead, and the cell multiplies all B rows by expert
    ``idx[b, j]``'s slice and keeps row ``b``.  The weight slice is loaded
    once either way, so the extra rows cost MXU passes, not HBM bytes.
    """
    del idx_ref
    b = pl.program_id(0)
    j = pl.program_id(1)
    fi = pl.program_id(2)

    @pl.when((b == 0) & (j == 0) & (fi == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    keep_row_b(acc_ref, b, w_ref[b, j] * swiglu_tile(
        x_ref[...], gw_ref[0], uw_ref[0], w2_ref[0]))

    @pl.when((b == pl.num_programs(0) - 1) & (j == n_k_slots - 1)
             & (fi == n_f_steps - 1))
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def keep_row_b(acc_ref, b, partial):
    """acc[b] += partial[b]: a row select by mask, not a dynamic slice."""
    rows = jax.lax.broadcasted_iota(jnp.int32, partial.shape, 0)
    acc_ref[...] += jnp.where(rows == b, partial, 0.0)


def decode_grid_spec(b: int, k: int, d: int, dp: int, bf: int, n_f: int,
                     extra_specs=lambda tile_of: ()):
    """(token, slot, f-step) grid over scalar-prefetched routed ids.

    ``extra_specs(tile_of)`` appends per-expert BlockSpecs (the quantized
    kernel's scale rows) indexed like the weight tiles."""
    def tile_of(b_, j_, fi, idx):
        return idx[b_, j_], fi

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, k, n_f),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((b, d), lambda b_, j_, fi, idx: (0, 0)),
            *gate_up_specs(dp, bf, n_f, tile_of),
            pl.BlockSpec((1, bf, dp),
                         lambda b_, j_, fi, idx: (idx[b_, j_], fi, 0)),
            *extra_specs(tile_of),
        ],
        out_specs=pl.BlockSpec((b, d), lambda b_, j_, fi, idx: (0, 0)),
        scratch_shapes=[pltpu.VMEM((b, d), jnp.float32)],
    )


def moe_decode_pallas(x, w1, w2, idx, weights, *, block_f: int = 256,
                      interpret: bool = False):
    """Fused routed-expert SwiGLU with in-kernel weighted combine.

    x [B, D]; w1 [E, D, 2F]; w2 [E, F, D]; idx [B, k] i32 in [0, E);
    weights [B, k] f32 router combine weights -> y [B, D] in x.dtype.

    Only the routed experts' weight tiles are read: ``idx`` is scalar-
    prefetched so the BlockSpec index maps DMA expert ``idx[b, j]``'s
    slices per grid cell.  ``k`` (= idx.shape[1]) is static -- per-layer k
    from a LExI plan compiles to a proportionally smaller grid.
    """
    b, d = x.shape
    e, f = w2.shape[0], w2.shape[1]
    k = idx.shape[1]
    assert w1.shape == (e, d, 2 * f), (w1.shape, (e, d, 2 * f))
    assert idx.shape == (b, k) and weights.shape == (b, k), \
        (idx.shape, weights.shape)
    bf = block_f_for(f, block_f)
    n_f = f // bf
    return pl.pallas_call(
        functools.partial(_kernel, n_k_slots=k, n_f_steps=n_f),
        grid_spec=decode_grid_spec(b, k, d, d, bf, n_f),
        out_shape=jax.ShapeDtypeStruct((b, d), x.dtype),
        interpret=interpret,
        name="moe_decode",
    )(idx.astype(jnp.int32), weights.astype(jnp.float32), x, w1, w1, w2)


def _lookahead_gather(w, idx, pred_idx):
    """Staged gather with hit-select (numerically a no-op).

    The staged gather depends only on ``pred_idx`` -- ids predicted one
    layer ahead from the *previous* layer's pre-FFN hidden -- so in the
    layer-stack graph it is schedulable before this layer's attention and
    router run, overlapping weight loads with compute.  The fresh gather
    (true ids) backs up every mispredicted slot: where ``pred == idx`` the
    select returns the staged block (bitwise equal to the fresh one), so
    the result is exactly the plain gather whatever the hit rate.
    """
    staged = jnp.take(w, pred_idx, axis=0)
    fresh = jnp.take(w, idx, axis=0)
    hit = (pred_idx == idx).reshape(idx.shape + (1,) * (w.ndim - 1))
    return jnp.where(hit, staged, fresh)


def _gather(w, idx, pred_idx):
    if pred_idx is None:
        return jnp.take(w, idx, axis=0)
    return _lookahead_gather(w, idx, pred_idx)


def moe_decode_routed_jnp(x, w1, w2, idx, weights, pred_idx=None):
    """jnp path with identical semantics (CPU fallback / non-kernel impl).

    Gathers the k routed experts' weight blocks per token and contracts in
    f32 -- the same O(B*k*D*F) work the kernel issues, spelled as XLA ops.
    The weight gather materializes [B, k, D, 2F] copies, which is exactly
    the traffic the TPU kernel's per-expert DMA avoids; at decode-shaped B
    it is still far below the gmm path's padded-tile buffer.

    ``pred_idx`` (router lookahead, [B, k] i32) stages the gathers on ids
    available before this layer's router runs; see ``_lookahead_gather``.

    It rounds where the kernel does (``swiglu_tile``): f32-accumulating
    dots on the weights' dtype, ``h`` cast to that dtype before the
    down-projection, and the combine an elementwise f32 multiply-add.
    """
    w1g = _gather(w1, idx, pred_idx)                          # [B, k, D, 2F]
    w2g = _gather(w2, idx, pred_idx)                          # [B, k, F, D]
    h = jnp.einsum("bd,bkdf->bkf", x.astype(w1.dtype), w1g,
                   preferred_element_type=jnp.float32)
    gate, up = jnp.split(h, 2, axis=-1)
    h = (jax.nn.silu(gate) * up).astype(w2.dtype)             # [B, k, F]
    y = jnp.einsum("bkf,bkfd->bkd", h, w2g,
                   preferred_element_type=jnp.float32)
    y = jnp.sum(y * weights.astype(jnp.float32)[..., None], axis=1)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------- #
# Quantized expert tiles: in-kernel dequant (DESIGN.md §7)
# --------------------------------------------------------------------------- #


def _quant_kernel(idx_ref, w_ref, x_ref, gw_ref, uw_ref, w2_ref, s1g_ref,
                  s1u_ref, s2_ref, o_ref, acc_ref, *, n_k_slots: int,
                  n_f_steps: int, packed: bool):
    """One (token, k-slot, f-step) grid cell over int8-stored tiles.

    Same walk as ``_kernel``; the expert tiles arrive int8 (int4: packed
    two-per-byte along D) with their scale rows sliced by the *same*
    scalar-prefetched index maps (``dequant_swiglu_tile`` in
    ``kernels/moe_gmm.py`` has the dequant placement).
    """
    del idx_ref
    b = pl.program_id(0)
    j = pl.program_id(1)
    fi = pl.program_id(2)

    @pl.when((b == 0) & (j == 0) & (fi == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    keep_row_b(acc_ref, b, w_ref[b, j] * dequant_swiglu_tile(
        x_ref[...], gw_ref[0], uw_ref[0], w2_ref[0], s1g_ref[0, 0],
        s1u_ref[0, 0], s2_ref[0], packed=packed))

    @pl.when((b == pl.num_programs(0) - 1) & (j == n_k_slots - 1)
             & (fi == n_f_steps - 1))
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def moe_decode_quant_pallas(x, w1q, w2q, s1, s2, idx, weights, *,
                            dtype: str, block_f: int = 256,
                            interpret: bool = False):
    """Quantized fused routed-expert SwiGLU with in-kernel dequant.

    x [B, D]; w1q int8 [E, D, 2F] (int4: [E, D//2, 2F]); w2q int8
    [E, F, D] (int4: [E, F, D//2]); s1 f32 [E, 2, F]; s2 f32 [E, F];
    idx/weights [B, k] -> y [B, D] in x.dtype.

    The scale rows ride the same scalar-prefetched routed ids as the
    weight tiles: per (token, slot, f-step) grid cell the BlockSpec index
    maps DMA expert ``idx[b, j]``'s quantized tile *and* its gate/up/down
    scale slices -- quantization adds no second indexing scheme.
    """
    if dtype not in ("int8", "int4"):
        raise ValueError(f"unsupported expert dtype {dtype!r}")
    packed = dtype == "int4"
    b, d = x.shape
    e, f = w2q.shape[0], w2q.shape[1]
    k = idx.shape[1]
    dp = d // 2 if packed else d
    assert w1q.shape == (e, dp, 2 * f), (w1q.shape, (e, dp, 2 * f))
    assert w2q.shape == (e, f, dp), (w2q.shape, (e, f, dp))
    assert s1.shape == (e, 2, f) and s2.shape == (e, f), (s1.shape, s2.shape)
    assert not packed or d % 2 == 0, d
    assert idx.shape == (b, k) and weights.shape == (b, k), \
        (idx.shape, weights.shape)
    bf = block_f_for(f, block_f)
    n_f = f // bf
    s1v, s2v = quant_scale_views(s1, s2)
    return pl.pallas_call(
        functools.partial(_quant_kernel, n_k_slots=k, n_f_steps=n_f,
                          packed=packed),
        grid_spec=decode_grid_spec(
            b, k, d, dp, bf, n_f,
            extra_specs=lambda tile_of: quant_scale_specs(bf, tile_of)),
        out_shape=jax.ShapeDtypeStruct((b, d), x.dtype),
        interpret=interpret,
        name="moe_decode_quant",
    )(idx.astype(jnp.int32), weights.astype(jnp.float32), x, w1q, w1q, w2q,
      s1v, s1v, s2v)


def moe_decode_routed_quant_jnp(x, w1q, w2q, s1, s2, idx, weights, *,
                                dtype: str, pred_idx=None):
    """Quantized jnp fallback: dequant-after-gather.

    The gathers move int8 (int4: packed) copies -- 1/2 (1/4) the bytes of
    the full-precision fallback's [B, k, D, 2F] blocks, matching the
    kernel's bytes-side semantics -- plus tiny f32 scale rows; dequant is
    a scale multiply placed exactly where the kernel places it (s1 after
    the w1 dot, s2 folded into h before the w2 dot).  ``pred_idx`` stages
    the gathers as in ``moe_decode_routed_jnp``.
    """
    if dtype not in ("int8", "int4"):
        raise ValueError(f"unsupported expert dtype {dtype!r}")
    b, d = x.shape
    f = w2q.shape[1]
    w1g = _gather(w1q, idx, pred_idx)         # [B, k, D(p), 2F] int8
    w2g = _gather(w2q, idx, pred_idx)         # [B, k, F, D(p)] int8
    s1g = _gather(s1, idx, pred_idx)          # [B, k, 2, F] f32
    s2g = _gather(s2, idx, pred_idx)          # [B, k, F] f32
    if dtype == "int4":
        from repro.models.moe.params import unpack_int4
        w1g = unpack_int4(w1g, axis=2)
        w2g = unpack_int4(w2g, axis=3)
    h = jnp.einsum("bd,bkdf->bkf", x.astype(jnp.float32),
                   w1g.astype(jnp.float32))
    h = h.reshape(b, -1, 2, f) * s1g          # [B, k, 2, F]
    h = jax.nn.silu(h[:, :, 0, :]) * h[:, :, 1, :] * s2g     # [B, k, F]
    y = jnp.einsum("bkf,bkfd,bk->bd", h, w2g.astype(jnp.float32),
                   weights.astype(jnp.float32))
    return y.astype(x.dtype)
