"""Pallas TPU kernel: ragged grouped SwiGLU matmul over a sorted token buffer.

The sort-based dropless MoE path (``models/moe/gmm.py``) argsorts token
copies by expert id and pads each expert's group to a multiple of the row
tile ``block_m``, so every row tile of the packed buffer ``xs [M, D]``
belongs to exactly one expert.  The host precomputes two small int32 arrays
from the routing decision:

  ``tile_expert [n_tiles]``  which expert's weights tile *i* multiplies
                             (clamped into ``[0, E)`` for dead tiles);
  ``tile_valid  [n_tiles]``  1 iff the tile holds at least one real row.

Both ride in through ``PrefetchScalarGridSpec``: they are available to the
BlockSpec index maps *before* the kernel body runs, so the correct expert's
weight slices are DMA'd per tile (no gather in the kernel, no [E, C, D]
capacity buffer in HBM), and entirely-padding tiles skip the MXU work.

Grid: ``(n_tiles, F/bf)`` -- the ffn dimension iterates fastest and
sequentially on TPU; the output tile accumulates partial ``h @ w2`` terms in
a f32 VMEM scratch and is flushed once per row tile (same accumulation
scheme as ``kernels/moe_ffn.py``, which this kernel generalizes to
variable-length expert groups).

Unlike the fixed-capacity kernel there is no per-expert capacity: memory is
O(T*k*D) + per-group tile padding, and compute scales with the number of
*occupied* tiles -- a LExI plan with smaller per-layer k runs proportionally
fewer tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def block_f_for(f: int, block_f: int) -> int:
    """Largest power-of-two-halving of ``block_f`` (capped at ``f``) that
    divides ``f``: the f-slice width every expert kernel walks."""
    bf = min(block_f, f)
    while f % bf:
        bf //= 2
    return max(bf, 1)


def gate_up_specs(d: int, bf: int, n_f: int, tile_of):
    """BlockSpecs for the gate and up halves of a fused ``w1 [E, D, 2F]``.

    ``tile_of(*grid_ids, *prefetch_refs) -> (expert, f_step)``.  The gate
    slice is column block ``f_step`` and the up slice column block
    ``n_f + f_step`` of the same array, so each block is a ``[D, bf]``
    matrix whose last two dims meet the TPU's (8, 128) tiling; a
    ``[E, D, 2, F]`` view would put the 2-wide gate/up axis in the sublane
    position, which Mosaic pads about 8x in VMEM.  The caller passes
    ``w1`` twice, once per spec.
    """
    def gate(*a):
        e, fi = tile_of(*a)
        return e, 0, fi

    def up(*a):
        e, fi = tile_of(*a)
        return e, 0, n_f + fi

    return (pl.BlockSpec((1, d, bf), gate), pl.BlockSpec((1, d, bf), up))


def swiglu_tile(x, gate_w, up_w, w2):
    """SwiGLU partial for one f-slice: x [R, D] . (gate|up) [D, bf] ->
    silu(gate) * up [R, bf] . w2 [bf, D] -> [R, D] f32.

    Operands stay in the weights' storage dtype (bf16 feeds the MXU
    natively) and every dot accumulates in f32; ``h`` is cast to the
    weights' dtype before the down-projection, as the MXU would round it.
    """
    dot = functools.partial(jax.lax.dot, preferred_element_type=jnp.float32)
    x = x.astype(gate_w.dtype)
    h = jax.nn.silu(dot(x, gate_w)) * dot(x, up_w)
    return dot(h.astype(w2.dtype), w2)


def _kernel(te_ref, tv_ref, x_ref, gw_ref, uw_ref, w2_ref, o_ref, acc_ref,
            *, n_f_steps: int):
    """One (row-tile, f-step) block.

    te_ref/tv_ref           scalar-prefetch refs (consumed by index maps)
    x_ref   [bm, D]         packed sorted rows for this tile
    gw_ref  [1, D, bf]      gate columns of tile_expert[i]'s fused w1
    uw_ref  [1, D, bf]      up columns (the same w1, offset by F)
    w2_ref  [1, bf, D]      down-projection slice of tile_expert[i]
    o_ref   [bm, D]         output tile (written at the last f-step)
    acc_ref [bm, D] f32     VMEM accumulator across f-steps
    """
    del te_ref
    i = pl.program_id(0)
    f_step = pl.program_id(1)

    @pl.when(tv_ref[i] == 1)
    def _compute():
        partial = swiglu_tile(x_ref[...], gw_ref[0], uw_ref[0],
                              w2_ref[0])                     # [bm, D]

        @pl.when(f_step == 0)
        def _init():
            acc_ref[...] = partial

        @pl.when(f_step > 0)
        def _acc():
            acc_ref[...] += partial

    @pl.when(f_step == n_f_steps - 1)
    def _flush():
        @pl.when(tv_ref[i] == 1)
        def _out():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

        @pl.when(tv_ref[i] == 0)
        def _dead():
            o_ref[...] = jnp.zeros_like(o_ref)


def moe_gmm_pallas(xs, w1, w2, tile_expert, tile_valid, *, block_m: int,
                   block_f: int = 256, interpret: bool = False):
    """Ragged grouped SwiGLU FFN over a tile-aligned sorted buffer.

    xs [M, D] (M = n_tiles * block_m), w1 [E, D, 2F], w2 [E, F, D],
    tile_expert [n_tiles] i32 in [0, E), tile_valid [n_tiles] i32 -> [M, D].
    """
    m, d = xs.shape
    e, f = w2.shape[0], w2.shape[1]
    assert w1.shape == (e, d, 2 * f), (w1.shape, (e, d, 2 * f))
    assert m % block_m == 0, (m, block_m)
    n_tiles = m // block_m
    assert tile_expert.shape == (n_tiles,), (tile_expert.shape, n_tiles)
    bf = block_f_for(f, block_f)
    n_f = f // bf

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, n_f),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, fi, te, tv: (i, 0)),
            *gate_up_specs(d, bf, n_f, lambda i, fi, te, tv: (te[i], fi)),
            pl.BlockSpec((1, bf, d), lambda i, fi, te, tv: (te[i], fi, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d), lambda i, fi, te, tv: (i, 0)),
        scratch_shapes=[pltpu.VMEM((block_m, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_f_steps=n_f),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), xs.dtype),
        interpret=interpret,
        name="moe_gmm",
    )(tile_expert, tile_valid, xs, w1, w1, w2)


# --------------------------------------------------------------------------- #
# Quantized expert tiles: in-kernel dequant (DESIGN.md §7)
# --------------------------------------------------------------------------- #


def dequant_swiglu_tile(x, gw, uw, w2, s1g, s1u, s2, *, packed: bool):
    """SwiGLU partial for one f-slice over int8-stored tiles -> [R, D] f32.

    gw/uw [D(p), bf] and w2 [bf, D(p)] int8 (int4: two per byte along D,
    blocked halves); s1g/s1u/s2 [1, bf] f32 scale rows.  Dequant placement
    follows the scale layout: s1 multiplies *after* the x @ w1q dots
    (constant along the D contraction), s2 folds into ``h`` *before* the
    h @ w2q dot (it varies along the F contraction and cannot move past
    it).  Accumulation stays f32.
    """
    x = x.astype(jnp.float32)                                 # [R, D]

    def unpack(p):
        p32 = p.astype(jnp.int32)
        lo = (((p32 & 0xF) ^ 8) - 8).astype(jnp.float32)
        hi = (p32 >> 4).astype(jnp.float32)
        return lo, hi

    def x_dot(w):
        if packed:
            lo, hi = unpack(w)
            d_half = x.shape[1] // 2
            return (jax.lax.dot(x[:, :d_half], lo)
                    + jax.lax.dot(x[:, d_half:], hi))
        return jax.lax.dot(x, w.astype(jnp.float32))

    gate = x_dot(gw) * s1g
    up = x_dot(uw) * s1u
    h = jax.nn.silu(gate) * up * s2                           # [R, bf]
    if packed:
        lo, hi = unpack(w2)
        return jnp.concatenate([jax.lax.dot(h, lo), jax.lax.dot(h, hi)],
                               axis=-1)
    return jax.lax.dot(h, w2.astype(jnp.float32))


def quant_scale_specs(bf: int, tile_of):
    """BlockSpecs for the gate/up/down scale rows of one f-slice.

    ``s1 [E, 2, F]`` is read as ``[E, 2, 1, F]`` and ``s2 [E, F]`` as
    ``[E, 1, F]`` (``quant_scale_views``), so every block ends in a
    ``(1, bf)`` row equal to the full second-minor dim."""
    def s1_at(half):
        def index(*a):
            e, fi = tile_of(*a)
            return e, half, 0, fi
        return pl.BlockSpec((1, 1, 1, bf), index)

    def s2_index(*a):
        e, fi = tile_of(*a)
        return e, 0, fi

    return (s1_at(0), s1_at(1), pl.BlockSpec((1, 1, bf), s2_index))


def quant_scale_views(s1, s2):
    e, _, f = s1.shape
    return (s1.astype(jnp.float32).reshape(e, 2, 1, f),
            s2.astype(jnp.float32).reshape(e, 1, f))


def _quant_kernel(te_ref, tv_ref, x_ref, gw_ref, uw_ref, w2_ref, s1g_ref,
                  s1u_ref, s2_ref, o_ref, acc_ref, *, n_f_steps: int,
                  packed: bool):
    """One (row-tile, f-step) block over int8-stored expert tiles.

    Same tile walk and dead-tile handling as ``_kernel``; the weight
    slices arrive int8 (int4: packed two-per-byte along D, blocked
    halves) with their scale rows sliced by the same ``te``-prefetched
    index maps (``dequant_swiglu_tile`` has the dequant placement).
    """
    del te_ref
    i = pl.program_id(0)
    f_step = pl.program_id(1)

    @pl.when(tv_ref[i] == 1)
    def _compute():
        partial = dequant_swiglu_tile(
            x_ref[...], gw_ref[0], uw_ref[0], w2_ref[0], s1g_ref[0, 0],
            s1u_ref[0, 0], s2_ref[0], packed=packed)

        @pl.when(f_step == 0)
        def _init():
            acc_ref[...] = partial

        @pl.when(f_step > 0)
        def _acc():
            acc_ref[...] += partial

    @pl.when(f_step == n_f_steps - 1)
    def _flush():
        @pl.when(tv_ref[i] == 1)
        def _out():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

        @pl.when(tv_ref[i] == 0)
        def _dead():
            o_ref[...] = jnp.zeros_like(o_ref)


def moe_gmm_quant_pallas(xs, w1q, w2q, s1, s2, tile_expert, tile_valid, *,
                         dtype: str, block_m: int, block_f: int = 256,
                         interpret: bool = False):
    """Quantized ragged grouped SwiGLU FFN with in-kernel dequant.

    xs [M, D]; w1q int8 [E, D, 2F] (int4: [E, D//2, 2F]); w2q int8
    [E, F, D] (int4: [E, F, D//2]); s1 f32 [E, 2, F]; s2 f32 [E, F];
    tile_expert/tile_valid [n_tiles] i32 -> [M, D].
    """
    if dtype not in ("int8", "int4"):
        raise ValueError(f"unsupported expert dtype {dtype!r}")
    packed = dtype == "int4"
    m, d = xs.shape
    e, f = w2q.shape[0], w2q.shape[1]
    dp = d // 2 if packed else d
    assert w1q.shape == (e, dp, 2 * f), (w1q.shape, (e, dp, 2 * f))
    assert w2q.shape == (e, f, dp), (w2q.shape, (e, f, dp))
    assert s1.shape == (e, 2, f) and s2.shape == (e, f), (s1.shape, s2.shape)
    assert not packed or d % 2 == 0, d
    assert m % block_m == 0, (m, block_m)
    n_tiles = m // block_m
    assert tile_expert.shape == (n_tiles,), (tile_expert.shape, n_tiles)
    bf = block_f_for(f, block_f)
    n_f = f // bf

    def tile_of(i, fi, te, tv):
        return te[i], fi

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, n_f),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, fi, te, tv: (i, 0)),
            *gate_up_specs(dp, bf, n_f, tile_of),
            pl.BlockSpec((1, bf, dp), lambda i, fi, te, tv: (te[i], fi, 0)),
            *quant_scale_specs(bf, tile_of),
        ],
        out_specs=pl.BlockSpec((block_m, d), lambda i, fi, te, tv: (i, 0)),
        scratch_shapes=[pltpu.VMEM((block_m, d), jnp.float32)],
    )
    s1v, s2v = quant_scale_views(s1, s2)
    return pl.pallas_call(
        functools.partial(_quant_kernel, n_f_steps=n_f, packed=packed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), xs.dtype),
        interpret=interpret,
        name="moe_gmm_quant",
    )(tile_expert, tile_valid, xs, w1q, w1q, w2q, s1v, s1v, s2v)
