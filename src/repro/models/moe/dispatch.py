"""Dispatch/Combine stage: token movement between router and expert compute.

Two families:

**Capacity buffers** (GShard): scatter token copies into fixed ``[E, C, D]``
buffers with token-major slot priority; tokens past capacity are dropped.
Memory O(E*C*D); the layout expert parallelism all-to-alls over.

**Sort-based dropless** (MegaBlocks / vLLM FusedMoE): argsort token copies
by expert id and pack them into a flat ``[M, D]`` buffer whose expert groups
are padded to a multiple of the compute row tile ``block_m``.  No drops, no
capacity knob; memory O(T*k*D) plus at most ``E*(block_m-1)`` padding rows.
``SortPlan`` carries everything Compute and Combine need -- including the
per-tile expert map the plan-aware Pallas kernel prefetches.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------- #
# Capacity-buffer family (dense / ep_a2a / ep_psum)
# --------------------------------------------------------------------------- #


def _slot_positions(idx, num_experts: int, cap: int):
    """Per (token, k-slot) position within its expert's capacity buffer.

    Token-major priority (earlier tokens keep their slots under overflow),
    matching GShard.  Returns (pos [T,k] i32, keep [T,k] bool).
    """
    t, k = idx.shape
    flat = idx.reshape(-1)                                        # [T*k]
    onehot = jax.nn.one_hot(flat, num_experts, dtype=jnp.int32)   # [T*k, E]
    pos_flat = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos_flat, flat[:, None], axis=1)[:, 0]
    pos = pos.reshape(t, k)
    keep = pos < cap
    return pos, keep


def _scatter(x2d, idx_eff, pos, keep, n_rows: int, cap: int):
    """Scatter token copies into capacity buffers.

    idx_eff [T,k] in [0, n_rows); dropped slots must carry keep=False.
    Returns buffer [n_rows, cap, D].
    """
    t, k = idx_eff.shape
    d = x2d.shape[-1]
    slot = idx_eff * cap + jnp.where(keep, pos, 0)
    flat_slot = jnp.where(keep, slot, n_rows * cap)               # trash row
    buf = jnp.zeros((n_rows * cap + 1, d), x2d.dtype)
    src = jnp.broadcast_to(x2d[:, None, :], (t, k, d)).reshape(t * k, d)
    buf = buf.at[flat_slot.reshape(-1)].set(src, mode="drop")
    return buf[: n_rows * cap].reshape(n_rows, cap, d)


def _gather_combine(ye, weights, idx_eff, pos, keep, cap: int):
    """ye [n_rows, C, D] -> y [T, D] weighted combine (dropped slots -> 0)."""
    t, k = idx_eff.shape
    d = ye.shape[-1]
    slot = (idx_eff * cap + jnp.where(keep, pos, 0)).reshape(-1)
    flat = ye.reshape(-1, d)
    gathered = flat[slot].reshape(t, k, d)
    w = (weights * keep).astype(jnp.float32)
    return jnp.einsum("tkd,tk->td", gathered.astype(jnp.float32), w)


# --------------------------------------------------------------------------- #
# Sort-based dropless family (gmm)
# --------------------------------------------------------------------------- #


class SortPlan(NamedTuple):
    """Static-shape description of one sorted dropless dispatch.

    ``dest[j]`` is the packed-buffer row of flat token copy ``j`` (token
    ``j // k``, slot ``j % k``) -- an injection into ``[0, num_rows)``, so
    scatter never collides and combine is a plain gather.
    """

    dest: jnp.ndarray                #: [T*k] i32 packed row per token copy
    group_sizes: jnp.ndarray         #: [E] i32 real rows per expert
    padded_group_sizes: jnp.ndarray  #: [E] i32 rows incl. tile padding
    tile_expert: jnp.ndarray         #: [n_tiles] i32 expert of each row tile
    tile_valid: jnp.ndarray          #: [n_tiles] i32 1 iff any real row
    block_m: int                     #: row-tile size (static)
    num_rows: int                    #: M = n_tiles * block_m (static)


def default_block_m(n_copies: int, cap: int = 128, floor: int = 1) -> int:
    """Row-tile size: MXU-friendly 128 at scale, clamped to the copy count
    (next power of two) below 8 copies.

    The clamp matters for decode shapes: the packed buffer pads every
    expert group to a multiple of ``block_m``, so a T=1, k=2 dispatch
    under the old unconditional floor of 8 carried up to ``E*7`` padding
    rows for 2 real ones -- mostly-empty tiles the compute stage still
    walks.  With the clamp the worst case is ``E*(n_copies-1)`` (and the
    fused ``decode`` impl removes the padding entirely when enabled;
    DESIGN.md §5).  At 8+ copies the old round-to-8 sizing is kept:
    rounding those up to a full power of two would only *grow* per-group
    padding.  ``floor`` lets the Pallas-kernel path reimpose its Mosaic
    sublane minimum (8) -- sub-8 row tiles only lower for the jnp path.
    """
    if n_copies >= 8:
        return max(floor, min(cap, ((n_copies + 7) // 8) * 8))
    bm = 1
    while bm < n_copies:
        bm *= 2
    return max(floor, bm)


def make_sort_plan(idx, num_experts: int, block_m: int) -> SortPlan:
    """Routing decision [T,k] -> SortPlan.  All shapes are static: the packed
    buffer is sized for the worst-case per-group padding ``E*(block_m-1)``."""
    t, k = idx.shape
    n = t * k
    bm = block_m
    n_tiles = (n + num_experts * (bm - 1) + bm - 1) // bm
    flat_e = idx.reshape(-1).astype(jnp.int32)                    # [N]
    order = jnp.argsort(flat_e, stable=True)                      # token-major
    sizes = jnp.zeros((num_experts,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(sizes) - sizes                            # exclusive
    padded = ((sizes + bm - 1) // bm) * bm
    pstarts = jnp.cumsum(padded) - padded
    sorted_e = flat_e[order]
    rank = jnp.arange(n, dtype=jnp.int32) - starts[sorted_e]
    dest_sorted = pstarts[sorted_e] + rank                        # [N]
    dest = jnp.zeros((n,), jnp.int32).at[order].set(dest_sorted)

    pends = jnp.cumsum(padded)
    tile_row0 = jnp.arange(n_tiles, dtype=jnp.int32) * bm
    # side="right" walks past zero-size (empty) groups
    tile_e = jnp.searchsorted(pends, tile_row0, side="right").astype(jnp.int32)
    in_range = tile_e < num_experts
    tile_e = jnp.minimum(tile_e, num_experts - 1)
    local = tile_row0 - pstarts[tile_e]
    tile_valid = (in_range & (local < sizes[tile_e])).astype(jnp.int32)
    return SortPlan(dest, sizes, padded, tile_e, tile_valid, bm, n_tiles * bm)


def sort_dispatch(x2d, plan: SortPlan, top_k: int):
    """x2d [T, D] -> packed sorted buffer [M, D] (padding rows zero)."""
    d = x2d.shape[-1]
    src = jnp.repeat(x2d, top_k, axis=0)                          # [T*k, D]
    xs = jnp.zeros((plan.num_rows, d), x2d.dtype)
    return xs.at[plan.dest].set(src)


def sort_combine(ys, weights, plan: SortPlan):
    """ys [M, D] -> y [T, D] f32: unsort via the same dest map, weighted sum.

    The weighting is an elementwise f32 multiply-add, as in the fused
    decode kernel's accumulator: spelled as a dot, TPU's default precision
    would round both f32 operands to bf16 first.
    """
    t, k = weights.shape
    gathered = ys[plan.dest].reshape(t, k, -1).astype(jnp.float32)
    return jnp.sum(gathered * weights.astype(jnp.float32)[..., None], axis=1)
