"""The fused routed-expert decode kernel (``kernels/moe_decode.py``).

Per MoE layer and step, every expert that some live slot routes to is read
once -- ``min(sum of the live slots' k, E)`` tiles at the configuration's
expert dtype (int8 tiles with their f32 scale rows) -- plus the live rows
of activations in and out.  The benchmark cannot see which experts were
routed, so the tile count is an upper bound on the need: it assumes no two
slots share an expert until all ``E`` are read.
"""

from typing import Sequence

from bench.roofline.work import ACT_BYTES, expert_tile_bytes

OPS = ("moe_decode", "moe_decode_quant")
STEP = "decode"
LAYERS = "moe"


def need(slot_ks: Sequence[int], *, d: int, f: int, e: int, dtype: str):
    """(operations, bytes) of one MoE layer of one decode step; ``slot_ks``
    holds each live slot's k in this layer."""
    routed = float(sum(slot_ks))
    flops = 6.0 * d * f * routed
    nbytes = (tiles(slot_ks, e) * expert_tile_bytes(d, f, dtype)
              + 2.0 * len(slot_ks) * d * ACT_BYTES)
    return flops, nbytes


def tiles(slot_ks: Sequence[int], e: int) -> float:
    """Expert tiles one MoE layer of a decode step is taken to need: at
    most every routed slot's own expert, at most all ``E``."""
    return min(float(sum(slot_ks)), float(e))


def work(w, call, ks, dtype):
    return need(ks, d=w["d_model"], f=w["expert_ff"], e=w["experts"],
                dtype=dtype)


def record(w, call, ks, dtype):
    return {"tiles": tiles(ks, w["experts"])}
