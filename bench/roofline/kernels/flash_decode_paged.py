"""Paged decode attention (``kernels/flash_decode_paged.py``): the live KV
of the live slots, read once, plus each live query and output row."""

from typing import Sequence

from bench.roofline.work import ACT_BYTES

OPS = ("flash_decode_paged",)
STEP = "decode"
LAYERS = "all"


def need(ctx: Sequence[int], *, heads: int, kv_heads: int, hd: int):
    """(operations, bytes) of one layer's decode attention over the live
    slots, slot ``i`` attending ``ctx[i]`` positions."""
    keys = float(sum(ctx))
    flops = 4.0 * keys * heads * hd
    nbytes = (2.0 * keys * kv_heads * hd * ACT_BYTES
              + 2.0 * len(ctx) * heads * hd * ACT_BYTES)
    return flops, nbytes


def work(w, call, ks, dtype):
    return need(call["ctx"], heads=w["heads"], kv_heads=w["kv_heads"],
                hd=w["head_dim"])
