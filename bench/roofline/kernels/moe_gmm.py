"""The grouped expert matmul of a chunk step (``kernels/moe_gmm.py``):
``2 * 3 * D * F`` operations per routed token-slot, and the tiles of the
occupied experts, ``min(routed token-slots, E)``."""

from typing import Sequence

from bench.roofline.work import ACT_BYTES, expert_tile_bytes

OPS = ("moe_gmm", "moe_gmm_quant")
STEP = "chunk"
LAYERS = "moe"


def need(token_ks: Sequence[int], *, d: int, f: int, e: int, dtype: str):
    """(operations, bytes) of one MoE layer of one chunk step;
    ``token_ks`` holds each valid token's k in this layer."""
    routed = float(sum(token_ks))
    tiles = min(routed, float(e))
    flops = 6.0 * d * f * routed
    nbytes = (tiles * expert_tile_bytes(d, f, dtype)
              + 2.0 * routed * d * ACT_BYTES)
    return flops, nbytes


def work(w, call, ks, dtype):
    token_ks = [k for k, n in zip(ks, call["tokens"]) for _ in range(n)]
    return need(token_ks, d=w["d_model"], f=w["expert_ff"], e=w["experts"],
                dtype=dtype)
