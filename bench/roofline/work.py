"""Operations and bytes each measured kernel needs, and the chip's peaks.

The counts are of the work the layer needs, not of what today's kernel
does, so a kernel that does less reads higher and the yardstick does not
move:

* ``moe_decode``: per MoE layer and step, every expert that some live
  slot routes to is read once -- ``min(sum of the live slots' k, E)``
  tiles at the configuration's expert dtype (int8 tiles with their f32
  scale rows) -- plus the live rows of activations in and out.  The
  benchmark cannot see which experts were routed, so the tile count is
  an upper bound on the need: it assumes no two slots share an expert
  until all ``E`` are read.
* ``flash_decode_paged``: the live KV of the live slots, read once, plus
  each live query and output row.
* ``moe_gmm``: ``2 * 3 * D * F`` operations per routed token-slot, and the
  tiles of the occupied experts, ``min(routed token-slots, E)``.

The least time is the larger of operations over the peak rate and bytes
over the peak bandwidth; a share of the roofline is that least time over
the measured kernel time.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Sequence

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")

#: bytes of one stored weight by expert dtype
WEIGHT_BYTES = {"bf16": 2.0, "int8": 1.0, "int4": 0.5}
ACT_BYTES = 2.0         # bf16 activations and KV


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks; a device that is not in the table is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, pk: Dict[str, float]) -> float:
    return max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_s"])


def expert_tile_bytes(d: int, f: int, dtype: str) -> float:
    """One expert's w1 ``[D, 2F]`` and w2 ``[F, D]`` as stored."""
    b = 3.0 * d * f * WEIGHT_BYTES[dtype]
    if dtype != "bf16":
        b += 3.0 * f * 4.0          # f32 scales: s1 [2, F] and s2 [F]
    return b


def moe_decode(slot_ks: Sequence[int], *, d: int, f: int, e: int,
               dtype: str):
    """(operations, bytes) of one MoE layer of one decode step; ``slot_ks``
    holds each live slot's k in this layer."""
    routed = float(sum(slot_ks))
    flops = 6.0 * d * f * routed
    nbytes = (decode_tiles(slot_ks, e) * expert_tile_bytes(d, f, dtype)
              + 2.0 * len(slot_ks) * d * ACT_BYTES)
    return flops, nbytes


def decode_tiles(slot_ks: Sequence[int], e: int) -> float:
    """Expert tiles one MoE layer of a decode step is taken to need: at
    most every routed slot's own expert, at most all ``E``."""
    return min(float(sum(slot_ks)), float(e))


def flash_decode_paged(ctx: Sequence[int], *, heads: int, kv_heads: int,
                       hd: int):
    """(operations, bytes) of one layer's decode attention over the live
    slots, slot ``i`` attending ``ctx[i]`` positions."""
    keys = float(sum(ctx))
    flops = 4.0 * keys * heads * hd
    nbytes = (2.0 * keys * kv_heads * hd * ACT_BYTES
              + 2.0 * len(ctx) * heads * hd * ACT_BYTES)
    return flops, nbytes


def moe_gmm(token_ks: Sequence[int], *, d: int, f: int, e: int, dtype: str):
    """(operations, bytes) of one MoE layer of one chunk step;
    ``token_ks`` holds each valid token's k in this layer."""
    routed = float(sum(token_ks))
    tiles = min(routed, float(e))
    flops = 6.0 * d * f * routed
    nbytes = (tiles * expert_tile_bytes(d, f, dtype)
              + 2.0 * routed * d * ACT_BYTES)
    return flops, nbytes


def model_flops(model: Dict, ks: Sequence[int], ctx: int) -> float:
    """Forward operations the model needs for one token that attends
    ``ctx`` positions, under per-layer expert counts ``ks``."""
    d, v = model["hidden_size"], model["vocab_size"]
    h, kvh, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    e, f = model["num_experts"], model["intermediate_size"]
    per_layer = (2.0 * d * (h + 2 * kvh) * hd      # q, k, v projections
                 + 2.0 * h * hd * d                # output projection
                 + 4.0 * ctx * h * hd              # scores and values
                 + 2.0 * d * e)                    # router
    total = 2.0 * d * v                            # output head
    for k in ks:
        total += per_layer + 6.0 * d * f * k
    return total
