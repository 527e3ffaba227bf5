"""The chip's peaks, and what the kernel files (``kernels/<kernel>.py``)
share: bytes of a stored weight and an activation, an expert tile's bytes.

The least time of a call is the larger of operations over the peak rate
and bytes over the peak bandwidth; a share of the roofline is that least
time over the measured kernel time.
"""

from __future__ import annotations

import json
import os
from typing import Dict

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
    """One expert's gate|up tile ``[D, 2F]`` and down tile ``[F, D]`` as
    the expert kernels store them."""
    b = 3.0 * d * f * WEIGHT_BYTES[dtype]
    if dtype != "bf16":
        b += 3.0 * f * 4.0          # f32 scales: s1 [2, F] and s2 [F]
    return b
