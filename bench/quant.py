"""Weight storage as the program serves it, for the reference's copy:
round trips through integer or float8 storage with per-channel scales.
The architecture modules (``bench/arch/``) quantize with these, so the
reference stays free of anything the program made."""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

#: quantization maxima of symmetric per-channel weight storage
QMAX = {"int8": 127, "int4": 7}
#: largest finite float8 e4m3 value
FP8_MAX = 448.0


def fake_quant(w, axis: int, dtype: str):
    """Round trip of ``w`` through ``dtype`` storage (``int8``, ``int4``:
    symmetric integers; ``fp8``: float8 e4m3) with one scale per slice
    along every dim but ``axis`` (the reduction dim of the absolute
    maximum)."""
    w = w.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-12)
    if dtype == "fp8":
        s = amax / FP8_MAX
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    s = amax / QMAX[dtype]
    return jnp.clip(jnp.round(w / s), -QMAX[dtype], QMAX[dtype]) * s


def expert_weights(gate_up, down, dtype: Optional[str]):
    """f32 expert tiles as served: as stored (bf16), or through the
    configuration's int8/int4 storage -- one scale per (expert, gate|up
    column) over D for the ``[E, D, 2F]`` gate|up tiles, one per (expert,
    f row) over D for the ``[E, F, D]`` down tiles."""
    if dtype in (None, "bf16"):
        return gate_up.astype(jnp.float32), down.astype(jnp.float32)
    return fake_quant(gate_up, 1, dtype), fake_quant(down, 2, dtype)
