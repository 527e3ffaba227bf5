"""Plain reference of the served model, and the comparison behind ``correct``.

A straightforward float32 forward pass (matmuls at ``highest`` precision)
over one whole sequence, with no kernel, cache, paging or batching.  It
imports nothing of the program and takes nothing the program made: it
makes the configuration's weights again from the run's seed
(``bench/weights.py``) and quantizes expert tiles itself where the
configuration serves them quantized.

The model, as the configuration states it (OLMoE's decoder layer):

    x = embed[tokens]
    per layer l:
        h = rmsnorm(x);  q, k, v = h Wq, h Wk, h Wv
        q, k = rmsnorm over each head (eps 1e-6), then RoPE (split halves)
        x = x + softmax(q k^T / sqrt(hd) + causal) v Wo
        h = rmsnorm(x);  p = softmax(h Wr) over all experts
        x = x + sum over the top k_l experts e of p_e * swiglu_e(h)
    logits = rmsnorm(x) Wlm

``k_l`` is the request's plan.  Top-k weights are not renormalized.

The comparison: for every served token, how far its reference logit lies
below the reference's best at that position.  That is valid for greedy
tokens, so only greedy requests are compared.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: quantization maxima of symmetric per-channel weight storage
QMAX = {"int8": 127, "int4": 7}
#: largest finite float8 e4m3 value
FP8_MAX = 448.0


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs            # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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


def expert_weights(w1, w2, dtype: Optional[str]):
    """f32 expert tiles as served: as stored (bf16), or through the
    configuration's int8/int4 storage -- one scale per (expert, gate|up
    column) over D for w1, one per (expert, f row) over D for w2."""
    if dtype in (None, "bf16"):
        return w1.astype(jnp.float32), w2.astype(jnp.float32)
    return fake_quant(w1, 1, dtype), fake_quant(w2, 2, dtype)


@partial(jax.jit, static_argnames=(
    "k_max", "heads", "kv_heads", "eps", "theta", "expert_dtype",
    "dense_dtype"))
def _layer(lw, x, valid, k, *, k_max, heads, kv_heads, eps, theta,
           expert_dtype, dense_dtype):
    t, d = x.shape
    hd = lw["attn"]["wq"].shape[1] // heads
    pos = jnp.arange(t)

    def dense(w):
        w = w.astype(jnp.float32)
        return w if dense_dtype is None else fake_quant(w, 0, dense_dtype)

    h = _rms(x, lw["norm1"]["scale"].astype(jnp.float32), eps)
    q = (h @ dense(lw["attn"]["wq"])).reshape(t, heads, hd)
    kk = (h @ dense(lw["attn"]["wk"])).reshape(t, kv_heads, hd)
    v = (h @ dense(lw["attn"]["wv"])).reshape(t, kv_heads, hd)
    q = _rope(_rms(q, lw["attn"]["q_norm"]["scale"].astype(jnp.float32),
                   1e-6), pos, theta)
    kk = _rope(_rms(kk, lw["attn"]["k_norm"]["scale"].astype(jnp.float32),
                    1e-6), pos, theta)
    rep = heads // kv_heads
    kk, v = jnp.repeat(kk, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, kk) / jnp.sqrt(jnp.float32(hd))
    mask = (pos[None, :] <= pos[:, None]) & valid[None, :]
    s = jnp.where(mask[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + a.reshape(t, heads * hd) @ dense(lw["attn"]["wo"])

    h = _rms(x, lw["norm2"]["scale"].astype(jnp.float32), eps)
    probs = jax.nn.softmax(h @ lw["moe"]["router"].astype(jnp.float32), -1)
    top, idx = jax.lax.top_k(probs, k_max)
    top = jnp.where(jnp.arange(k_max) < k, top, 0.0)       # the top k only
    n_exp = probs.shape[-1]
    gate = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], idx].set(top)
    w1, w2 = expert_weights(lw["moe"]["w1"], lw["moe"]["w2"], expert_dtype)
    f = w2.shape[1]

    def one(e, y):
        g = h @ w1[e]
        act = jax.nn.silu(g[:, :f]) * g[:, f:]
        return y + gate[:, e][:, None] * (act @ w2[e])

    y = jax.lax.fori_loop(0, n_exp, one, jnp.zeros_like(x))
    return x + y


@partial(jax.jit, static_argnames=("eps", "dense_dtype"))
def _head(w, x, *, eps, dense_dtype):
    head = w["lm_head"].astype(jnp.float32)
    if dense_dtype is not None:
        head = fake_quant(head, 0, dense_dtype)
    return _rms(x, w["final_norm"]["scale"].astype(jnp.float32), eps) @ head


def logits(weights: Dict, model: Dict, tokens: Sequence[int],
           ks: Sequence[int], *, expert_dtype: Optional[str] = None,
           dense_dtype: Optional[str] = None, pad_to: int = 512):
    """Reference logits ``[len(tokens), V]`` (float32, numpy).

    ``expert_dtype`` is how expert tiles are stored (``None``/``bf16``,
    ``int8``, ``int4``, ``fp8``); ``dense_dtype`` quantizes every other
    matrix too
    (the lower-precision control; ``None`` in the reference proper).
    The sequence is padded to a multiple of ``pad_to`` so that few shapes
    compile; causal masking keeps the pad out of every real position.
    """
    n = len(tokens)
    t = -(-n // pad_to) * pad_to
    tok = np.zeros(t, np.int32)
    tok[:n] = tokens
    valid = jnp.asarray(np.arange(t) < n)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(weights["embed"], jnp.asarray(tok), axis=0).astype(
            jnp.float32)
        for lw, k in zip(weights["stack"]["groups"], ks):
            x = _layer(lw, x, valid, jnp.int32(k),
                       k_max=model["num_experts_per_tok"],
                       heads=model["num_attention_heads"],
                       kv_heads=model["num_key_value_heads"],
                       eps=model["rms_norm_eps"], theta=model["rope_theta"],
                       expert_dtype=expert_dtype, dense_dtype=dense_dtype)
        out = _head(weights, x, eps=model["rms_norm_eps"],
                    dense_dtype=dense_dtype)
    return np.asarray(out[:n])


def gaps(ref_logits: np.ndarray, chosen: Sequence[int]) -> np.ndarray:
    """Per position: the reference's best logit minus the chosen token's."""
    chosen = np.asarray(chosen)
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(chosen)), chosen]


def served_gaps(weights, model, prompt: List[int], served: List[int], ks,
                *, expert_dtype=None, control_dense=None,
                control_experts=None) -> Dict[str, np.ndarray]:
    """Gaps of the served tokens (``served``), and, when a control
    precision is given, of the tokens the control would put first at the
    same positions."""
    seq = list(prompt) + list(served[:-1])
    lo = len(prompt) - 1
    ref = logits(weights, model, seq, ks, expert_dtype=expert_dtype)[lo:]
    out = {"served": gaps(ref, served)}
    if control_dense is not None or control_experts is not None:
        ctl = logits(weights, model, seq, ks,
                     expert_dtype=control_experts or expert_dtype,
                     dense_dtype=control_dense)[lo:]
        out["control"] = gaps(ref, ctl.argmax(axis=-1))
    return out
