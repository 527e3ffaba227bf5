"""OLMoE's decoder layer (arXiv:2409.02060), as the program serves it.

The model, as the configuration states it:

    x = embed[tokens]
    per layer l:
        h = rmsnorm(x);  q, k, v = h Wq, h Wk, h Wv
        q, k = rmsnorm over each head (eps 1e-6), then RoPE (split halves)
        x = x + softmax(q k^T / sqrt(hd) + causal) v Wo
        h = rmsnorm(x);  p = softmax(h Wr) over all experts
        x = x + sum over the top k_l experts e of p_e * swiglu_e(h)
    logits = rmsnorm(x) Wlm

``k_l`` is the request's plan.  Top-k weights are not renormalized.  The
configuration's ``intermediate_size`` is the expert width.

Weights: every matrix is N(0, 1/fan_in), so each projection of a unit-RMS
input has unit RMS; the embedding is N(0, 1), so the residual stream starts
at the scale the norms give it; norm scales are 1; the router is f32.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.quant import expert_weights, fake_quant

# --------------------------------------------------------------------------- #
# Weights
# --------------------------------------------------------------------------- #


def attention_leaves(g: Tuple, m: Dict) -> List:
    """The leaves of layer ``g``'s norms and QK-norm attention."""
    d, dt = m["hidden_size"], m["dtype"]
    h, kvh, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    return [
        (g + ("norm1", "scale"), (d,), dt, 0.0),
        (g + ("attn", "wq"), (d, h * hd), dt, d ** -0.5),
        (g + ("attn", "wk"), (d, kvh * hd), dt, d ** -0.5),
        (g + ("attn", "wv"), (d, kvh * hd), dt, d ** -0.5),
        (g + ("attn", "wo"), (h * hd, d), dt, (h * hd) ** -0.5),
        (g + ("attn", "q_norm", "scale"), (hd,), dt, 0.0),
        (g + ("attn", "k_norm", "scale"), (hd,), dt, 0.0),
        (g + ("norm2", "scale"), (d,), dt, 0.0),
    ]


def moe_leaves(g: Tuple, m: Dict, f: int) -> List:
    """The leaves of layer ``g``'s router and ``f``-wide experts."""
    d, e, dt = m["hidden_size"], m["num_experts"], m["dtype"]
    return [
        (g + ("moe", "router"), (d, e), "float32", d ** -0.5),
        # gate | up halves side by side along the last dim
        (g + ("moe", "w1"), (e, d, 2 * f), dt, d ** -0.5),
        (g + ("moe", "w2"), (e, f, d), dt, f ** -0.5),
    ]


def outer_leaves(m: Dict) -> List:
    """The embedding, the final norm and the output head."""
    d, v, dt = m["hidden_size"], m["vocab_size"], m["dtype"]
    return [(("embed",), (v, d), dt, 1.0),
            (("final_norm", "scale"), (d,), dt, 0.0),
            (("lm_head",), (d, v), dt, d ** -0.5)]


def leaf_specs(m: Dict) -> List[Tuple[Tuple, Tuple[int, ...], str, float]]:
    out = outer_leaves(m)
    for i in range(m["num_hidden_layers"]):
        g = ("stack", "groups", i)
        out += attention_leaves(g, m) + moe_leaves(g, m,
                                                   m["intermediate_size"])
    return out


# --------------------------------------------------------------------------- #
# The program's configuration
# --------------------------------------------------------------------------- #


def program_keys(cfg) -> Dict:
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim_, "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.moe_top_k,
            "intermediate_size": cfg.moe_d_ff,
            "vocab_size": cfg.padded_vocab,
            "num_hidden_layers": cfg.num_layers,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "norm_topk_prob": cfg.norm_topk_prob, "dtype": cfg.dtype}


def moe_layers(m: Dict) -> List[int]:
    return list(range(m["num_hidden_layers"]))


def widths(m: Dict) -> Dict[str, int]:
    return {"d_model": m["hidden_size"], "vocab": m["vocab_size"],
            "heads": m["num_attention_heads"],
            "kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
            "experts": m["num_experts"], "expert_ff": m["intermediate_size"]}


def attention_flops(m: Dict, ctx: int) -> float:
    """One token's attention in one layer: projections, scores, values."""
    d = m["hidden_size"]
    h, kvh, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    return (2.0 * d * (h + 2 * kvh) * hd      # q, k, v projections
            + 2.0 * h * hd * d                # output projection
            + 4.0 * ctx * h * hd)             # scores and values


def model_flops(m: Dict, ks: Sequence[int], ctx: int) -> float:
    d, v = m["hidden_size"], m["vocab_size"]
    e, f = m["num_experts"], m["intermediate_size"]
    per_layer = attention_flops(m, ctx) + 2.0 * d * e      # + router
    total = 2.0 * d * v                                    # output head
    for k in ks:
        total += per_layer + 6.0 * d * f * k
    return total


# --------------------------------------------------------------------------- #
# The plain reference
# --------------------------------------------------------------------------- #


def rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def rope(x, pos, theta):
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs            # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def dense(w, dense_dtype):
    """A non-expert matrix in f32, through the control's storage if any."""
    w = w.astype(jnp.float32)
    return w if dense_dtype is None else fake_quant(w, 0, dense_dtype)


def attention(lw, x, valid, *, heads, kv_heads, eps, theta, dense_dtype):
    """``x`` plus layer ``lw``'s causal QK-norm attention over ``x``."""
    t, d = x.shape
    hd = lw["attn"]["wq"].shape[1] // heads
    pos = jnp.arange(t)
    h = rms(x, lw["norm1"]["scale"].astype(jnp.float32), eps)
    q = (h @ dense(lw["attn"]["wq"], dense_dtype)).reshape(t, heads, hd)
    kk = (h @ dense(lw["attn"]["wk"], dense_dtype)).reshape(t, kv_heads, hd)
    v = (h @ dense(lw["attn"]["wv"], dense_dtype)).reshape(t, kv_heads, hd)
    q = rope(rms(q, lw["attn"]["q_norm"]["scale"].astype(jnp.float32),
                 1e-6), pos, theta)
    kk = rope(rms(kk, lw["attn"]["k_norm"]["scale"].astype(jnp.float32),
                  1e-6), pos, theta)
    rep = heads // kv_heads
    kk, v = jnp.repeat(kk, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, kk) / jnp.sqrt(jnp.float32(hd))
    mask = (pos[None, :] <= pos[:, None]) & valid[None, :]
    s = jnp.where(mask[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return x + a.reshape(t, heads * hd) @ dense(lw["attn"]["wo"],
                                                dense_dtype)


def moe(lw, x, k, *, k_max, eps, expert_dtype):
    """``x`` plus layer ``lw``'s top-``k`` routed experts over its norm."""
    t, _ = x.shape
    h = rms(x, lw["norm2"]["scale"].astype(jnp.float32), eps)
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


@partial(jax.jit, static_argnames=(
    "k_max", "heads", "kv_heads", "eps", "theta", "expert_dtype",
    "dense_dtype"))
def layer(lw, x, valid, k, *, k_max, heads, kv_heads, eps, theta,
          expert_dtype, dense_dtype):
    """One OLMoE layer: attention, then the top-``k`` experts."""
    x = attention(lw, x, valid, heads=heads, kv_heads=kv_heads, eps=eps,
                  theta=theta, dense_dtype=dense_dtype)
    return moe(lw, x, k, k_max=k_max, eps=eps, expert_dtype=expert_dtype)


@partial(jax.jit, static_argnames=("eps", "dense_dtype"))
def head(w, x, *, eps, dense_dtype):
    out = w["lm_head"].astype(jnp.float32)
    if dense_dtype is not None:
        out = fake_quant(out, 0, dense_dtype)
    return rms(x, w["final_norm"]["scale"].astype(jnp.float32), eps) @ out


def forward(weights: Dict, model: Dict, tokens: Sequence[int], layers,
            *, dense_dtype: Optional[str], pad_to: int):
    """Logits ``[len(tokens), V]`` of the embedding, ``layers`` (one
    ``fn(layer weights, x, valid) -> x`` per layer) and the head.

    The sequence is padded to a multiple of ``pad_to`` so that few shapes
    compile; causal masking keeps the pad out of every real position."""
    n = len(tokens)
    t = -(-n // pad_to) * pad_to
    tok = np.zeros(t, np.int32)
    tok[:n] = tokens
    valid = jnp.asarray(np.arange(t) < n)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(weights["embed"], jnp.asarray(tok), axis=0).astype(
            jnp.float32)
        for lw, fn in zip(weights["stack"]["groups"], layers):
            x = fn(lw, x, valid)
        out = head(weights, x, eps=model["rms_norm_eps"],
                   dense_dtype=dense_dtype)
    return np.asarray(out[:n])


def logits(weights: Dict, model: Dict, tokens: Sequence[int],
           ks: Sequence[int], *, expert_dtype: Optional[str] = None,
           dense_dtype: Optional[str] = None, pad_to: int = 512):
    kw = dict(k_max=model["num_experts_per_tok"],
              heads=model["num_attention_heads"],
              kv_heads=model["num_key_value_heads"],
              eps=model["rms_norm_eps"], theta=model["rope_theta"],
              expert_dtype=expert_dtype, dense_dtype=dense_dtype)
    layers = [lambda lw, x, valid, k=k: layer(lw, x, valid, jnp.int32(k),
                                              **kw) for k in ks]
    return forward(weights, model, tokens, layers, dense_dtype=dense_dtype,
                   pad_to=pad_to)
