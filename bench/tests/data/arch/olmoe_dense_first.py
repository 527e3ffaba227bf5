"""Test data: OLMoE's layer with its first ``first_k_dense_replace``
layers dense, as DeepSeek's configurations lay out depth.

A dense layer has OLMoE's attention and, in place of the experts, one
SwiGLU MLP ``intermediate_size`` wide; experts are
``moe_intermediate_size`` wide.  The program serves it as its registered
``olmoe-1b-7b`` with ``first_k_dense`` and ``d_ff`` set.  It is here to
show that the harness takes a new architecture as one new file: the
tests run it through ``run.run_cell`` with ``arch_dir`` pointing here.
"""

from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp

from bench.arch import olmoe


def _dense_count(m: Dict) -> int:
    return m["first_k_dense_replace"]


def leaf_specs(m: Dict) -> List:
    d, f, dt = m["hidden_size"], m["intermediate_size"], m["dtype"]
    out = olmoe.outer_leaves(m)
    for i in range(m["num_hidden_layers"]):
        g = ("stack", "groups", i)
        out += olmoe.attention_leaves(g, m)
        if i < _dense_count(m):
            out += [(g + ("mlp", "w1"), (d, 2 * f), dt, d ** -0.5),
                    (g + ("mlp", "w2"), (f, d), dt, f ** -0.5)]
        else:
            out += olmoe.moe_leaves(g, m, m["moe_intermediate_size"])
    return out


def program_keys(cfg) -> Dict:
    return dict(olmoe.program_keys(cfg), intermediate_size=cfg.d_ff,
                moe_intermediate_size=cfg.moe_d_ff,
                first_k_dense_replace=cfg.first_k_dense)


def moe_layers(m: Dict) -> List[int]:
    return list(range(_dense_count(m), m["num_hidden_layers"]))


def widths(m: Dict) -> Dict[str, int]:
    return dict(olmoe.widths(m), expert_ff=m["moe_intermediate_size"])


def model_flops(m: Dict, ks: Sequence[int], ctx: int) -> float:
    d, v, e = m["hidden_size"], m["vocab_size"], m["num_experts"]
    att = olmoe.attention_flops(m, ctx)
    total = 2.0 * d * v
    total += _dense_count(m) * (att + 6.0 * d * m["intermediate_size"])
    for k in ks:
        total += att + 2.0 * d * e + 6.0 * d * m["moe_intermediate_size"] * k
    return total


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta",
                                   "dense_dtype"))
def dense_layer(lw, x, valid, *, heads, kv_heads, eps, theta, dense_dtype):
    x = olmoe.attention(lw, x, valid, heads=heads, kv_heads=kv_heads,
                        eps=eps, theta=theta, dense_dtype=dense_dtype)
    h = olmoe.rms(x, lw["norm2"]["scale"].astype(jnp.float32), eps)
    g = h @ olmoe.dense(lw["mlp"]["w1"], dense_dtype)
    f = g.shape[-1] // 2
    act = jax.nn.silu(g[:, :f]) * g[:, f:]
    return x + act @ olmoe.dense(lw["mlp"]["w2"], dense_dtype)


def logits(weights: Dict, model: Dict, tokens: Sequence[int],
           ks: Sequence[int], *, expert_dtype=None, dense_dtype=None,
           pad_to: int = 512):
    att = dict(heads=model["num_attention_heads"],
               kv_heads=model["num_key_value_heads"],
               eps=model["rms_norm_eps"], theta=model["rope_theta"],
               dense_dtype=dense_dtype)
    layers = [lambda lw, x, valid: dense_layer(lw, x, valid, **att)
              ] * _dense_count(model)
    layers += [lambda lw, x, valid, k=k: olmoe.layer(
        lw, x, valid, jnp.int32(k), k_max=model["num_experts_per_tok"],
        expert_dtype=expert_dtype, **att) for k in ks]
    return olmoe.forward(weights, model, tokens, layers,
                         dense_dtype=dense_dtype, pad_to=pad_to)
