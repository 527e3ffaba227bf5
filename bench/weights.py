"""Random weights from a seed, made on the device in one jitted call.

The benchmark makes the weights itself, so the plain reference can make
the very same ones again (from the same seed) without taking anything the
program made.  The tree is laid out the way the serving runner takes it
(one entry per layer), every matrix in the dtype it is served in (the
configuration's ``dtype``), with the router in f32.

Scales: every matrix is N(0, 1/fan_in), so each projection of a unit-RMS
input has unit RMS; the embedding is N(0, 1), so the residual stream starts
at the scale the norms give it; norm scales are 1.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def seed_key(seed: int):
    """A PRNG key for any whole ``seed`` (wider than 32 bits too)."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    while True:             # fold the seed in 32-bit words, low word first
        key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
        seed >>= 32
        if not seed:
            return key


def leaf_specs(m: Dict) -> List[Tuple[Tuple, Tuple[int, ...], str, float]]:
    """(path, shape, dtype, std) of every weight; std 0 means all ones.

    ``m`` is the configuration's ``model`` block.  The order is fixed: the
    leaf at position i draws from ``fold_in(key, i)``.
    """
    d, v = m["hidden_size"], m["vocab_size"]
    h, kvh, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    e, f = m["num_experts"], m["intermediate_size"]
    dt = m["dtype"]
    out = [(("embed",), (v, d), dt, 1.0),
           (("final_norm", "scale"), (d,), dt, 0.0),
           (("lm_head",), (d, v), dt, d ** -0.5)]
    for i in range(m["num_hidden_layers"]):
        g = ("stack", "groups", i)
        out += [
            (g + ("norm1", "scale"), (d,), dt, 0.0),
            (g + ("attn", "wq"), (d, h * hd), dt, d ** -0.5),
            (g + ("attn", "wk"), (d, kvh * hd), dt, d ** -0.5),
            (g + ("attn", "wv"), (d, kvh * hd), dt, d ** -0.5),
            (g + ("attn", "wo"), (h * hd, d), dt, (h * hd) ** -0.5),
            (g + ("attn", "q_norm", "scale"), (hd,), dt, 0.0),
            (g + ("attn", "k_norm", "scale"), (hd,), dt, 0.0),
            (g + ("norm2", "scale"), (d,), dt, 0.0),
            (g + ("moe", "router"), (d, e), "float32", d ** -0.5),
            # gate | up halves side by side along the last dim
            (g + ("moe", "w1"), (e, d, 2 * f), dt, d ** -0.5),
            (g + ("moe", "w2"), (e, f, d), dt, f ** -0.5),
        ]
    return out


def _nest(pairs):
    """{path: leaf} -> nested dicts, with ``("stack", "groups", i)`` lists."""
    tree: Dict = {}
    for path, leaf in pairs:
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf
    groups = tree["stack"]["groups"]
    tree["stack"]["groups"] = [groups[i] for i in range(len(groups))]
    return tree


def make_weights(model: Dict, seed: int):
    """The whole weight tree on the default device, from ``seed``."""
    import jax
    import jax.numpy as jnp

    specs = leaf_specs(model)

    def build(key):
        pairs = []
        for i, (path, shape, dtype, std) in enumerate(specs):
            dt = jnp.dtype(dtype)
            if std == 0.0:
                leaf = jnp.ones(shape, dt)
            else:
                k = jax.random.fold_in(key, i)
                leaf = jax.random.normal(k, shape, dt) * jnp.asarray(std, dt)
            pairs.append((path, leaf))
        return _nest(pairs)

    return jax.jit(build)(seed_key(seed))
