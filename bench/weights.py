"""Random weights from a seed, made on the device in one jitted call.

The benchmark makes the weights itself, so the plain reference can make
the very same ones again (from the same seed) without taking anything the
program made.  Which leaves there are, their shapes, dtypes and scales
are the architecture's (``leaf_specs`` of ``bench/arch/<arch>.py``); the
tree is laid out the way the serving runner takes it, one entry per layer
under ``("stack", "groups", i)``.
"""

from __future__ import annotations

from typing import Dict

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


def make_weights(model: Dict, seed: int, arch):
    """The whole weight tree on the default device, from ``seed``: the
    leaves of ``arch`` (a module of ``bench/arch``) in its fixed order, the leaf at position i drawn
    from ``fold_in(key, i)``, a std of 0 meaning all ones."""
    import jax
    import jax.numpy as jnp

    specs = arch.leaf_specs(model)

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
