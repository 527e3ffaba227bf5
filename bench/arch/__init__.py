"""What the harness knows of one model architecture, found by name.

A configuration file names its architecture with ``"arch"``, and
``load(name)`` finds ``bench/arch/<name>.py``.  Every model formula the
benchmark uses lives there, so a new architecture is a new file beside
the others (and its kernels are files under ``bench/roofline/kernels/``).
A module gives:

* ``leaf_specs(model)``: ``(path, shape, dtype, std)`` of every weight, in
  the layout the serving runner takes (``bench/weights.py`` makes them);
* ``logits(weights, model, tokens, ks, *, expert_dtype, dense_dtype,
  pad_to)``: the plain float32 forward pass at ``highest`` precision, as
  ``bench/reference.py`` calls it;
* ``program_keys(cfg)``: ``{file key: value}`` read from the program's
  ``ModelConfig``, which must equal the configuration file's;
* ``moe_layers(model)``: the indices of the layers that carry a plan's k,
  in order (a plan's i-th k is for the i-th of them);
* ``widths(model)``: the widths the kernel files read, by the names they
  use (``d_model``, ``experts``, ``expert_ff``, ``heads``, ``kv_heads``,
  ``head_dim``, ...);
* ``model_flops(model, ks, ctx)``: forward operations of one token that
  attends ``ctx`` positions under per-MoE-layer expert counts ``ks``.

``model`` is the configuration file's object.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))

_LOADED: Dict[str, object] = {}


def load(name: str, arch_dir: Optional[str] = None):
    """The module of architecture ``name``, loaded once per file (its
    jitted functions keep their compiled programs across calls)."""
    path = os.path.join(arch_dir or HERE, name + ".py")
    if path not in _LOADED:
        if not os.path.isfile(path):
            raise KeyError(f"no architecture module {path}")
        spec = importlib.util.spec_from_file_location(f"bench_arch_{name}",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def of(model: Dict, arch_dir: Optional[str] = None):
    """The module the configuration ``model`` names."""
    return load(model["arch"], arch_dir)
