"""The measured kernels, one file each, found by name.

``bench/roofline/kernels/<kernel>.py`` gives:

* ``OPS``: the custom-call instruction base names in the device trace
  that are calls of this kernel (``moe_decode``, ``moe_decode_quant``);
* ``STEP``: the step program a call of it makes, ``"decode"`` or
  ``"chunk"`` (``bench/trace.py`` tells programs apart by their kernels);
* ``LAYERS``: which layers call it once per step: ``"all"`` or ``"moe"``
  (those the architecture's ``moe_layers`` lists);
* ``work(w, call, ks, dtype) -> (operations, bytes)``: the work one call
  needs, ``w`` being the architecture's ``widths``, ``call`` the step's
  record (``bench/measure.py``), ``ks`` each live slot's k in this layer
  (None in a layer without experts) and ``dtype`` the expert dtype;
* optionally ``record(w, call, ks, dtype) -> dict``: more numbers kept
  with each call.

The counts are of the work the layer needs, not of what today's kernel
does, so a kernel that does less reads higher and the yardstick does not
move.  A new kernel's roofline is its file here and a reader under
``bench/metrics/``.
"""

from __future__ import annotations

import glob
import importlib.util
import os
from typing import Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


def load_all(kernel_dir: Optional[str] = None) -> Dict[str, object]:
    """kernel name -> its module, for every file in ``kernel_dir``."""
    out = {}
    for path in sorted(glob.glob(os.path.join(kernel_dir or HERE, "*.py"))):
        name = os.path.basename(path)[:-3]
        if name.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            f"bench_kernel_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[name] = mod
    return out
