"""Plain reference of the served model, and the comparison behind ``correct``.

A straightforward float32 forward pass (matmuls at ``highest`` precision)
over one whole sequence, with no kernel, cache, paging or batching.  It
imports nothing of the program and takes nothing the program made: it
makes the configuration's weights again from the run's seed
(``bench/weights.py``) and quantizes expert tiles itself where the
configuration serves them quantized.  The layer equations are the
architecture's (``bench/arch/<arch>.py``, named by the configuration).

The comparison: for every served token, how far its reference logit lies
below the reference's best at that position.  That is valid for greedy
tokens, so only greedy requests are compared.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def gaps(ref_logits: np.ndarray, chosen: Sequence[int]) -> np.ndarray:
    """Per position: the reference's best logit minus the chosen token's."""
    chosen = np.asarray(chosen)
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(chosen)), chosen]


def served_gaps(weights, model, prompt: List[int], served: List[int], ks,
                *, arch, expert_dtype=None, control_dense=None,
                control_experts=None) -> Dict[str, np.ndarray]:
    """Gaps of the served tokens (``served``), and, when a control
    precision is given, of the tokens the control would put first at the
    same positions.  ``arch`` (a module of ``bench/arch``) gives the
    reference's ``logits``."""
    seq = list(prompt) + list(served[:-1])
    lo = len(prompt) - 1
    ref = arch.logits(weights, model, seq, ks,
                      expert_dtype=expert_dtype)[lo:]
    out = {"served": gaps(ref, served)}
    if control_dense is not None or control_experts is not None:
        ctl = arch.logits(weights, model, seq, ks,
                          expert_dtype=control_experts or expert_dtype,
                          dense_dtype=control_dense)[lo:]
        out["control"] = gaps(ref, ctl.argmax(axis=-1))
    return out
