"""Arithmetic the metric readers share: window selection, percentiles, and
the join of trace events with the benchmark's record of each step.

A reader (``bench/metrics/<name>.py``) gets the run as a dict:

* ``window``: ``(t0, t1)`` on ``perf_counter``, the measured window;
* ``records``: the load generator's record of every request;
* ``stats0`` / ``stats1``: the engine's counters at ``t0`` and ``t1``;
* ``setup_s``: seconds from process start to the first request;
* ``trace``: the reduced trace (``bench/trace.py``) or None;
* ``decode_calls`` / ``chunk_calls``: what each decode and chunk step
  worked on, by call number (the number in the step's host span);
* ``plan_ks``: plan name -> k per MoE layer; ``model``: the
  configuration; ``arch``: its architecture module (``bench/arch``, the one
  the configuration's ``"arch"`` names);
  ``expert_dtype``; ``peaks``: the chip's peaks.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

from bench import trace as tr
from bench.roofline import work


def pct(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile by nearest rank (no interpolation, so an
    infinite sample stays one sample); None for no values."""
    vals = sorted(values)
    if not vals:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return float(vals[rank - 1])


def in_window(run: Dict, t: float) -> bool:
    t0, t1 = run["window"]
    return t0 <= t <= t1


def token_times(run: Dict) -> List[float]:
    """Arrival times of every streamed token in the window."""
    return [t for r in run["records"] for t in r["tokens_t"]
            if in_window(run, t)]


def token_gaps(run: Dict) -> List[float]:
    """Seconds between consecutive streamed tokens of one request, for
    every pair whose both tokens arrived in the window."""
    out = []
    for r in run["records"]:
        ts = r["tokens_t"]
        out += [b - a for a, b in zip(ts, ts[1:])
                if in_window(run, a) and in_window(run, b)]
    return out


def due_in_window(run: Dict) -> List[Dict]:
    return [r for r in run["records"] if in_window(run, r["due"])]


def counter_delta(run: Dict, key: str) -> float:
    return float(run["stats1"].get(key, 0) - run["stats0"].get(key, 0))


# --------------------------------------------------------------------------- #
# trace joins
# --------------------------------------------------------------------------- #


def trace_window(run: Dict):
    return tr.window(run["trace"])


def step_runs(run: Dict, kind: str) -> List[Dict]:
    """The traced window's step runs of ``kind`` whose launching call is
    known (matched to its host span)."""
    if run.get("trace") is None:
        return []
    t0, t1 = trace_window(run)
    return [s for s in tr.steps(run["trace"], t0, t1)
            if s["kind"] == kind and s["span"] is not None]


def _call(run: Dict, step: Dict) -> Dict:
    n = int(step["span"].rsplit(".", 1)[1])
    return run[f"{step['kind']}_calls"][n]


def _layer_ks(run: Dict, plans: Sequence[str], moe_index: int) -> List[int]:
    return [run["plan_ks"][p][moe_index] for p in plans]


def kernel_work(run: Dict, kernel: str) -> List[Dict]:
    """Every traced call of ``kernel``: its operations, bytes and device
    time, with the layer it ran for.  A step program calls the kernel
    once in each layer its file's ``LAYERS`` names."""
    m, a = run["model"], run["arch"]
    mod = tr.KERNEL_MODULES[kernel]
    w = a.widths(m)
    moe = list(a.moe_layers(m))
    layers = moe if mod.LAYERS == "moe" else list(
        range(m["num_hidden_layers"]))
    out = []
    for step in step_runs(run, mod.STEP):
        call = _call(run, step)
        calls = [c for c in step["kernels"] if c[0] == kernel]
        if len(calls) != len(layers):
            continue            # not one call per layer: not this program
        for layer, (_, _, dur) in zip(layers, calls):
            ks = (_layer_ks(run, call["plans"], moe.index(layer))
                  if layer in moe else None)
            flops, nbytes = mod.work(w, call, ks, run["expert_dtype"])
            rec = (mod.record(w, call, ks, run["expert_dtype"])
                   if hasattr(mod, "record") else {})
            out.append(dict(rec, layer=layer, flops=flops, bytes=nbytes,
                            seconds=dur * 1e-9))
    return out


def roofline_pct(run: Dict, kernel: str) -> Optional[float]:
    """Least time over measured time, summed over the traced calls, %."""
    calls = kernel_work(run, kernel)
    spent = sum(c["seconds"] for c in calls)
    if not calls or spent <= 0:
        return None
    least = sum(work.least_seconds(c["flops"], c["bytes"], run["peaks"])
                for c in calls)
    return 100.0 * least / spent


def model_flops(run: Dict, kind: str) -> Optional[float]:
    """Model operations of the tokens the traced ``kind`` steps worked on."""
    steps = step_runs(run, kind)
    if not steps:
        return None
    m, flops = run["model"], run["arch"].model_flops
    total = 0.0
    for step in steps:
        call = _call(run, step)
        if kind == "decode":
            for plan, ctx in zip(call["plans"], call["ctx"]):
                total += flops(m, run["plan_ks"][plan], ctx)
        else:
            for plan, start, n in zip(call["plans"], call["starts"],
                                      call["tokens"]):
                for p in range(start, start + n):
                    total += flops(m, run["plan_ks"][plan], p + 1)
    return total


def mfu_pct(run: Dict, kind: str) -> Optional[float]:
    flops = model_flops(run, kind)
    if flops is None:
        return None
    t0, t1 = trace_window(run)
    return 100.0 * flops / ((t1 - t0) * 1e-9 * run["peaks"]["bf16_flops"])


def mean_step_ms(run: Dict, kind: str) -> Optional[float]:
    steps = step_runs(run, kind)
    if not steps:
        return None
    return sum(s["dur"] for s in steps) / len(steps) * 1e-6
