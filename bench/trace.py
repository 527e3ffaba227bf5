"""Reduction of a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three event lists, each ``[name, start_ns, dur_ns]`` on the trace's one
clock:

* ``ops``: the device's ``XLA Ops`` line (every HLO operation run);
* ``modules``: the device's ``XLA Modules`` line (every program run);
* ``host``: the benchmark's own host spans (names starting ``bench.``),
  which it wraps around the engine's and the runner's calls.

A program run is told apart by the kernels inside it (each kernel file
under ``bench/roofline/kernels/`` says which step its calls make: the
paged decode kernel or the fused routed-expert kernel make a decode step,
the grouped-matmul kernel a chunk step), and a kernel by its custom call's
instruction name, which carries the kernel wrapper's name
(``%moe_decode.15 = bf16[16,2048]{...} custom-call(s32[16,8]...``).  Each
step run is matched to the host span of the call that launched it: the
last span of its kind that began before the run did (the engine waits for
every step's tokens, so steps never overlap).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from bench.roofline import kernels

#: kernel -> its file's module (``bench/roofline/kernels/<kernel>.py``)
KERNEL_MODULES = kernels.load_all()
#: custom-call instruction base name -> kernel, for the kernels measured
KERNELS = {op: name for name, mod in KERNEL_MODULES.items()
           for op in mod.OPS}
#: which kernels make a program run a decode or a chunk step
STEP_KIND = {name: mod.STEP for name, mod in KERNEL_MODULES.items()}

_OP = re.compile(r"^%(?P<base>[A-Za-z_][A-Za-z0-9_\-]*?)(?:\.\d+)? = ")
_OPCODE = re.compile(r"^ (?P<opcode>[a-z][a-z\-]*)\((?P<args>.*)$")

Event = List  # [name, start_ns, dur_ns]


def load(trace_dir: str) -> Dict:
    """The reduced trace of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = {"ops": [], "modules": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    out[key] += [[ev.name, float(ev.start_ns),
                                  float(ev.duration_ns)]
                                 for ev in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"] += [[ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)]
                                for ev in line.events
                                if ev.name.startswith("bench.")]
    for key in out:
        out[key].sort(key=lambda e: e[1])
    return out


def window(red: Dict) -> Tuple[float, float]:
    """The traced window: the ``bench.window`` host span."""
    for name, start, dur in red["host"]:
        if name == "bench.window":
            return start, start + dur
    raise ValueError("the trace has no bench.window span")


def clip(events: List[Event], t0: float, t1: float) -> List[Event]:
    """Events that start inside ``[t0, t1)``."""
    return [e for e in events if t0 <= e[1] < t1]


def union(intervals) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` intervals of ``[name, start, dur]`` events."""
    spans = sorted((e[1], e[1] + e[2]) for e in intervals if e[2] > 0)
    merged: List[List[float]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(red: Dict, t0: float, t1: float) -> float:
    """Time in ``[t0, t1)`` in which some operation ran on the device."""
    total = 0.0
    for a, b in union(red["ops"]):
        a, b = max(a, t0), min(b, t1)
        if b > a:
            total += b - a
    return total


def parse_op(name: str) -> Optional[Dict]:
    """``{"base", "opcode", "out", "args"}`` of an XLA Ops event name
    (``%<base>.<n> = <out> <opcode>(<args>``; ``out`` may be a tuple)."""
    m = _OP.match(name)
    if not m:
        return None
    rest = name[m.end():]
    if rest.startswith("("):            # a tuple: up to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        out, rest = rest[:i + 1], rest[i + 1:]
    else:
        out, _, rest = rest.partition(" ")
        rest = " " + rest
    op = _OPCODE.match(rest)
    if not op:
        return None
    return {"base": m["base"], "opcode": op["opcode"], "out": out,
            "args": op["args"]}


def kernel_of(name: str) -> Optional[str]:
    """The measured kernel an op event runs, or None."""
    op = parse_op(name)
    if op is None or op["opcode"] != "custom-call":
        return None
    return KERNELS.get(op["base"])


def base_name(name: str) -> str:
    """An op's instruction name without its number (for the breakdown)."""
    op = parse_op(name)
    return op["base"] if op else name.split("(")[0]


def steps(red: Dict, t0: float, t1: float) -> List[Dict]:
    """Step program runs in the window, each with its kind, its kernel
    calls (in order) and the host span that launched it.

    ``{"kind": "decode"|"chunk", "start", "dur", "span": name or None,
    "kernels": [[kernel, op name, dur_ns], ...]}``
    """
    ops = clip(red["ops"], t0, t1)
    mods = clip(red["modules"], t0, t1)
    out = []
    j = 0
    for name, start, dur in mods:
        end = start + dur
        while j < len(ops) and ops[j][1] < start:
            j += 1
        calls = []
        k = j
        while k < len(ops) and ops[k][1] < end:
            kern = kernel_of(ops[k][0])
            if kern:
                calls.append([kern, ops[k][0], ops[k][2]])
            k += 1
        kinds = {STEP_KIND[c[0]] for c in calls}
        if len(kinds) != 1:
            continue
        kind = kinds.pop()
        out.append({"kind": kind, "start": start, "dur": dur,
                    "span": _launcher(red["host"], kind, start),
                    "kernels": calls})
    return out


def _launcher(host: List[Event], kind: str, start: float) -> Optional[str]:
    prefix = f"bench.{kind}."
    best = None
    for name, s, _ in host:
        if s > start:
            break
        if name.startswith(prefix):
            best = name
    return best


def top_ops(red: Dict, t0: float, t1: float, n: int = 10):
    """``[[op, seconds], ...]``: the ops that took most device time."""
    tot: Dict[str, float] = {}
    for name, _, dur in clip(red["ops"], t0, t1):
        b = base_name(name)
        tot[b] = tot.get(b, 0.0) + dur
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(red: Dict, t0: float, t1: float, n: int = 10):
    """``[[host span, seconds], ...]``: idle device time, summed by the
    innermost benchmark span open on the host during each gap (``idle``
    when none was), longest first."""
    busy = [(max(a, t0), min(b, t1)) for a, b in union(red["ops"])
            if b > t0 and a < t1]
    gaps = []
    cur = t0
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < t1:
        gaps.append((cur, t1))
    spans = [(s, s + d, _span_label(nm)) for nm, s, d in red["host"]
             if nm != "bench.window"]
    tot: Dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        inner = None
        for s, e, label in spans:
            if s <= mid < e and (inner is None or e - s < inner[1] - inner[0]):
                inner = (s, e, label)
        label = inner[2] if inner else "idle"
        tot[label] = tot.get(label, 0.0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def _span_label(name: str) -> str:
    """``bench.decode.17`` -> ``bench.decode`` (drop the call number)."""
    head, _, tail = name.rpartition(".")
    return head if tail.isdigit() else name
