"""Window readings of the serving loop's own counters (``/v1/stats``).

The program keeps running sums in ``Engine.stats``: ``host_s:<phase>``,
the wall seconds of each phase of the pump's iteration;
``host_cpu_s``, the pump's CPU seconds outside the two waits;
``experts_routed:l<i>``, the distinct experts MoE layer ``i`` routed per
decode step, summed; and the step counts ``steps`` (decode),
``chunk_steps`` and ``iterations``.  The readers here take their deltas
over the window.  A program without these counters (one older than
them) reads None: the run's line then leaves the metric out.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional

from bench import measure

WALL = "host_s:"
CPU = "host_cpu_s"
ROUTED = "experts_routed:l"


def _has(run: Dict, keys: Iterable[str]) -> bool:
    return all(k in run["stats0"] and k in run["stats1"] for k in keys)


def ms_per(run: Dict, phases: List[str], per: str) -> Optional[float]:
    """Window wall time of ``phases`` over the window's Δ``per``, in ms."""
    keys = [WALL + p for p in phases]
    if not _has(run, keys + [per]):
        return None
    n = measure.counter_delta(run, per)
    if n <= 0:
        return None
    return 1e3 * sum(measure.counter_delta(run, k) for k in keys) / n


def cpu_share(run: Dict) -> Optional[float]:
    """The pump's CPU time over the wall time of its non-wait phases, %."""
    keys = [k for k in run["stats1"]
            if k.startswith(WALL) and not k.endswith(".wait")]
    if not keys or not _has(run, keys + [CPU]):
        return None
    wall = sum(measure.counter_delta(run, k) for k in keys)
    if wall <= 0:
        return None
    return 100.0 * measure.counter_delta(run, CPU) / wall


def experts_per_layer_step(run: Dict) -> Optional[float]:
    """Distinct experts routed per MoE layer and decode step."""
    keys = [k for k in run["stats1"] if k.startswith(ROUTED)]
    if not keys or not _has(run, keys + ["steps"]):
        return None
    steps = measure.counter_delta(run, "steps")
    if steps <= 0:
        return None
    return (sum(measure.counter_delta(run, k) for k in keys)
            / (steps * len(keys)))
