"""Spans and counters for the phases of the serving loop.

``phase(stats, name, **meta)`` marks one phase of the pump's work.  It
opens ``jax.profiler.TraceAnnotation(name, **meta)``, a span on the
profiler's host plane, on the same clock as the device's ``XLA Ops``
line, so a traced window can put each idle gap on the device down to
the phase the host was in.  With no profiler session running the span
costs under a microsecond.

With a ``stats`` dict it also adds the phase's wall time to
``stats["host_s:<name>"]`` and, for every phase but a wait (a name that
ends in ``.wait``: the host blocked on the device), the calling thread's
CPU time to ``stats["host_cpu_s"]``.  ``stats=None`` makes a span only,
for phases that run outside the engine lock or that enclose other
phases.  Spans and counters are always on; there is nothing to switch.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

from jax.profiler import TraceAnnotation

#: the counter of the pump thread's CPU time in non-wait phases
CPU_KEY = "host_cpu_s"
#: prefix of each phase's wall-time counter
WALL_PREFIX = "host_s:"

#: every phase that has a counter: the leaves of one pump iteration
PHASES = (
    "engine.admit",
    "engine.chunk.prepare", "engine.chunk.dispatch", "engine.chunk.sample",
    "engine.chunk.wait", "engine.chunk.commit",
    "engine.decode.prepare", "engine.decode.dispatch",
    "engine.decode.sample", "engine.decode.wait", "engine.decode.commit",
    "server.retire", "server.handoff",
)


class phase:
    """Context manager: a host span, and (with ``stats``) the phase's
    wall and CPU time added to its counters on exit."""

    __slots__ = ("stats", "name", "span", "t0", "c0")

    def __init__(self, stats: Optional[Dict[str, float]], name: str,
                 **meta):
        self.stats = stats
        self.name = name
        self.span = TraceAnnotation(name, **meta)

    def __enter__(self) -> "phase":
        self.span.__enter__()
        if self.stats is not None:
            # the CPU reading nests inside the wall reading
            self.t0 = time.perf_counter()
            self.c0 = time.thread_time()
        return self

    def __exit__(self, *exc) -> None:
        stats = self.stats
        if stats is not None:
            cpu = time.thread_time() - self.c0
            wall = time.perf_counter() - self.t0
            key = WALL_PREFIX + self.name
            stats[key] = stats.get(key, 0.0) + wall
            if not self.name.endswith(".wait"):   # host blocked on device
                stats[CPU_KEY] = stats.get(CPU_KEY, 0.0) + cpu
        self.span.__exit__(*exc)
