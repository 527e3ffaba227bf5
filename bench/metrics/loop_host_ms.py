"""Host time of the serving loop around each engine step, ms: the
window's wall time in admission, retirement and the pump's handoff (its
wait for queued control actions and the engine lock) over its engine
iterations (program counters)."""

from bench import host_phases


def read(run):
    return host_phases.ms_per(
        run, ["engine.admit", "server.retire", "server.handoff"],
        "iterations")
