"""Host time of one decode step outside the wait for the device, ms: the
window's wall time in the decode step's prepare, dispatch, sample and
commit phases over its decode steps (program counters)."""

from bench import host_phases


def read(run):
    return host_phases.ms_per(
        run, ["engine.decode.prepare", "engine.decode.dispatch",
              "engine.decode.sample", "engine.decode.commit"], "steps")
