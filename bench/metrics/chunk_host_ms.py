"""Host time of one chunked-prefill step outside the wait for the device,
ms: the window's wall time in the chunk step's prepare, dispatch, sample
and commit phases over its chunk steps (program counters)."""

from bench import host_phases


def read(run):
    return host_phases.ms_per(
        run, ["engine.chunk.prepare", "engine.chunk.dispatch",
              "engine.chunk.sample", "engine.chunk.commit"], "chunk_steps")
