"""The pump thread's CPU time over the wall time of its phases, the two
waits on the device left out, in the window, %: under 100% where the
pump waits for the GIL or the engine lock (program counters)."""

from bench import host_phases


def read(run):
    return host_phases.cpu_share(run)
