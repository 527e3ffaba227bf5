"""Share of the traced window in which no operation ran on the device, %."""

from bench import trace


def read(run):
    if run.get("trace") is None:
        return None
    t0, t1 = trace.window(run["trace"])
    return 100.0 * (1.0 - trace.busy_ns(run["trace"], t0, t1) / (t1 - t0))
