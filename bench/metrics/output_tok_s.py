"""Streamed output tokens that reached clients in the window, per second."""

from bench import measure


def read(run):
    t0, t1 = run["window"]
    return len(measure.token_times(run)) / (t1 - t0)
