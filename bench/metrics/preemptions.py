"""Requests evicted from the KV pool in the window (engine counter)."""

from bench import measure


def read(run):
    return measure.counter_delta(run, "preemptions")
