"""Mean live slots per decode step in the window: the window's decode
tokens over its decode steps (engine counters)."""

from bench import measure


def read(run):
    steps = measure.counter_delta(run, "steps")
    if steps <= 0:
        return None
    return measure.counter_delta(run, "decode_tokens") / steps
